"""Compile the serving hot path for a described TPU v5e chip.

Nothing runs: the TPU compiler installed beside JAX lowers and compiles
each program for ``v5e:2x2``'s first chip from shapes alone, which is what
interpret-mode tests cannot show (block shapes the Mosaic lowering refuses,
programs too large for the chip's memory).  The kernel ops pick interpret
mode from the live backend, so each test steers ``backend.interpret`` to
the compiled path.  Keep every such compile in this one file: the topology
is described inside a fixture, by the one worker that runs this file.
"""
import dataclasses
import importlib.util
import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import backend
from repro.kernels.paged_attention.ops import (
    paged_attention_op,
    paged_verify_attention_op,
    scatter_kv_pages,
)
from repro.models import transformer
from repro.serving.engine import (
    fused_paged_verify,
    fused_paged_verify_specs,
    paged_prefill,
)

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """Skips only where the TPU compiler is not installed; any other fault
    in describing the chip fails the tests that need it."""
    from jax.experimental import topologies

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without that chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compiled_path(monkeypatch, no_compile_cache):
    monkeypatch.setattr(backend, "interpret", lambda: False)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES


# qwen2-7b head geometry: 28 q heads over 4 KV heads (G=7), D=128, P=256
H, HKV, D, P = 28, 4, 128, 256


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_compiles_for_v5e(one_chip, compiled_path, dtype):
    B, n_pages, n_max = 16, 33, 8
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = (S((B, H, D), dtype), S((n_pages, HKV, P, D), dtype),
            S((n_pages, HKV, P, D), dtype), S((B, n_max), jnp.int32),
            S((B,), jnp.int32))
    _check(jax.jit(paged_attention_op).lower(*args).compile())


def test_scatter_kv_pages_compiles_for_v5e(one_chip, compiled_path):
    """The layer's write-then-read pair: new K/V scattered through the
    block table into a bf16 pool, then the flattened verify attention."""
    B, T, n_pages, n_max = 4, 7, 33, 8
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((n_pages, HKV, P, D), jnp.bfloat16)
    new = S((B, T, HKV, D), jnp.float32)
    lens = S((B,), jnp.int32)

    def write_then_attend(q, kp, vp, k, v, bt, base, tl):
        kp, vp = scatter_kv_pages(kp, vp, k, v, bt, base, tl)
        return paged_verify_attention_op(q, kp, vp, bt, base), kp, vp

    compiled = jax.jit(write_then_attend).lower(
        S((B, T, H, D), jnp.bfloat16), pool, pool, new, new,
        S((B, n_max), jnp.int32), lens, lens,
    ).compile()
    assert "scatter" in compiled.as_text()
    _check(compiled)


def test_fused_paged_verify_compiles_for_v5e(one_chip, compiled_path):
    """The engine's per-epoch program at qwen2-7b's published widths, cut
    to 2 layers (depth is a scan: it moves compile time, not shapes)."""
    cfg = dataclasses.replace(get_config("qwen2-7b-14l"), n_layers=2)
    specs = fused_paged_verify_specs(
        cfg, batch=4, k=6, n_pages=17, n_max=4, top_c=64, sharding=one_chip,
    )
    program = fused_paged_verify(
        lambda *a: transformer.decode_paged(cfg, *a)
    )
    _check(program.lower(*specs, method="residual", tagged=True).compile())


#: ops that copy a pool, or cut a layer's slice out of the stacked pools
#: or write one back: matched by opcode, or by the name the compiler gives
#: a fusion built around one (``copy_bitcast_fusion``,
#: ``constant_dynamic-slice_fusion``, ``copy_dynamic-update-slice_fusion``)
POOL_COPY_OPS = ("copy", "dynamic-slice", "dynamic-update-slice")
_INSTR = re.compile(r"%(\S+) = \w+\[([\d,]*)\]\S* (\S+?)\(")


def _pool_copies(hlo: str, shapes) -> list:
    bad = []
    for name, dims, op in _INSTR.findall(hlo):
        shape = tuple(int(d) for d in dims.split(",") if d)
        if shape in shapes and any(k in op or k in name
                                   for k in POOL_COPY_OPS):
            bad.append(name)
    return bad


@pytest.mark.parametrize("program", ["verify", "prefill"])
def test_paged_layer_scan_keeps_pools_in_place_for_v5e(
        one_chip, compiled_path, program):
    """The verify program and the prefill program (GShard MoE routing,
    as the engine builds it) at qwen2-7b's widths write the bf16 pools in
    place: no temp as large as one layer's slice of one pool (nor the
    prefill's (B, T, V) logits, which stay fused into its first tokens),
    and no copy, dynamic-slice or dynamic-update-slice that produces a
    whole pool or a layer's slice.  Each flat pool (514 pages of 256 KiB)
    is larger than the chip's 128 MiB of VMEM, as at the cell's size: a
    smaller one may be staged whole into VMEM and back, which is a
    placement a real pool never gets, not a per-layer copy."""
    n_layers, n_pages, B, T = 2, 257, 4, 64
    cfg = dataclasses.replace(get_config("qwen2-7b-14l"), n_layers=n_layers)
    specs = fused_paged_verify_specs(
        cfg, batch=B, k=6, n_pages=n_pages, n_max=8, top_c=64,
        cache_dtype=jnp.bfloat16, sharding=one_chip,
    )
    if program == "verify":
        compiled = fused_paged_verify(
            partial(transformer.decode_paged, cfg)
        ).lower(*specs, method="residual", tagged=True).compile()
    else:
        S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                   sharding=one_chip)
        params, _, pool, _, bt, lens = specs[:6]
        compiled = paged_prefill(
            partial(transformer.decode_paged, cfg, dropless=False)
        ).lower(params, S((B, T), jnp.int32), pool, pool, bt, lens, lens,
                None).compile()
    _check(compiled)
    page = specs[2].shape[2:]                      # (Hkv, P, D)
    layer_slice = n_pages * math.prod(page) * 2    # bytes of bf16
    assert compiled.memory_analysis().temp_size_in_bytes < layer_slice
    shapes = {(n_layers, n_pages, *page), (n_layers * n_pages, *page),
              (n_pages, *page), (1, n_pages, *page)}
    assert _pool_copies(compiled.as_text(), shapes) == []


@pytest.mark.parametrize("program", ["verify", "prefill"])
def test_moe_programs_run_grouped_experts_for_v5e(one_chip, compiled_path,
                                                   program):
    """deepseek-moe-16b at 7 layers and its published widths (64 experts
    of 1408, top-6, 2 shared), with a pool of 33 pages (231 MiB a pool,
    more than the chip's VMEM): both paged programs compile with the
    grouped expert kernel (`moe_gmm`) and build no expert buffer, neither
    GShard's at capacity nor a dropless one at the row length:
    ``(B, E * C, D)``, ``(B, E, C, D)`` or ``(B, E, C, F)``."""
    from repro.models.moe import expert_capacity

    cfg = dataclasses.replace(get_config("deepseek-moe-16b"), n_layers=7)
    B, n_pages = 4, 33
    T = 7 if program == "verify" else 48
    specs = fused_paged_verify_specs(
        cfg, batch=B, k=T - 1, n_pages=n_pages, n_max=8, top_c=8,
        cache_dtype=jnp.bfloat16, sharding=one_chip,
    )
    decode = partial(transformer.decode_paged, cfg)
    if program == "verify":
        compiled = fused_paged_verify(decode).lower(
            *specs, method="residual", tagged=True).compile()
    else:
        S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                   sharding=one_chip)
        params, _, pool, _, bt, lens = specs[:6]
        compiled = paged_prefill(decode).lower(
            params, S((B, T), jnp.int32), pool, pool, bt, lens, lens,
            None).compile()
    _check(compiled)
    hlo = compiled.as_text()
    assert re.search(r"%moe_gmm_gated\S* = .*custom-call", hlo)
    assert re.search(r"%moe_gmm_down\S* = .*custom-call", hlo)
    m, D = cfg.moe, cfg.d_model
    E, F = m.num_experts, m.d_expert
    buffers = set()
    for C in (T, expert_capacity(m, T)):
        buffers |= {(B, E * C, D), (B, E, C, D), (B, E, C, F)}
    shapes = {tuple(int(d) for d in dims.split(",") if d)
              for _, dims, _ in _INSTR.findall(hlo)}
    assert not shapes & buffers
