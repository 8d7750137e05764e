"""Pallas TPU kernel: grouped matmul over experts' contiguous row groups.

The serving MoE layer (`repro.models.moe.moe_serve`) sorts its (token,
expert) assignments by expert, so expert ``g``'s rows are the contiguous
group ``[offsets[g], offsets[g + 1])`` of ``x``.  The grid walks
``(n tile, row-tile visit, k tile)``: each visit multiplies one ``(tm, tk)``
tile of rows by its group's ``(tk, tn)`` weight tile, and a row tile that
two groups share is visited once for each, every visit storing only its
own group's rows.  The visit list is megablox's (`make_group_metadata`):
empty groups get no visit, and rows after the last group (padding) are
never computed, so the work grows with the routed tokens and not with the
expert count.

The weights come stacked over layers, ``(L, E, K, N)``, with the layer
to use as a scalar: the paged layer scan hands every layer the whole
stack, so no layer's experts are cut out of it and copied (a kernel's
operand cannot be a slice fused into it, as a dot's can).

Two forms, one kernel:
  * gated — ``silu(x @ w_gate[g]) * (x @ w_up[g])``, the first half of an
    expert's SwiGLU, with both weights read in one pass over ``x``;
  * plain — ``x @ w[g]``, the expert's down projection.

Every row's dot accumulates over the same ``tk`` tiles in the same order
whatever the other rows are, so a row's result does not depend on the
batch it came in (verification relies on that).  Dots accumulate in
float32; float32 operands take ``HIGHEST`` precision (the MXU's default
is one bf16 pass), bf16 operands the default, as in the paged kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

#: scoped VMEM for one call: the gated form at tk = 1024 double-buffers two
#: (1024, 1408) bf16 weight tiles (11.5 MiB) beside two f32 accumulators
VMEM_LIMIT = 64 << 20


def _kernel(meta, _layer, x_ref, *refs, gated: bool, tiles_k: int, tm: int,
            precision):
    offsets, group_ids, m_tile_ids = meta
    if gated:
        wg_ref, wu_ref, o_ref, acc_g, acc_u = refs
    else:
        w_ref, o_ref, acc = refs
    grid_id = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        if gated:
            acc_g[...] = jnp.zeros_like(acc_g)
            acc_u[...] = jnp.zeros_like(acc_u)
        else:
            acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)
    if gated:
        acc_g[...] += dot(x, wg_ref[...])
        acc_u[...] += dot(x, wu_ref[...])
    else:
        acc[...] += dot(x, w_ref[...])

    @pl.when(k == tiles_k - 1)
    def _store():
        g = group_ids[grid_id]
        row = (jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
               + m_tile_ids[grid_id] * tm)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        val = jax.nn.silu(acc_g[...]) * acc_u[...] if gated else acc[...]
        o_ref[...] = jnp.where(mine, val, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def _tile(dim: int, want: int) -> int:
    """The largest tile of at most ``want`` that divides ``dim`` in whole
    lanes (128), or ``dim`` itself."""
    if dim <= want:
        return dim
    for t in range(want - want % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def grouped_matmul(x, ws, group_sizes, layer, *, tm: int, tk: int, tn: int,
                   out_dtype, interpret: bool = False):
    """``x`` (M, K) rows sorted by group; ``ws`` one (L, G, K, N) weight
    (plain) or two (gated), of which layer ``layer`` ((1,) int32) is used;
    ``group_sizes`` (G,) int32 with sum <= M.  Returns (M, N)
    ``out_dtype``; rows past the last group are left unwritten."""
    M, K = x.shape
    _, G, _, N = ws[0].shape
    if M % tm:
        raise ValueError(f"{M} rows are not a whole number of {tm}-row tiles")
    tk, tn = _tile(K, tk), _tile(N, tn)
    tiles_k, tiles_n = K // tk, N // tn
    meta, n_visits = make_group_metadata(
        group_sizes=group_sizes, m=M, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=G,
        visit_empty_groups=False)
    gated = len(ws) == 2
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    kernel = functools.partial(_kernel, gated=gated, tiles_k=tiles_k, tm=tm,
                               precision=precision)
    x_spec = pl.BlockSpec((tm, tk), lambda n, i, k, meta, l: (meta[2][i], k))
    w_spec = pl.BlockSpec((None, None, tk, tn),
                          lambda n, i, k, meta, l: (l[0], meta[1][i], k, n))
    o_spec = pl.BlockSpec((tm, tn), lambda n, i, k, meta, l: (meta[2][i], n))
    acc = [pltpu.VMEM((tm, tn), jnp.float32)] * len(ws)
    w_bytes = sum(w.dtype.itemsize for w in ws) * K * N
    cost = pl.CostEstimate(
        flops=2 * M * K * N * len(ws), transcendentals=M * N if gated else 0,
        bytes_accessed=(x.size * x.dtype.itemsize * tiles_n + w_bytes * G
                        + M * N * jnp.dtype(out_dtype).itemsize))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles_n, n_visits, tiles_k),
            in_specs=[x_spec] + [w_spec] * len(ws),
            out_specs=o_spec,
            scratch_shapes=acc,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=cost,
        interpret=interpret,
        name="moe_gmm_gated" if gated else "moe_gmm_down",
    )(meta, layer, x, *ws)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_gmm(
    x,                 # (M, D) assignment rows sorted by expert
    w_gate,            # (L, E, D, F) stacked over layers
    w_up,              # (L, E, D, F)
    w_down,            # (L, E, F, D)
    group_sizes,       # (E,) int32 rows per expert, sum <= M
    layer,             # (1,) int32 the layer's index in the stack
    *,
    tm: int,
    interpret: bool = False,
):
    """The routed experts' SwiGLU over sorted rows: (M, D) float32, each
    row computed by its own group's expert; rows past the last group
    (padding) come back unspecified."""
    h = grouped_matmul(x, (w_gate, w_up), group_sizes, layer, tm=tm,
                       tk=1024, tn=2048, out_dtype=x.dtype,
                       interpret=interpret)
    return grouped_matmul(h, (w_down,), group_sizes, layer, tm=tm, tk=2048,
                          tn=1024, out_dtype=jnp.float32, interpret=interpret)
