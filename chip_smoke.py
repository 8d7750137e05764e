#!/usr/bin/env python3
"""Smoke run of the WISP verification server on a TPU.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the verifier fleet on four chips

One chip: the served path at qwen2-7b-14l's published widths (random
weights from ``--seed``) through ``run_serving`` -- event-driven cluster
runtime, ``wisp`` policy, chunked prefill, compact-q residual verification,
edges drafting with the target's own params -- checked session by session
against the solo lock-step replay oracle (`repro.serving.oracle`); drafts
the target rejects, submitted through ``WISPServer``, to run rollback and
tail-page trimming; the paged-attention kernel against its reference in
f32 and bf16; and the fused verify program's compiled text.

Four chips: ``run_serving(verifiers=4)`` with one replica per chip, and
again with all four on chip 0 sharing one params copy; the committed
streams must be byte-identical.  Nothing else runs with this option.

Every line before the last is a smoke diagnostic, not a benchmark number.
The last line is ``{"ok": true, "device": {...}}``; it is printed only when
every phase passed.  Without a TPU the script exits non-zero before any
phase.  Everything runs in this one process: a child could not reach a
chip its parent holds.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TARGET = "qwen2-7b-14l"
#: max |kernel - reference| on the chip, about twice the v5e readings
#: (5.1e-7 in f32, where both sides run their dots at "highest"; 1.95e-3 in
#: bf16, one ulp of the bf16 output).  Unpinned f32 dots would read 4e-3.
KERNEL_TOL = {"float32": 1e-6, "bfloat16": 4e-3}

_compile_s: collections.Counter = collections.Counter()


def _on_duration(event: str, seconds: float, **_) -> None:
    if event.endswith("backend_compile_duration"):
        _compile_s["total"] += seconds


def diag(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[smoke diagnostic] {phase}: {body}", flush=True)


def served_phase(target: str = TARGET, *, reduced: bool = False,
                 seed: int = 0, devices: int = 4, rounds: int = 3,
                 prompt_len: int = 300, max_len: int = 512, k_max: int = 6,
                 q_top_c: int = 64, chunk: int = 128, verbose: bool = True):
    """The served main path; every session's committed stream must equal
    its solo lock-step replay.  Returns ``(report, result)``."""
    from repro.launch.serve import run_serving
    from repro.serving.oracle import replay_session

    r = run_serving(
        target, reduced=reduced, devices=devices, rounds=rounds,
        k_max=k_max, policy="wisp", prompt_len=prompt_len, max_len=max_len,
        seed=seed, verbose=verbose, self_draft=True, method="residual",
        q_mode="compact", q_top_c=q_top_c, prefill_mode="chunked",
        prefill_chunk_tokens=chunk,
    )
    server, edges, m = r["server"], r["edges"], r["metrics"]
    engine = server.engine
    vocab = engine.cfg.vocab
    assert prompt_len > engine.page_size, "prompts must span several pages"
    answered = [e for e in edges if e.session is not None
                and e.session.resolved > 0]
    assert len(answered) == devices, (
        f"{len(answered)} of {devices} sessions got a verdict")
    for e in edges:
        toks = np.asarray(e.session.committed)
        assert ((toks >= 0) & (toks < vocab)).all(), "token out of vocab"
    for i, e in enumerate(edges):
        sid = e.session.session_id
        its = sorted((it for it in m.iterations if it.session_id == sid),
                     key=lambda it: it.round_index)
        want = [int(t) for t in e.response_tokens]
        got = replay_session(
            engine.cfg, engine.params, e.cfg, e.params,
            prompt=e.session.committed[:e.session.prompt_len],
            k_schedule=[it.k_used for it in its], session_id=sid,
            device_seed=seed + 10 + i, engine_seed=0, k_max=k_max,
            q_mode="compact", q_top_c=q_top_c, max_len=max_len,
        )
        assert got == want, (
            f"session {sid} diverged from its solo replay: "
            f"{got[:8]} vs {want[:8]}")
    total = r["total"]
    report = {
        "sessions_answered": len(answered),
        "tokens_committed": total.committed,
        "acceptance": total.acceptance_rate,
        "oracle_replays_matched": len(edges),
        "stats": dict(engine.stats),
        "dispatch_counts": dict(engine.dispatch_counts),
    }
    return report, r


def rejected_draft_phase(cfg, params, *, seed: int = 0, k: int = 6,
                         rounds: int = 3, top_c: int = 1):
    """Drafts the target rejects, through ``WISPServer.submit``/``step``.

    Each block claims probability 1 for random tokens the target gives
    about 1/V, so the residual rule accepts a token with probability p/q:
    ``accept_len < K``, the length pointer rolls back, and the block's
    pages past the rollback are released.  The prompt ends two tokens short
    of a page, so every block reaches into a page that rollback frees."""
    from repro.core.estimator import analytic_tpu_coeffs
    from repro.core.speculative import CompactQ
    from repro.serving.engine import VerificationEngine
    from repro.serving.server import WISPServer
    from repro.serving.transport import NetworkModel

    engine = VerificationEngine(cfg, params, max_slots=1, max_len=512,
                                method="residual", seed=seed)
    P = engine.page_size
    server = WISPServer(engine, analytic_tpu_coeffs(cfg), policy="wisp",
                        network=NetworkModel())
    rng = np.random.default_rng(seed)
    prompt = rng.integers(2, cfg.vocab, size=P - 2).tolist()
    handle = server.open_session(0, prompt, queue_on_full=False)
    assert handle.first_token is not None
    slot = server.sessions[0].slot
    now, verdicts = 0.0, []
    for _ in range(rounds):
        fed = int(engine.fed[slot])
        assert fed + k + 1 > P * engine.kv.seq_pages(slot), (
            "the block must reach into a page of its own")
        draft = rng.integers(2, cfg.vocab, size=k).astype(np.int32)
        q = CompactQ(
            logq_tok=np.zeros(k, np.float32),
            top_idx=draft[:, None].astype(np.int32),
            top_logq=np.zeros((k, top_c), np.float32),
            tail=np.zeros(k, np.float32),
        )
        server.submit(0, draft, q_compact=q, now=now, t_draft=0.0,
                      t_network=0.0)
        while server.queue_depth:
            verdicts += server.step(now)
            now += 0.005
        server.pop_events()
        v = verdicts[-1]
        assert v.accept_len < k, f"accept_len {v.accept_len} == K"
        assert 0 <= v.token < cfg.vocab
        assert int(engine.fed[slot]) == fed + v.accept_len + 1
        assert engine.kv.seq_pages(slot) == -(-int(engine.fed[slot]) // P), (
            "tail pages of the rejected block were not released")
    return {
        "accept_lens": [v.accept_len for v in verdicts],
        "k": k,
        "pages_after": engine.kv.seq_pages(slot),
        "stats": dict(engine.stats),
    }


def kernel_phase(seed: int = 0):
    """``paged_attention`` against ``paged_attention_ref`` at qwen2-7b's
    head geometry (G=7, D=128, P=256), in f32 and bf16 pages."""
    from repro.kernels.paged_attention.ops import (
        paged_attention_op,
        paged_attention_ref,
    )

    B, H, Hkv, D, P, n_pages, n_max = 4, 28, 4, 128, 256, 33, 8
    rng = np.random.default_rng(seed)
    out = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
        kp = jnp.asarray(rng.normal(size=(n_pages, Hkv, P, D)), dtype)
        vp = jnp.asarray(rng.normal(size=(n_pages, Hkv, P, D)), dtype)
        bt = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                         .reshape(B, n_max), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, n_max * P + 1, size=B),
                              jnp.int32)
        got = np.asarray(paged_attention_op(q, kp, vp, bt, lengths),
                         np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(paged_attention_ref(q, kp, vp, bt, lengths),
                              np.float32)
        # witness: how far the same reference moves at the chip's default
        # matmul precision (what the kernel's dots would take unpinned)
        ref_default = np.asarray(paged_attention_ref(q, kp, vp, bt, lengths),
                                 np.float32)
        err = float(np.max(np.abs(got - want)))
        name = jnp.dtype(dtype).name
        assert np.isfinite(got).all(), f"{name}: non-finite output"
        assert err <= KERNEL_TOL[name], (
            f"{name}: max |kernel - ref| {err} > {KERNEL_TOL[name]}")
        out[name] = {
            "max_abs_err": err, "tol": KERNEL_TOL[name],
            "ref_default_vs_highest": float(np.max(np.abs(ref_default - want))),
        }
    return out


def fused_program_phase(engine, *, batch: int = 4, k: int = 6,
                        top_c: int = 64):
    """Compile the engine's own fused verify program on the chip and look
    for the paged-attention kernel in it."""
    from repro.serving.engine import fused_paged_verify_specs

    specs = fused_paged_verify_specs(
        engine.cfg, batch=batch, k=k, n_pages=engine.kv.allocator.n_pages,
        n_max=2, top_c=top_c, page_size=engine.page_size,
        cache_dtype=engine.kv.k_pages.dtype,
        sharding=jax.sharding.SingleDeviceSharding(jax.devices()[0]),
    )
    text = engine._fused_verify.lower(
        *specs, method="residual", tagged=True).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the fused program"
    return {"tpu_custom_call": True}


def fleet_phase(target: str = TARGET, *, reduced: bool = False,
                seed: int = 0, verifiers: int = 4, rounds: int = 2,
                prompt_len: int = 300, verbose: bool = True):
    """``run_serving(verifiers=4)`` with its default placement (replica i
    on device i) against the same fleet stacked on device 0: committed
    streams must be byte-identical."""
    from repro.launch.serve import run_serving

    kw = dict(reduced=reduced, devices=verifiers, rounds=rounds,
              verifiers=verifiers, policy="wisp", prompt_len=prompt_len,
              seed=seed, verbose=verbose, self_draft=True,
              method="residual", q_mode="compact", prefill_mode="chunked",
              prefill_chunk_tokens=128)

    def streams(r):
        return {e.session.session_id: list(map(int, e.session.committed))
                for e in r["edges"]}

    r = run_serving(target, **kw)
    placement = {}
    for vid, srv in r["server"].verifiers.items():
        eng = srv.engine
        held = {d for leaf in jax.tree.leaves(eng.params)
                for d in leaf.devices()}
        held |= eng.kv.k_pages.devices() | eng.kv.v_pages.devices()
        assert held == {eng.device}, f"{vid} spans {held}"
        placement[vid] = (str(eng.device), eng.stats["batches"])
    n_dev = min(verifiers, len(jax.devices()))
    assert len({p for p, _ in placement.values()}) == n_dev, placement
    placed = streams(r)
    generated = sum(len(e.session.committed) - e.session.prompt_len
                    for e in r["edges"])
    rejected = r["total"].sent - r["total"].accepted
    del r
    gc.collect()
    r = run_serving(target, verifier_devices=[jax.devices()[0]] * verifiers,
                    **kw)
    stacked = streams(r)
    del r
    gc.collect()
    assert placed == stacked, "per-chip fleet diverged from the chip-0 fleet"
    return {"placement": placement, "sessions": len(placed),
            "generated_tokens": generated, "rejected_draft_tokens": rejected}


def _run(name: str, fn, failed: list):
    c0, t0 = _compile_s["total"], time.perf_counter()
    try:
        out = fn()
    except Exception:
        traceback.print_exc()
        failed.append(name)
        diag(name, passed=False)
        return None
    diag(name, passed=True,
         wall_s=round(time.perf_counter() - t0, 3),
         compile_s=round(_compile_s["total"] - c0, 3))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica fleet comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    n_chips = 4 if args.four_chips else 1
    if len(jax.devices()) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.common.compile_cache import enable_compile_cache

    diag("setup", device_kind=dev.device_kind, chips=len(jax.devices()),
         compile_cache=enable_compile_cache(),
         note="smoke diagnostics, not benchmark numbers")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    failed: list = []

    if args.four_chips:
        out = _run("fleet", lambda: fleet_phase(seed=args.seed), failed)
        if out is not None:
            for vid, (where, batches) in out["placement"].items():
                diag("fleet", replica=vid, device=where,
                     verify_batches=batches)
            diag("fleet", sessions=out["sessions"],
                 generated_tokens=out["generated_tokens"],
                 rejected_draft_tokens=out["rejected_draft_tokens"],
                 streams="byte-identical to the all-on-chip-0 fleet")
    else:
        out = _run("kernel", lambda: kernel_phase(args.seed), failed)
        if out is not None:
            for name, row in out.items():
                diag("kernel", dtype=name, **row)
        served = _run("served", lambda: served_phase(seed=args.seed), failed)
        if served is not None:
            report, r = served
            diag("served", **{k: v for k, v in report.items()
                              if k not in ("stats", "dispatch_counts")})
            diag("served", stats=json.dumps(report["stats"]))
            diag("served", dispatch_counts=json.dumps(
                report["dispatch_counts"]))
            engine = r["server"].engine
            out = _run("rejected_drafts", lambda: rejected_draft_phase(
                engine.cfg, engine.params, seed=args.seed), failed)
            if out is not None:
                diag("rejected_drafts", **out)
            out = _run("fused_program",
                       lambda: fused_program_phase(engine), failed)
            if out is not None:
                diag("fused_program", **out)
            del r, engine, served
            gc.collect()

    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats() or {}
        diag("memory", device=str(d),
             peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"))
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
