"""Verification hot-path microbenchmark: dispatches/epoch, host<->device
bytes/epoch, and verify wall time per backend (DESIGN.md §9).

Measures the engine's own counters (``VerificationEngine.stats`` /
``dispatch_counts``) around batched ``verify`` epochs for every backend
(dense-slot attention, paged attention, recurrent) and every draft-q
representation (dense logits, compact top-C table, greedy/none), then
**asserts the hot-path budgets** so CI fails on a regression:

  * the fused per-epoch program dispatches exactly ONCE per verify call on
    every backend — in particular the recurrent backend is O(1) in K
    (measured at two draft lengths), where the pre-refactor stepwise loop
    was K+2 dispatches and K+2 live state copies;
  * at V >= 32k with C = 64, compact-q staging is >= 10x smaller than
    dense-q staging; greedy stages no q bytes at all.

Rows are written to ``BENCH_hotpath.json`` at the repo root: rows with
``phase="seed"`` are the pre-refactor baseline measured at the seed commit
(dispatch counts measured by wrapping the seed engine's jitted callables;
staged bytes computed from the seed staging buffers' shapes) and are
preserved verbatim; ``phase="current"`` rows are refreshed every run —
the file is the repo's hot-path perf trajectory.

Usage: PYTHONPATH=src:. python benchmarks/hotpath.py [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.speculative import compact_from_logits
from repro.models import build
from repro.serving.engine import VerificationEngine, VerifyItem

from benchmarks.common import print_rows

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_hotpath.json")

V = 32768          # vocab large enough that q staging dominates (>= 32k)
C = 64             # compact top-C table width

BACKENDS = ("dense", "paged", "recurrent")
Q_MODES = ("dense", "compact", "greedy")


def _make_engine(backend: str, q_mode: str, max_slots: int):
    name = {"dense": "qwen2-7b", "paged": "qwen2-7b",
            "recurrent": "xlstm-350m"}[backend]
    cfg = dataclasses.replace(get_config(name).reduced(), vocab=V,
                              name=name + "-hotpath")
    bundle = build(cfg)
    method = "greedy" if q_mode == "greedy" else "residual"
    if cfg.family in ("ssm", "hybrid"):
        params = bundle.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        kw = {"cache_dtype": jnp.float32}
    else:
        params = bundle.init(jax.random.PRNGKey(0))
        kw = {"paged": backend == "paged"}
        if backend == "paged":
            kw["page_size"] = 16
    return cfg, VerificationEngine(cfg, params, max_slots=max_slots,
                                   max_len=256, method=method, seed=0, **kw)


def _items(slots, K: int, q_mode: str, rnd: int):
    out = []
    for i, s in enumerate(slots):
        g = np.random.default_rng(100 * rnd + i)
        toks = g.integers(0, V, size=K).astype(np.int32)
        qlog = (g.normal(size=(K, V)) * 1.5).astype(np.float32)
        it = VerifyItem(slot=s, draft_tokens=toks, rng_tag=(i, rnd))
        if q_mode == "dense":
            it.q_logits = qlog
        elif q_mode == "compact":
            it.q_compact = compact_from_logits(qlog, toks, C=C)
        out.append(it)
    return out


def bench_cell(backend: str, q_mode: str, *, B: int, K: int,
               epochs: int) -> dict:
    cfg, eng = _make_engine(backend, q_mode, B)
    rng = np.random.default_rng(0)
    slots = [eng.new_session(rng.integers(0, V, size=8).astype(np.int32))[0]
             for _ in range(B)]
    eng.verify(_items(slots, K, q_mode, 0))             # warmup / compile
    base = dict(eng.stats)
    base_launches = sum(eng.dispatch_counts.values())
    base_verify = eng.dispatch_counts["verify"]
    t0 = time.perf_counter()
    for r in range(1, 1 + epochs):
        eng.verify(_items(slots, K, q_mode, r))
    dt = (time.perf_counter() - t0) / epochs
    d = {k: eng.stats[k] - base[k] for k in
         ("h2d_bytes", "h2d_q_bytes", "d2h_bytes")}
    d["dispatches"] = sum(eng.dispatch_counts.values()) - base_launches
    return {
        "table": "hotpath", "phase": "current", "backend": backend,
        "method": eng.method,
        "q_mode": {"greedy": "none"}.get(q_mode, q_mode),
        "B": B, "K": K, "V": V, "C": C if q_mode == "compact" else None,
        "dispatches_per_epoch": d["dispatches"] / epochs,
        "verify_dispatches_per_epoch":
            (eng.dispatch_counts["verify"] - base_verify) / epochs,
        "h2d_bytes_per_epoch": d["h2d_bytes"] // epochs,
        "h2d_q_bytes_per_epoch": d["h2d_q_bytes"] // epochs,
        "d2h_bytes_per_epoch": d["d2h_bytes"] // epochs,
        "state_copies": 1,            # the scan carries one selected state
        "t_verify_ms": round(dt * 1e3, 3),
    }


def run(quick: bool = True) -> list[dict]:
    B = 2 if quick else 4
    K = 8
    epochs = 2 if quick else 8
    rows = []
    for backend in BACKENDS:
        for q_mode in Q_MODES:
            rows.append(bench_cell(backend, q_mode, B=B, K=K, epochs=epochs))
    # O(1)-in-K evidence: the recurrent fused program must cost the same
    # dispatch count at half the draft length
    rows.append(bench_cell("recurrent", "dense", B=B, K=K // 2,
                           epochs=epochs))

    # -- budget assertions (CI gate) -------------------------------------
    for r in rows:
        assert r["verify_dispatches_per_epoch"] == 1.0, (
            f"hot-path regression: {r['backend']}/{r['q_mode']} runs "
            f"{r['verify_dispatches_per_epoch']} fused verify dispatches "
            f"per epoch (budget: 1)"
        )
    rec = [r for r in rows if r["backend"] == "recurrent"]
    ks = {r["K"]: r["verify_dispatches_per_epoch"] for r in rec}
    assert len(set(ks.values())) == 1, (
        f"recurrent verify dispatches must be O(1) in K, got {ks}"
    )
    by = {(r["backend"], r["q_mode"]): r for r in rows if r["K"] == K}
    for backend in BACKENDS:
        dense_q = by[(backend, "dense")]["h2d_q_bytes_per_epoch"]
        compact_q = by[(backend, "compact")]["h2d_q_bytes_per_epoch"]
        greedy_q = by[(backend, "none")]["h2d_q_bytes_per_epoch"]
        assert greedy_q == 0, f"{backend}: greedy staged {greedy_q} q bytes"
        assert dense_q >= 10 * max(compact_q, 1), (
            f"{backend}: compact q staging {compact_q}B is not >= 10x "
            f"smaller than dense {dense_q}B at V={V}, C={C}"
        )
        dense_all = by[(backend, "dense")]["h2d_bytes_per_epoch"]
        compact_all = by[(backend, "compact")]["h2d_bytes_per_epoch"]
        assert dense_all >= 10 * compact_all, (
            f"{backend}: total staged bytes {compact_all}B not >= 10x "
            f"below dense {dense_all}B"
        )
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few epochs (CI)")
    args = ap.parse_args()
    rows = run(quick=args.smoke)
    # preserve the committed seed-baseline rows; refresh the current rows
    seed_rows = []
    if os.path.exists(OUT_PATH):
        with open(OUT_PATH) as f:
            seed_rows = [r for r in json.load(f) if r.get("phase") == "seed"]
    with open(OUT_PATH, "w") as f:
        json.dump(seed_rows + rows, f, indent=1)
    print_rows(rows)
    if seed_rows:
        base = {r["backend"]: r for r in seed_rows}
        cur = {(r["backend"], r["q_mode"]): r for r in rows if r["K"] == 8}
        for backend in BACKENDS:
            s, c = base[backend], cur[(backend, "compact")]
            # seed and current rows may have been measured at different
            # batch sizes (--smoke shrinks B): compare PER-ROW bytes
            sb = s["h2d_bytes_per_epoch"] / s["B"]
            cb = c["h2d_bytes_per_epoch"] / c["B"]
            print(
                f"[hotpath] {backend}: dispatches/epoch "
                f"{s['dispatches_per_epoch']:.0f} -> "
                f"{c['dispatches_per_epoch']:.0f}, staged bytes/epoch/row "
                f"{sb:.0f} -> {cb:.0f} ({sb / cb:.0f}x)"
            )
    print(f"[hotpath] budgets OK; wrote {os.path.abspath(OUT_PATH)}")


if __name__ == "__main__":
    main()
