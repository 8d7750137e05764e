#!/usr/bin/env python3
"""The WISP verification-server benchmark: one cell, one run.

    python3 bench/run.py --workload qwen2-7b-14l.chat --seed 7 --seconds 40 --trace 0

The cell (``BENCHMARK.json``) names a configuration (``bench/configs``)
and a traffic mix (``bench/traffic``).  Set-up makes the weights on the
chip from the seed, sizes the KV page pool from the compiled programs'
memory analysis, builds `VerificationEngine` (paged, bf16 pages) under
`WISPServer` (``wisp`` policy, chunked prefill, compact-q residual
verification) and runs every program the window can reach once.  The
window then drives the server on the wall clock from an emulated edge
fleet (`bench.harness`).  Afterwards the program's state is freed and a
sample of the finished sessions is compared with the plain reference
(`bench.check`).  ``--trace 1`` takes a profiler trace of a few seconds
of the window and prints the cell's per-layer metrics instead of its
end-to-end ones.

Diagnostics go to stderr; the last stdout line is the result.  Without
an accelerator, or with fewer chips than the cell asks for, the run exits
2 and prints no result.  ``--control 1`` (not used by the benchmark's own
runs) also reads the int8 control against the reference.  ``--rate``
overrides the mix's session rate, for the sweep that finds the knee.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]

from bench.common import (BENCH, ROOT, find, load_benchmark,  # noqa: E402
                          load_limits, log)

CHECK_SESSIONS = 6


def reader(name: str, directory: Path = BENCH / "metrics"):
    """The ``read`` function of metric ``name`` (its own file)."""
    path = Path(directory) / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metric_names(bm: dict, cell: str, trace: bool) -> list:
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m["name"] for m in group
            if "workloads" not in m or cell in m["workloads"]]


def device_info(jax, chips: int, require_chip: bool):
    devs = jax.devices()
    d = devs[0]
    if require_chip and (d.platform == "cpu" or len(devs) < chips):
        log("no accelerator", platform=d.platform, count=len(devs),
            needed=chips)
        raise SystemExit(2)
    return d, {"platform": d.platform, "kind": d.device_kind,
               "count": len(devs)}


def main(argv=None, *, require_chip: bool = True, root: Path = ROOT,
         fault=None) -> dict | None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    a = ap.parse_args(argv)

    bm = load_benchmark(root)
    cell = find(bm["workloads"], a.workload, "workload")
    cfg_entry = find(bm["configs"], cell["config"], "configuration")
    c = json.loads((root / cfg_entry["file"]).read_text())
    from bench import traffic as TR

    mix = TR.load_mix(cell["traffic"], root / "bench" / "traffic")

    import jax

    from bench import harness as H

    dev, device = device_info(jax, cell["chips"], require_chip)
    log("device", **device)
    H.enable_cache(root)

    import jax.numpy as jnp

    from bench import peaks as PK
    from bench import reference as R
    from bench import weights as W
    from bench.common import program_config
    from repro.core.estimator import analytic_tpu_coeffs
    from repro.core.scheduler import SchedulerConfig
    from repro.models import build
    from repro.serving import VerificationEngine, WISPServer

    pk = PK.peaks(dev.device_kind) if require_chip else None
    cfg = program_config(c)
    spec = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    R.check_layout(c, spec)
    t = time.perf_counter()
    params = W.make_params(spec, a.seed, dev)
    log("weights", seconds=time.perf_counter() - t)
    srv = mix["server"]
    page = srv.get("page_size", 256)
    t = time.perf_counter()
    n_pages, sizing = H.size_pool(cfg, mix, dev, page)
    log("pool", n_pages=n_pages, seconds=time.perf_counter() - t, **sizing)
    engine_seed = a.seed % (1 << 31)
    engine = VerificationEngine(
        cfg, params, max_slots=srv["max_slots"], max_len=mix["max_len"],
        method="residual", seed=engine_seed, cache_dtype=jnp.bfloat16,
        page_size=page, n_pages=n_pages)
    if fault is not None:
        fault(engine)
    t = time.perf_counter()
    n_prog = H.warm_up(engine, mix, a.seed)
    log("warm-up", programs=n_prog, seconds=time.perf_counter() - t)
    slo = mix["slo"]
    server = WISPServer(
        engine, analytic_tpu_coeffs(cfg), policy=srv["policy"],
        sched_cfg=SchedulerConfig(
            max_batch_requests=srv["max_batch_requests"]),
        slo_classes={int(k): float(v) for k, v in slo["tok_s"].items()},
        ttft_slo={int(k): float(v) for k, v in slo["ttft_s"].items()},
        prefill="chunked", prefill_chunk_tokens=srv["prefill_chunk_tokens"])
    vocab = cfg.vocab
    prefixes = TR.prefix_tokens(mix, a.seed, vocab)
    if mix.get("prefill_prefixes_at_setup"):
        H.prefill_prefixes(server, prefixes, srv["prefill_chunk_tokens"])
    plans = TR.plan(mix, a.seed, a.seconds, rate=a.rate)
    compiles = H.CompileCounter()
    tracer = None
    tdir = None
    if a.trace:
        (root / ".bench_out").mkdir(exist_ok=True)
        tdir = tempfile.mkdtemp(prefix="trace-", dir=root / ".bench_out")
        tracer = H.Tracer(tdir)
    setup_s = time.perf_counter() - T_START
    log("setup", setup_s=setup_s, sessions_planned=len(plans))
    compiles.start()
    w = H.drive(server, mix, plans, prefixes, a.seed, a.seconds, tracer=tracer)
    compiles.stop()
    w.setup_s, w.n_pages, w.config, w.peaks = setup_s, n_pages, c, pk
    lat = sorted(w.lateness)
    log("window", sessions=len(w.sessions), steps=len(w.steps),
        compiles_in_window=compiles.n, run_end_s=w.t_end,
        lateness_p50_ms=1e3 * lat[len(lat) // 2] if lat else 0,
        lateness_p99_ms=1e3 * lat[int(len(lat) * 0.99)] if lat else 0,
        lateness_max_ms=1e3 * lat[-1] if lat else 0)
    mem = dev.memory_stats() or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    result = {}
    if a.trace:
        from bench import trace as TRC

        w.trace = TRC.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        if w.trace is not None:
            device["busy_s"] = TRC.busy_s(w.trace)
            device["window_s"] = w.trace.window_s
            result["breakdown"] = TRC.breakdown(w.trace)
    names = metric_names(bm, a.workload, bool(a.trace))
    units = {m["name"]: m["unit"] for m in bm["end_to_end"] + bm["per_layer"]}
    metrics = {}
    for name in names:
        v = reader(name, root / "bench" / "metrics")(w)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": units[name]}
        log("metric", name=name, value=v)
    attempted = sum(w.in_window(s.plan.due) for s in w.sessions.values())
    from bench.metrics.goodput_tok_s import met_slo

    met = sum(w.in_window(s.plan.due) and met_slo(w, s)
              for s in w.sessions.values())
    log("slo", attempted=attempted, met=met,
        attainment=met / attempted if attempted else 0.0)
    failed = sum(w.in_window(s.plan.due) and math.isnan(s.t_first)
                 for s in w.sessions.values())
    # the check: free the program's state, then the reference
    from bench import check as CK

    chosen = CK.sample(w, a.seed, CHECK_SESSIONS)
    del server, engine, params
    H.free()
    t = time.perf_counter()
    cmp = CK.compare(c, a.seed, engine_seed, chosen, control=bool(a.control),
                     pad_to=mix["max_len"])
    log("check", seconds=time.perf_counter() - t, sessions=cmp["sessions"],
        served_tokens=cmp["served_tokens"], decisions=cmp["decisions"])
    limits = load_limits(a.workload, root / "bench" / "limits")
    checks = {}
    for k in CK.NUMBERS:
        if k in limits:
            checks[k] = {"value": cmp["program"][k], "limit": limits[k]}
    log("program", **cmp["program"])
    if a.control:
        log("control", **cmp["control"])
    correct = bool(chosen) and bool(checks) and all(
        v["value"] <= v["limit"] for v in checks.values())
    for k, v in checks.items():
        print(f"check {k}: {v['value']} limit {v['limit']}", file=sys.stderr)
    result.update({"correct": correct, "attempted": attempted,
                   "failed": failed, "metrics": metrics, "device": device})
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
