"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick mode
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sweeps
    PYTHONPATH=src python -m benchmarks.run --only capacity goodput
    PYTHONPATH=src python -m benchmarks.run --only goodput wdt ttft \\
        --policy wisp fcfs edf priority        # one sweep, all policies

Prints ``key=value`` CSV rows per table and writes JSON artifacts under
``artifacts/bench/``.  ``--policy`` is forwarded to every benchmark whose
``run()`` accepts a ``policies`` argument (goodput / wdt / ttft); those
emit the policy name into each result row.
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import time
import traceback

from benchmarks.common import print_rows, save_rows
from repro.common.compile_cache import enable_compile_cache
from repro.core.scheduler import available_policies

#: module -> paper reference
TABLES = {
    "kernels": "kernel microbench (roofline terms per kernel)",
    "roofline": "dry-run roofline, all (arch x shape x mesh) cells",
    "estimator": "Tables 7/12 + App. C (verification-time estimator)",
    "wdt": "Fig. 1 (WDT vs device goodput)",
    "slo_violations": "Table 1 + Fig. 7 (violation rates / knee)",
    "attribution": "Fig. 8 (queue-vs-compute violation attribution)",
    "goodput": "Table 3 (system goodput)",
    "predictor": "Tables 4/10/11 + Figs. 2-3 (rejection predictor)",
    "predictor_ablation": "Tables 5/6 (predictor ON/OFF ablations)",
    "capacity": "Table 2 (system capacity per SLO class)",
    "paged_serving": "§4.5 (dense vs paged engine: throughput + prefix hits)",
    "ttft": "long-prompt interference: monolithic vs chunked prefill (§8)",
    "hotpath": "verification hot-path budgets: dispatches + bytes (§9)",
    "adaptive_k": "§4.1 (static vs adaptive per-session draft length)",
    "tiered_kv": "§12 (tiered KV admission capacity at 25% device pool)",
    "fleet": "§10 (fleet goodput under verifier churn)",
    "tenancy": "§13 (multi-tenant isolation under adversarial flood)",
    "chaos": "§14 (goodput under edge-link loss: hardened vs no-retry)",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sweeps")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--policy", nargs="+", default=None,
                    choices=available_policies(),
                    help="scheduling policies to sweep in the benchmarks "
                         "that support it (rows carry the policy name)")
    args = ap.parse_args()
    enable_compile_cache()

    names = args.only or list(TABLES)
    failures = []
    for name in names:
        mod = importlib.import_module(f"benchmarks.{name}")
        print(f"# === {name}: {TABLES.get(name, '')} ===", flush=True)
        t0 = time.time()
        kwargs = {"quick": not args.full}
        if (args.policy
                and "policies" in inspect.signature(mod.run).parameters):
            kwargs["policies"] = args.policy
        try:
            rows = mod.run(**kwargs)
        except Exception as e:
            traceback.print_exc()
            failures.append(name)
            print(f"# {name} FAILED: {e!r}", flush=True)
            continue
        print_rows(rows)
        save_rows(name, rows)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
