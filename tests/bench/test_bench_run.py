"""bench/run.py end to end: no chip, no result; a new cell found by name
runs and is correct on the CPU at a tiny size."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path


import bench_tiny

ROOT = Path(__file__).resolve().parents[2]

#: the tiny cell's limits (nats).  Sound runs read 0 to 0.0011 here; the
#: faults in test_bench_faults read 0.1 (KV writes lost) and whole nats
#: (answers altered or left out).
LIMITS = {"decision_shift_max": 0.03}


def _run(cmd, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_accelerator_no_result():
    p = _run([sys.executable, "bench/run.py", "--workload",
              "qwen2-7b-14l.chat", "--seed", "1", "--seconds", "1",
              "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]
    assert "no accelerator" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2-7b-14l.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_new_cell_runs_and_is_correct(tmp_path):
    root = bench_tiny.make_root(tmp_path, LIMITS)
    # a new per-layer metric is one new file, found by name
    (root / "bench" / "metrics" / "sessions_seen.py").write_text(
        "def read(w):\n    return float(len(w.sessions))\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["per_layer"].append({"name": "sessions_seen", "unit": "sessions",
                            "better": "higher", "source": "program_counter",
                            "layer": "server and scheduler",
                            "moves": "goodput_tok_s",
                            "workloads": [bench_tiny.CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    line = bench_tiny.run(root, 5)
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == set(LIMITS)
    assert line["attempted"] > 0 and line["failed"] == 0
    m = line["metrics"]
    for k in ("verify_p95_ms", "goodput_tok_s", "setup_s"):
        assert m[k]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    # the per-layer readers, on the same kind of run
    from bench import run as R

    w_metrics = R.metric_names(bm, bench_tiny.CELL, True)
    assert "sessions_seen" in w_metrics
