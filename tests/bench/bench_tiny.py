"""A tiny cell for CPU tests: a new configuration, traffic mix and limits
dropped into a copy of the benchmark's tree, found by name."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny-qwen2.chat"

CONFIG = {
    "name": "tiny-qwen2", "source": "test",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "attention_bias": True, "tie_word_embeddings": False,
    "program": {"arch": "qwen2-7b", "replace": {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab": 512}},
}


def mix() -> dict:
    m = json.loads((ROOT / "bench" / "traffic" / "chat.json").read_text())
    m.update({"rate_per_s": 3.0, "preroll_s": 1.0, "drain_s": 8.0,
              "len_quantum": 16, "max_len": 64, "q_top_c": 4,
              "prefixes": {"count": 3, "min": 16, "max": 32, "zipf_s": 1.0},
              "suffix": {"median": 16, "sigma": 0.5, "min": 16, "max": 16},
              "response": {"median": 12, "sigma": 0.3, "min": 8, "max": 16}})
    m["server"] = dict(m["server"], prefill_chunk_tokens=32,
                       max_batch_requests=2, max_slots=64, page_size=16,
                       n_pages=64)
    return m


def make_root(tmp: Path, limits: dict | None = None,
              config: dict | None = None) -> Path:
    """A benchmark tree under ``tmp`` with the tiny cell added to it."""
    root = Path(tmp) / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "configs" / "tiny-qwen2.json").write_text(
        json.dumps(config or CONFIG))
    (root / "bench" / "traffic" / "tiny-chat.json").write_text(
        json.dumps(mix()))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny-qwen2", "source": "test",
                          "file": "bench/configs/tiny-qwen2.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": CELL, "config": "tiny-qwen2",
                            "traffic": "tiny-chat", "chips": 1,
                            "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "qwen2-7b-14l.chat" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    if limits is not None:
        d = root / "bench" / "limits"
        d.mkdir(exist_ok=True)
        (d / f"{CELL}.json").write_text(json.dumps({"limits": limits}))
    return root


def run(root: Path, seed: int, *, fault=None, control: bool = False):
    """One run of the tiny cell, skipping the look for a chip."""
    import jax

    from bench import run as R

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        return R.main(["--workload", CELL, "--seed", str(seed),
                       "--seconds", "3", "--trace", "0",
                       "--control", str(int(control))],
                      require_chip=False, root=root, fault=fault)
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
