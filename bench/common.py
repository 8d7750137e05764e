"""Paths, the cell's files found by name, and the diagnostic lines."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
LIMITS = BENCH / "limits"


def log(phase: str, **fields) -> None:
    """One diagnostic line on stderr (never a result)."""
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[bench] {phase}: {body}", file=sys.stderr, flush=True)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def load_limits(cell: str, directory: Path = LIMITS) -> dict:
    """The cell's check limits (``{number: limit}``); empty if none."""
    path = Path(directory) / f"{cell}.json"
    return json.loads(path.read_text())["limits"] if path.is_file() else {}


#: configuration-file key -> program `ArchConfig` field, checked equal
_WIDTHS = {
    "hidden_size": "d_model", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
    "num_hidden_layers": "n_layers", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "head_dim": "head_dim",
}


def program_config(c: dict):
    """The program's `ArchConfig` for configuration file ``c``; raises
    unless every size the file states is what the program runs."""
    from repro.configs import get_config

    prog = c["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **prog.get("replace", {}))
    want = {f: c[k] for k, f in _WIDTHS.items() if k in c}
    if c.get("n_routed_experts"):
        m = cfg.moe
        got_moe = (m.num_experts, m.top_k, m.num_shared_experts,
                   m.d_expert or cfg.d_ff)
        want_moe = (c["n_routed_experts"], c["num_experts_per_tok"],
                    c["n_shared_experts"], c["moe_intermediate_size"])
        if got_moe != want_moe:
            raise ValueError(f"program MoE {got_moe} != file {want_moe}")
    else:
        want["d_ff"] = c["intermediate_size"]
    want["qkv_bias"] = bool(c.get("attention_bias"))
    want["tie_embeddings"] = bool(c.get("tie_word_embeddings"))
    for f, v in want.items():
        got = getattr(cfg, f)
        if f == "head_dim":
            got = cfg.resolved_head_dim
        if (abs(got - v) > 1e-12 * abs(v)) if isinstance(v, float) else got != v:
            raise ValueError(f"program {f}={got} but the file states {v}")
    return cfg
