"""Needed work at both configurations' published widths, by hand."""
import json
from pathlib import Path

import pytest

from bench import work

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_active_params_qwen2():
    p = work.active_params(cfg("qwen2-7b-14l"))
    # q and o 3584x3584 each, k and v 3584x512 each; gate/up/down 3584x18944
    assert p["layer"] == 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert p["layer"] == 233_046_016
    assert p["head"] == 3584 * 152064


def test_active_params_deepseek_moe_counts_topk_not_dropless():
    p = work.active_params(cfg("deepseek-moe-16b-7l"))
    attn = 4 * 2048 * 2048
    router = 2048 * 64
    routed = 6 * 3 * 2048 * 1408          # top-6, not all 64
    shared = 3 * 2048 * 2816
    assert p["layer"] == attn + router + routed + shared == 86_114_304
    assert p["head"] == 2048 * 102400


def test_step_flops_qwen2_one_verify_row():
    c = cfg("qwen2-7b-14l")
    got = work.step_flops(c, [(1000, 7)])
    matmul = 2 * 7 * (233_046_016 * 14 + 3584 * 152064)
    # causal: queries at 1001..1007 positions, 4 * ctx * H * hd per layer
    attn = 4 * (7 * 1000 + 28) * 28 * 128 * 14
    assert got == pytest.approx(matmul + attn, rel=0, abs=0.5)
    assert got == pytest.approx(54_717_530_112, rel=0, abs=0.5)


def test_attention_bytes_read_history_once():
    q = work.attention_bytes(cfg("qwen2-7b-14l"), 1000, 7)
    assert q == (1007 * 4 * 128 * 2 * 2 + 2 * 7 * 28 * 128 * 2) * 14
    assert q == 30_277_632
    d = work.attention_bytes(cfg("deepseek-moe-16b-7l"), 3584, 7)
    assert d == (3591 * 16 * 128 * 2 * 2 + 2 * 7 * 16 * 128 * 2) * 7
    assert d == 206_323_712


def test_roofline_names_its_bound():
    pk = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, b = work.roofline_s(197e12, 1.0, pk)
    assert (t, b) == (1.0, "flops")
    t, b = work.roofline_s(1.0, 819e9, pk)
    assert (t, b) == (1.0, "bytes")


def test_peaks_unknown_device_is_an_error():
    from bench.peaks import peaks

    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")
