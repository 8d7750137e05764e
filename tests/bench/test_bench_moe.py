"""The MoE expert layer's readers, and the DeepSeekMoE stage's
configuration against the program, by hand at the published widths."""
import json
from pathlib import Path

import jax
import pytest

from bench import moe_work
from bench import reference as R
from bench.common import program_config
from bench.harness import Step, Window
from bench.run import reader
from bench.trace import Reduced

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"
C = json.loads((CONFIGS / "deepseek-moe-16b-7l-s2.json").read_text())
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
EXPERT = 3 * 2048 * 1408                   # gate, up and down of one expert


def test_gmm_needed_work_by_hand():
    # 100 tokens: 600 assignments, all 64 experts' weights read once
    assert moe_work.experts_read(C, 100) == 64
    assert moe_work.gmm_flops(C, 100) == 2 * 600 * EXPERT * 7
    assert moe_work.gmm_flops(C, 100) == 72_666_316_800
    assert moe_work.gmm_bytes(C, 100) == (
        64 * EXPERT * 2 + 2 * 600 * 2048 * 2) * 7
    # one token reads its 6 experts; 10 tokens at most 60
    assert moe_work.experts_read(C, 1) == 6
    assert moe_work.experts_read(C, 10) == 60
    assert moe_work.gmm_bytes(C, 1) == (6 * EXPERT * 2 + 2 * 6 * 2048 * 2) * 7


def window(ops, config=C, calls=((10, 7), (20, 7))):
    w = Window({}, 10.0, 0.0)
    w.config, w.peaks = config, PEAKS
    w.trace_span = (1.0, 2.0)
    w.steps = [Step(1.1, 1.2, list(calls), [(0, 64), (64, 32)], 5),
               Step(1.5, 1.6, [(30, 7)], [], 5),
               Step(2.5, 2.6, [(40, 7)], [], 5)]      # after the trace
    w.trace = Reduced(1.0, 2.0, ops, [], [], 1)
    return w


def test_moe_gmm_roofline_by_hand():
    ops = [("moe_gmm_gated.3", 1.10, 0.004), ("moe_gmm_down.3", 1.11, 0.002),
           ("paged_attention", 1.12, 0.005),            # not the kernel
           ("moe_gmm_gated.3", 1.50, 0.003), ("moe_gmm_down.7", 1.51, 0.001),
           ("moe_gmm_gated.3", 0.50, 0.009)]            # before the window
    # three calls: 14 verify tokens and 96 prefill tokens in the first
    # step, 7 verify tokens in the second; each reads min(64, 6 n)
    # experts' weights once a layer
    flops = 2 * (14 + 96 + 7) * 6 * EXPERT * 7
    nbytes = sum((e * EXPERT * 2 + 2 * n * 6 * 2048 * 2) * 7
                 for n, e in ((14, 64), (96, 64), (7, 42)))
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == nbytes / 819e9                      # bytes-bound
    assert reader("moe_gmm_roofline")(window(ops)) == pytest.approx(
        100 * least / 0.010)


@pytest.mark.parametrize("case", ["no trace", "no kernel", "dense model"])
def test_moe_gmm_roofline_reads_nothing(case):
    ops = [("moe_gmm_gated.3", 1.10, 0.004)]
    w = window(ops)
    if case == "no trace":
        w.trace = None
    elif case == "no kernel":          # a program without the grouped kernel
        w = window([("fusion.12", 1.10, 0.004)])
    else:
        w = window(ops, config={"num_hidden_layers": 14})
    assert reader("moe_gmm_roofline")(w) is None


def test_moe_tokens_per_expert_from_the_counters():
    w = Window({}, 10.0, 0.0)
    w.config = C
    w.stats0 = {"moe_assignments": 4200, "moe_expert_passes": 70}
    w.stats1 = {"moe_assignments": 4200 + 7 * 6 * (21 + 96),
                "moe_expert_passes": 70 + 2 * 7}
    # 117 real tokens over two calls: 702 assignments over 128 expert passes
    assert reader("moe_tokens_per_expert")(w) == pytest.approx(
        117 * 6 / (2 * 64))
    w.stats0, w.stats1 = {"batches": 3}, {"batches": 9}   # no such counters
    assert reader("moe_tokens_per_expert")(w) is None


def test_stage_config_is_what_the_program_runs():
    from repro.models import build

    cfg = program_config(C)
    R.check_layout(C, jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0)))
    assert cfg.n_layers == C["num_hidden_layers"] == 7
    # the reference routes as the file says; the program as its preset
    assert cfg.moe.norm_topk_prob is C["norm_topk_prob"] is False
    assert "departures" not in C
