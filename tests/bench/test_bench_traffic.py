"""The traffic generator: same work for every seed, in another order; a
new mix is found by name."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic as TR

MIXES = Path(__file__).resolve().parents[2] / "bench" / "traffic"


@pytest.mark.parametrize("name", ["chat", "chat-burst", "docqa"])
def test_every_seed_gets_the_same_window(name):
    mix = TR.load_mix(name)
    w0, w1 = mix["preroll_s"], mix["preroll_s"] + 40.0
    key = lambda p: (p.prefix, p.suffix_len, p.response_len,
                     json.dumps(p.edge, sort_keys=True))
    runs = [TR.plan(mix, s, 40.0) for s in (1, 2**31 + 12345)]
    inside = [[p for p in r if w0 <= p.due < w1] for r in runs]
    assert len(inside[0]) == len(inside[1]) == round(
        float(np.diff(TR.cumulative([w0, w1], mix))[0]))
    assert sorted(map(key, inside[0])) == sorted(map(key, inside[1]))
    assert list(map(key, inside[0])) != list(map(key, inside[1]))
    assert [p.due for p in runs[0]] != [p.due for p in runs[1]]
    assert [p.sid for p in runs[0]] == list(range(len(runs[0])))


def test_cumulative_and_inverse_agree():
    mix = TR.load_mix("chat-burst")
    t = np.linspace(0.0, 95.0, 400)
    np.testing.assert_allclose(TR.inverse(TR.cumulative(t, mix), mix), t,
                               atol=1e-6)


def test_same_seed_same_plan():
    mix = TR.load_mix("chat")
    assert TR.plan(mix, 7, 100.0) == TR.plan(mix, 7, 100.0)


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_lengths_fit_and_are_quantized(name):
    mix = TR.load_mix(name)
    pre = TR.prefix_lengths(mix)
    q = mix["len_quantum"]
    assert all(n % q == 0 for n in pre)
    for p in TR.plan(mix, 3, 300.0):
        assert p.suffix_len % q == 0
        total = pre[p.prefix] + p.suffix_len + p.response_len + mix["k"] + 1
        assert total <= mix["max_len"]
        assert p.response_len >= 1


def test_bursts_raise_the_rate_inside_them():
    mix = TR.load_mix("chat-burst")
    b = mix["bursts"]
    due = np.array([p.due for p in TR.plan(mix, 5, 400.0)])
    phase = due % b["period_s"]
    inside = (phase < b["length_s"]).sum() / b["length_s"]
    outside = (phase >= b["length_s"]).sum() / (b["period_s"] - b["length_s"])
    assert 2.0 < inside / outside < 4.5
    mean = len(due) / (400.0 + mix["preroll_s"] + mix["drain_s"])
    want = mix["rate_per_s"] * (b["factor"] * b["length_s"] + b["period_s"]
                                - b["length_s"]) / b["period_s"]
    assert mean == pytest.approx(want, rel=0.15)


def test_poisson_rate():
    mix = TR.load_mix("chat")
    n = len(TR.plan(mix, 9, 1000.0))
    span = 1000.0 + mix["preroll_s"] + mix["drain_s"]
    assert n == pytest.approx(mix["rate_per_s"] * span, abs=2)


def test_new_mix_file_is_found_by_name(tmp_path):
    for f in MIXES.glob("*.json"):
        (tmp_path / f.name).write_text(f.read_text())
    before = {f.name: f.read_text() for f in tmp_path.glob("*.json")}
    new = dict(json.loads((MIXES / "chat.json").read_text()), rate_per_s=9.0)
    (tmp_path / "chat-fast.json").write_text(json.dumps(new))
    assert TR.load_mix("chat-fast", tmp_path)["rate_per_s"] == 9.0
    assert {f.name: f.read_text() for f in tmp_path.glob("*.json")
            if f.name in before} == before
    with pytest.raises(FileNotFoundError):
        TR.load_mix("no-such-mix", tmp_path)
