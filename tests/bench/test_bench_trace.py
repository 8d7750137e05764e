"""The trace reduction, against a small recorded device trace."""
import json
import types
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data" / "trace_v5e.json"


def as_xspace(d):
    ev = lambda e: types.SimpleNamespace(name=e[0], start_ns=e[1],
                                         duration_ns=e[2])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p["name"], lines=[
            types.SimpleNamespace(name=l["name"], events=[ev(e) for e in
                                                          l["events"]])
            for l in p["lines"]]) for p in d["planes"]])


def synthetic():
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["%a.1 = f32[] x", 1000, 2000],
                                           ["%b.2 = f32[] y", 1500, 1000],
                                           ["%a.3 = f32[] x", 8000, 1000]]},
            {"name": "XLA Modules", "events": [["jit_fused", 1000, 3500]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["bench.traced", 0, 10000],
                                          ["bench.step", 900, 3700],
                                          ["bench.idle", 4000, 3900]]}]}]}


def test_reduction_by_hand():
    red = T.reduce_xspace(as_xspace(synthetic()))
    assert red.window_s == pytest.approx(10e-6)
    # ops cover [1, 3] and [8, 9] us (b nests inside the first a): 3 of 10
    assert T.busy_s(red) == pytest.approx(3e-6)
    gaps = T.idle_gaps(red)
    assert gaps[0] == ["bench.idle", pytest.approx(5e-6)]
    assert [g[0] for g in gaps] == ["bench.idle", "other", "other"]
    # self time: the first a encloses b, so a's own share is 1 + 1 us
    assert T.top_ops(red) == [["a", pytest.approx(2e-6)],
                              ["b", pytest.approx(1e-6)]]
    b = T.breakdown(red)
    assert set(b) == {"device_ops", "idle_gaps"}


@pytest.mark.skipif(not DATA.is_file(), reason="no recorded trace")
def test_recorded_trace():
    d = json.loads(DATA.read_text())
    red = T.reduce_xspace(as_xspace(d))
    want = d["expected"]
    assert red.window_s == pytest.approx(want["window_s"])
    assert T.busy_s(red) == pytest.approx(want["busy_s"])
    assert len(red.ops) == want["n_ops"]
    # the slice holds the traced window's longest idle gap, 2 ms each side
    assert T.idle_gaps(red)[0][1] == pytest.approx(want["gap_s"])
    assert want["window_s"] == pytest.approx(want["gap_s"] + 4e-3, abs=1e-8)
    # busy time and every idle gap tile the window
    gaps = T.idle_gaps(red, top=10**6)
    assert T.busy_s(red) + sum(g for _, g in gaps) == pytest.approx(
        red.window_s)
    assert 3.5e-3 < T.busy_s(red) <= 4e-3 + 1e-9
