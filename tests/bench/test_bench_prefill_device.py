"""`prefill_device_ms_per_ktok` on a hand-made window and trace."""
import pytest

from bench.harness import Step, Window
from bench.run import reader
from bench.trace import Reduced

read = reader("prefill_device_ms_per_ktok")


def window(modules, prefill_rows=((0, 64), (64, 64))):
    w = Window({}, 10.0, 0.0)
    w.trace_span = (1.0, 2.0)
    w.steps = [Step(1.1, 1.2, [(10, 7)], list(prefill_rows), 5),
               Step(1.5, 1.6, [(17, 7)], [], 5),
               Step(2.5, 2.6, [], [(0, 999)], 5)]      # after the trace
    w.trace = Reduced(1.0, 2.0, [], modules, [], 1)
    return w


def test_prefill_device_time_per_ktok():
    w = window([("jit_prefill_paged", 1.1, 0.010),
                ("jit_fused_verify", 1.2, 0.080),        # not the prefill
                ("jit_prefill_paged", 1.5, 0.020),
                ("jit_prefill_paged", 0.5, 0.010),       # before the window
                ("jit_prefill_paged", 1.99, 0.020)])     # half inside
    # 40 ms of prefill programs over the 128 tokens of the traced steps
    assert read(w) == pytest.approx(1e3 * 0.040 / 0.128)


@pytest.mark.parametrize("case", ["no trace", "no prefill", "old name"])
def test_prefill_device_reads_nothing(case):
    w = window([("jit_prefill_paged", 1.1, 0.010)])
    if case == "no trace":
        w.trace = None
    elif case == "no prefill":
        w = window([("jit_prefill_paged", 1.1, 0.010)], prefill_rows=())
    else:          # a program whose prefill module is ``jit__unknown``
        w = window([("jit__unknown", 1.1, 0.010)])
    assert read(w) is None
