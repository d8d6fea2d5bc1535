"""The trace reduction of the chip benchmark, on a small trace recorded on
a TPU v5 lite by ``record_trace.py``: host spans ``busy``, ``wait`` (50 ms
of host sleep, the device idle) and ``busy``; each ``busy`` runs a jitted
matmul (``jit__lambda``) and a Pallas kernel in ``jit_double``."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import trace as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return tr.reduce(DATA, ("busy", "wait"))


def test_one_device_and_its_busy_time_is_the_union_of_its_ops(summary):
    assert summary.devices == 1
    union = tr.union((o.start, o.start + o.dur) for o in summary.ops)
    assert summary.busy_s == pytest.approx(sum(b - a for a, b in union))
    # overlapping ops count once: the union is below the plain sum
    assert summary.busy_s <= sum(o.dur for o in summary.ops)
    assert 0 < summary.busy_s < summary.window_s


def test_kernel_time_by_name(summary):
    kernel = [o for o in summary.ops if o.name.startswith("%double")]
    assert len(kernel) == 2
    assert summary.op_seconds(("%double",)) == pytest.approx(
        sum(o.dur for o in kernel))
    assert summary.op_seconds(("no such kernel",)) == 0.0
    names = [n for n, _ in summary.top_ops(10)]
    assert "%double.1" in names and "%fusion" in names


def test_program_executions(summary):
    names = sorted(m.name.split("(")[0] for m in summary.modules)
    assert names == ["jit__lambda", "jit__lambda", "jit_double", "jit_double"]


def test_gaps_are_tagged_by_the_host_span_open_during_them(summary):
    assert [s.name for s in summary.spans] == ["busy", "wait", "busy"]
    wait = summary.spans[1]
    longest_in_wait = max(sec for name, sec in summary.longest_gaps(10)
                          if name == "wait")
    # the device idles through the 50 ms sleep
    assert longest_in_wait == pytest.approx(wait.dur, abs=2e-3)
    gaps = summary.gaps()
    assert sum(b - a for a, b in gaps) == pytest.approx(
        summary.window_s - summary.busy_s)
