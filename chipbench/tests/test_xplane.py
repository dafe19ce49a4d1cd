"""The trace reduction, on a hand-made trace and on a small recorded one."""

import os
from types import SimpleNamespace as NS

import pytest

from chipbench import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def test_reduce_hand_made_trace():
    host = plane("/host:CPU", {
        "main": [ev("window", 1000, 10000), ev("pass", 1000, 6000),
                 ev("finalize", 6000, 1000), ev("pass", 7000, 4000)],
        "prefetch": [ev("source_read", 1000, 500), ev("source_read", 7000, 800)],
    })
    dev = plane("/device:TPU:0", {
        "XLA Modules": [ev("jit_chunked_update(3)", 1500, 4000), ev("jit_chunked_update(4)", 8000, 2000),
                        ev("jit_other(5)", 500, 1000)],
        "XLA Ops": [ev("%scatter.1 = s32[8] scatter(...)", 1500, 2000),
                    ev("%fusion.2 = s32[8] fusion(...)", 3000, 2500),  # overlap
                    ev("%scatter.1 = s32[8] scatter(...)", 8000, 2000), ev("%copy", 500, 1000)],
    })
    s = xplane.reduce([host, dev, plane("/device:TPU:0 stats", {})])
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(10000e-9)
    # ops clipped to the window [1000, 11000): copy 500 of 1000 counts
    busy = (1500 - 1000) + (5500 - 1500) + 2000
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert s.programs_s["jit_chunked_update"] == pytest.approx(6000e-9)
    assert s.device_s(r"^jit_chunked_update$") == pytest.approx(6000e-9)
    assert s.device_s(r"^%scatter", level="ops") == pytest.approx(4000e-9)
    # idle gaps: [5500, 8000) lies in finalize then the second pass
    # (owner at its midpoint 6750: finalize), [10000, 11000) in the pass
    assert s.gaps[0] == ("finalize", pytest.approx(2500e-9))
    assert s.gaps[1] == ("pass", pytest.approx(1000e-9))
    b = s.breakdown()
    assert b["device_ops"][0][0] == "%scatter.1"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_reduce_needs_the_window_span():
    with pytest.raises(RuntimeError):
        xplane.reduce([plane("/host:CPU", {"main": [ev("pass", 0, 10)]})])


def test_recorded_trace():
    """A trace recorded on a TPU v5e: the chunked update and the Pallas
    kernel over 20,000 edges at n = 4,096, with ``source_read`` and
    ``pass`` spans.  It was recorded without a ``window`` span, so one
    covering the whole trace is added."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(os.path.join(DATA, "v5e-chunked-pallas.xplane.pb")).planes)
    lo, hi = 41615051, 72251572  # first start and last end in the trace
    s = xplane.reduce(planes + [plane("/host:bench", {"w": [ev("window", lo, hi - lo)]})])
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.030636521)
    assert s.busy_s == pytest.approx(0.009917891)
    assert s.device_s(r"^jit_chunked_update$") == pytest.approx(0.002735689)
    assert s.device_s(r"^jit_pallas_update$") == pytest.approx(0.007189865)
    assert s.host_s["source_read"] == pytest.approx(0.00540168)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "%pallas_update.1"
    assert b["device_ops"][1][0] == "%while"  # the chunked update's scan
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0] == ["source_read", pytest.approx(0.006627603)]
    gaps = [g for _, g in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
