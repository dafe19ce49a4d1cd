"""The reduction of the program's own spans (``chipbench/spans.py``), on a
hand-made trace, on a small one recorded on a TPU v5e, and in a whole
traced run at a tiny size."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from chipbench import spans, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")


def ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def hand_made():
    """The window is [1000, 21000); the caller fits, finalizes and takes
    the labels, the prefetch thread stages and reads.  Seven device idle
    gaps, each in another kind of host work."""
    caller = [
        ev("window", 1000, 21000), ev("pass", 1000, 15000),
        ev("repro.fit", 1000, 12000), ev("repro.clusterer.init", 1000, 1400),
        ev("repro.pipeline.wait", 1400, 3000), ev("repro.dispatch", 3000, 3500),
        ev("repro.pipeline.wait", 3500, 4000), ev("repro.dispatch", 4000, 4500),
        ev("repro.pipeline.wait", 4500, 5000), ev("repro.dispatch", 5000, 5600),
        ev("finalize", 12000, 15000), ev("repro.finalize", 12000, 13000),
        ev("repro.finalize.fetch", 12200, 12900), ev("repro.labels", 13000, 14800),
    ]
    prefetch = [
        # before the window: clipped to [1000, 1200), its read left out
        ev("repro.pipeline.stage", 500, 1200), ev("repro.source.read", 600, 900),
        ev("repro.pipeline.stage", 1200, 2800), ev("repro.source.read", 1300, 2500),
        ev("source_read", 1300, 2400),
        ev("repro.pipeline.stage", 3400, 4000), ev("repro.source.read", 3400, 3500),
    ]
    busy = [(1000, 1500), (2600, 3600), (3900, 4600), (5000, 12100),
            (12700, 13200), (14700, 14850), (15000, 20000)]
    dev = plane("/device:TPU:0", {"XLA Ops": [ev("%op", s, e) for s, e in busy]})
    return [plane("/host:CPU", {"main": caller, "prefetch": prefetch}), dev]


def test_self_time_counts_and_window():
    p = spans.reduce(hand_made())
    want_ns = {
        "repro.fit": 11000 - (400 + 1600 + 500 + 500 + 500 + 500 + 600),
        "repro.clusterer.init": 400,
        "repro.pipeline.wait": 1600 + 500 + 500,
        "repro.dispatch": 500 + 500 + 600,
        "repro.finalize": 1000 - 700,
        "repro.finalize.fetch": 700,
        "repro.labels": 1800,
        # 200 clipped, 1600 less its read, 600 less its read
        "repro.pipeline.stage": 200 + (1600 - 1200) + (600 - 100),
        # the benchmark's own source_read span is no child
        "repro.source.read": 0 + 1200 + 100,
    }
    assert set(p.self_s) == set(want_ns)
    for name, ns in want_ns.items():
        assert p.self_s[name] == pytest.approx(ns * 1e-9), name
    assert p.n == {
        "repro.fit": 1, "repro.clusterer.init": 1, "repro.pipeline.wait": 3,
        "repro.dispatch": 3, "repro.finalize": 1, "repro.finalize.fetch": 1,
        "repro.labels": 1, "repro.pipeline.stage": 3, "repro.source.read": 2,
    }


def test_idle_gap_owners():
    p = spans.reduce(hand_made())
    assert p.gaps == [
        ("repro.labels", pytest.approx(1500e-9)),
        ("repro.pipeline.wait>repro.source.read", pytest.approx(1100e-9)),
        ("window", pytest.approx(1000e-9)),  # after the pass: no program span
        ("repro.finalize.fetch", pytest.approx(600e-9)),
        ("repro.pipeline.wait", pytest.approx(400e-9)),  # the prefetch thread idle
        ("repro.pipeline.wait>repro.pipeline.stage", pytest.approx(300e-9)),
        ("finalize", pytest.approx(150e-9)),  # after the labels, in the benchmark's span
    ]
    # the same gaps as xplane finds, owned as it owns them where no
    # program span covers them
    s = xplane.reduce(hand_made())
    assert [d for _, d in s.gaps] == [d for _, d in p.gaps]
    assert s.gaps[2][0] == "window" and s.gaps[6][0] == "finalize"


def test_metrics_per_medge_and_per_pass():
    p = spans.Program(
        self_s={"repro.source.read": 0.5, "repro.dispatch": 2.0, "repro.labels": 1.2},
        n={"repro.source.read": 4, "repro.dispatch": 4, "repro.labels": 2},
    )
    got = spans.metrics({"live_edges": 4_000_000, "passes": [{}, {}]}, p)
    assert got == {
        "pipeline_read_ms_per_medge": pytest.approx(125.0),
        "dispatch_ms_per_medge": pytest.approx(500.0),
        "canonical_labels_ms_per_pass": pytest.approx(600.0),
    }


def test_needs_the_window_span():
    with pytest.raises(RuntimeError):
        spans.reduce([plane("/host:CPU", {"main": [ev("repro.fit", 0, 10)]})])


def test_recorded_trace():
    """A trace recorded on a TPU v5e: one pass of the chunked tier and one
    of the Pallas tier over the same 20,000 edges at n = 4,096 (2,048-edge
    batches, the file read in 3,000-row slices), each a ``one_pass`` of the
    benchmark's driver inside its ``window`` and ``pass`` spans."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(os.path.join(DATA, "v5e-program-spans.xplane.pb")).planes)
    p = spans.reduce(planes)
    assert p.n == {
        "repro.fit": 2, "repro.clusterer.init": 2, "repro.finalize": 2,
        "repro.finalize.fetch": 2, "repro.labels": 2, "repro.dispatch": 20,
        # 11 items a pass (10 batches and the end), one more pull past it
        "repro.pipeline.wait": 22, "repro.pipeline.stage": 24,
        "repro.source.read": 16,  # 7 slices and the end, a pass
    }
    want = {
        "repro.clusterer.init": 0.004614069, "repro.dispatch": 0.0176386,
        "repro.finalize": 3.328e-05, "repro.finalize.fetch": 0.00360078,
        "repro.fit": 0.002657611, "repro.labels": 0.00083457,
        "repro.pipeline.stage": 0.002672811, "repro.pipeline.wait": 0.001474949,
        "repro.source.read": 0.001614049,
    }
    assert p.self_s == pytest.approx(want)
    assert [o for o, _ in p.gaps] == (
        ["repro.finalize.fetch"] * 2 + ["repro.clusterer.init"] * 2 + ["repro.dispatch"] * 6
    )
    assert p.gaps[0][1] == pytest.approx(0.003876992)
    # the gaps xplane finds, in the benchmark's spans only
    s = xplane.reduce(planes)
    assert s.window_s == pytest.approx(0.032928028)
    assert [d for _, d in s.gaps] == [d for _, d in p.gaps]
    assert [o for o, _ in s.gaps] == ["finalize"] * 2 + ["pass"] * 8
    # the program's read encloses the benchmark's
    assert p.self_s["repro.source.read"] >= s.host_s["source_read"] == pytest.approx(0.001247158)


def test_traced_run(monkeypatch, capsys):
    """``python3 -m chipbench.spans`` at a tiny size, past the look for a
    chip: the result line carries the program's spans, and the driver's
    ``xplane.stop`` is put back afterwards."""
    from chipbench import run
    from chipbench.tests.tiny import tiny_cell

    cell = tiny_cell("g500-chunked-bin", scale=11, edgefactor=8, batch=2048)
    monkeypatch.setattr(run, "load_cell", lambda name: cell)
    monkeypatch.setattr(run, "require_chips", lambda chips: {"platform": "cpu", "kind": "cpu", "count": 1})
    stop = xplane.stop
    assert spans.main(["--workload", "g500-chunked-bin", "--seed", str(2**31 + 11),
                       "--seconds", "0.2"]) == 0
    assert xplane.stop is stop
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and list(out)[-1] == "checks"
    prog = out["program"]
    assert set(prog["metrics"]) == {m for m, _, _ in spans.METRICS}
    assert all(v > 0 for v in prog["metrics"].values())
    passes = out["attempted"]
    for name in ("repro.fit", "repro.clusterer.init", "repro.finalize",
                 "repro.finalize.fetch", "repro.labels"):
        assert prog["n"][name] == passes, name
    # one span per dispatch the counter counts: the time per dispatch reads
    # the same over the spans and over the counter
    per_medge = out["metrics"]["dispatches_per_medge"]["value"]
    ms = 1e3 * prog["self_s"]["repro.dispatch"]
    assert prog["metrics"]["dispatch_ms_per_medge"] / per_medge == pytest.approx(
        ms / prog["n"]["repro.dispatch"])
    assert prog["idle_gaps"] == []  # no device planes off a TPU
