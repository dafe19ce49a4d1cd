"""The program's own spans in a profiler trace, and a traced run that
reports them.

The program marks its layers with ``repro.*`` spans (listed in
``repro.obs``).  :func:`reduce` takes from a trace's planes, clipped to the
benchmark's ``window`` span:

* the self time of each span name: its spans' durations less the part that
  ``repro.*`` spans nested in them on the same host line cover;
* the number of spans of each name;
* the owner of each of the longest device idle gaps: the innermost
  ``repro.*`` span, at the gap's midpoint, on the line that holds the
  ``repro.dispatch`` spans (the dispatching thread).  Where that span is
  ``repro.pipeline.wait`` the owner is what the prefetch thread was in then,
  written ``repro.pipeline.wait>repro.source.read``.  A gap that no program
  span covers keeps the owner ``xplane`` gives it.

``xplane`` reads none of this yet.  Run one cell traced, as ``run.py
--trace 1`` does, with these numbers added to its result line under
``program``:

    python3 -m chipbench.spans --workload <cell> --seed <n> --seconds <s>

from the root of a checkout.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

from chipbench import run, xplane

T_START = time.perf_counter()

PREFIX = "repro."
DISPATCH = "repro.dispatch"
WAIT = "repro.pipeline.wait"

# (metric, span, unit of the base): self milliseconds per million live
# edges, or per pass
METRICS = (
    ("pipeline_read_ms_per_medge", "repro.source.read", "medge"),
    ("pipeline_stage_ms_per_medge", "repro.pipeline.stage", "medge"),
    ("pipeline_wait_ms_per_medge", WAIT, "medge"),
    ("dispatch_ms_per_medge", DISPATCH, "medge"),
    ("finalize_fetch_ms_per_pass", "repro.finalize.fetch", "pass"),
    ("canonical_labels_ms_per_pass", "repro.labels", "pass"),
)

Span = Tuple[int, int, str]  # start, end (ns), name


@dataclass
class Program:
    self_s: Dict[str, float] = field(default_factory=dict)
    n: Dict[str, int] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)


def _program_lines(planes) -> List[List[Span]]:
    """Per host line, its ``repro.*`` spans, parents before children."""
    lines = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [
                (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns), ev.name)
                for ev in line.events
                if ev.name.startswith(PREFIX)
            ]
            if spans:
                lines.append(sorted(spans, key=lambda sp: (sp[0], -sp[1])))
    return lines


def _self_times(spans: List[Span], w0: int, w1: int, out: Dict[str, float]) -> None:
    """Add each span's self time within ``[w0, w1)`` to ``out``.  Spans of
    one thread nest, so a span's children are disjoint."""

    def clipped(sp: Span) -> int:
        return max(0, min(sp[1], w1) - max(sp[0], w0))

    own = [clipped(sp) for sp in spans]
    stack: List[int] = []  # indices of the open spans
    for k, sp in enumerate(spans):
        while stack and spans[stack[-1]][1] <= sp[0]:
            stack.pop()
        if stack and sp[1] <= spans[stack[-1]][1]:
            own[stack[-1]] -= clipped(sp)
        stack.append(k)
    for sp, s in zip(spans, own):
        out[sp[2]] = out.get(sp[2], 0.0) + s / 1e9


def _innermost(spans: List[Span], t: int):
    """The name of the span that covers ``t`` and starts last, or None."""
    inner = None
    for s, e, name in spans:
        if s > t:
            break
        if t < e:
            inner = name
    return inner


def _idle_gaps(planes, w0: int, w1: int) -> List[Tuple[int, int]]:
    """The device idle gaps in ``[w0, w1)``, longest first, as ``xplane``
    finds them."""
    gaps: List[Tuple[int, int]] = []
    for plane in planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        iv = [
            (max(int(ev.start_ns), w0), min(int(ev.start_ns) + int(ev.duration_ns), w1))
            for line in plane.lines
            if line.name == xplane.OPS_LINE
            for ev in line.events
        ]
        a = np.array(iv, np.int64).reshape(-1, 2)
        a = a[a[:, 1] > a[:, 0]]
        bs, be = xplane._union(a[:, 0], a[:, 1])
        idle_s, idle_e = np.append(w0, be), np.append(bs, w1)
        keep = idle_e > idle_s
        gaps += list(zip(idle_s[keep].tolist(), idle_e[keep].tolist()))
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps


def _owner(lines: List[List[Span]], host_spans, t: int) -> str:
    callers = [sp for sp in lines if any(name == DISPATCH for _, _, name in sp)]
    inner = next(filter(None, (_innermost(sp, t) for sp in callers)), None)
    if inner is None:
        return xplane._owner(host_spans, t)
    if inner != WAIT:
        return inner
    other = [sp for sp in lines if all(sp is not c for c in callers)]
    behind = next(filter(None, (_innermost(sp, t) for sp in other)), None)
    return inner if behind is None else f"{inner}>{behind}"


def reduce(planes) -> Program:
    """The program's spans in a trace's planes (``ProfileData.planes``)."""
    planes = list(planes)
    host_spans = xplane._host_spans(planes)
    if not host_spans["window"]:
        raise RuntimeError("no 'window' span in the trace")
    w0 = min(s for s, _ in host_spans["window"])
    w1 = max(e for _, e in host_spans["window"])
    out = Program()
    lines = _program_lines(planes)
    for spans in lines:
        _self_times(spans, w0, w1, out.self_s)
        for s, e, name in spans:
            if e > w0 and s < w1:
                out.n[name] = out.n.get(name, 0) + 1
    out.gaps = [
        (_owner(lines, host_spans, (a + b) // 2), (b - a) / 1e9)
        for a, b in _idle_gaps(planes, w0, w1)[: xplane.TOP]
    ]
    return out


def metrics(record: dict, program: Program) -> Dict[str, float]:
    """The per-layer figures the spans give; a span that is absent gives
    none."""
    bases = {"medge": record["live_edges"] / 1e6, "pass": len(record["passes"])}
    out = {}
    for metric, name, base in METRICS:
        if name in program.n and bases[base]:
            out[metric] = 1e3 * program.self_s[name] / bases[base]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one cell traced, with the program's spans.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    run._paths()
    cell = run.load_cell(args.workload)
    device = run.require_chips(int(cell["chips"]))
    driver = importlib.import_module(f"chipbench.drivers.{cell['traffic_data']['driver']}")
    kept = {}
    original = xplane.stop

    def stop(log_dir: str) -> xplane.Summary:
        # the driver's ``xplane.stop``, reading the trace's planes once for
        # both reductions
        import jax
        from jax.profiler import ProfileData

        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file under {log_dir}, found {files}")
        planes = list(ProfileData.from_file(files[0]).planes)
        kept["program"] = reduce(planes)
        return xplane.reduce(planes)

    xplane.stop = stop
    try:
        record = driver.run(
            cell, seed=args.seed, seconds=args.seconds, trace=True, t_start=T_START
        )
    finally:
        xplane.stop = original
    device.update(record["device"])
    program = kept["program"]
    result = {
        "correct": record["correct"],
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": run.read_metrics(cell, record, True),
        "device": device,
        "breakdown": record["breakdown"],
        "program": {
            "metrics": metrics(record, program),
            "self_s": program.self_s,
            "n": program.n,
            "idle_gaps": [[k, v] for k, v in program.gaps],
        },
        "checks": record["checks"],
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
