"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics and the ``breakdown`` read.

The benchmark marks its own host spans with ``jax.profiler.TraceAnnotation``:
``window`` around the measured window, ``pass`` around each pass,
``source_read`` around each read of the program's source, and ``finalize``
around ``finalize()`` and ``.labels``.  From the device planes it takes the
op events (busy time, the ops that took most time) and the program events
(time per jitted program), all clipped to the ``window`` span.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
HOST_SPANS = ("window", "pass", "source_read", "finalize")
# what the host was doing in an idle gap, most specific first
GAP_OWNERS = ("finalize", "source_read", "pass", "window")
TOP = 10


def options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python calls would swamp the host planes
    return opts


def start(log_dir: str) -> None:
    import jax

    jax.profiler.start_trace(log_dir, profiler_options=options())


def stop(log_dir: str) -> "Summary":
    import jax

    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, found {files}")
    return summarize(files[0])


def _union(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """The union of intervals ``[starts, ends)`` as sorted disjoint ones."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], e[last]


def _op(name: str) -> str:
    """An op event's name is its HLO text; keep the instruction's name."""
    return name.split(" = ", 1)[0]


def _program(name: str) -> str:
    """A program event's name without its run-id suffix: ``jit_f(12)``."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclass
class Summary:
    window_s: float = 0.0
    busy_s: float = 0.0
    n_devices: int = 0
    programs_s: Dict[str, float] = field(default_factory=dict)
    ops_s: Dict[str, float] = field(default_factory=dict)
    host_s: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def device_s(self, pattern: str, level: str = "programs") -> float:
        """Device seconds (per chip) of the programs or ops whose name
        matches ``pattern``."""
        table = self.programs_s if level == "programs" else self.ops_s
        rx = re.compile(pattern)
        return sum(s for name, s in table.items() if rx.search(name))

    def breakdown(self) -> dict:
        ops = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]],
        }


def _host_spans(planes) -> Dict[str, List[Tuple[int, int]]]:
    spans: Dict[str, List[Tuple[int, int]]] = {k: [] for k in HOST_SPANS}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    s = int(ev.start_ns)
                    spans[ev.name].append((s, s + int(ev.duration_ns)))
    return spans


def _owner(spans, t: int) -> str:
    for name in GAP_OWNERS:
        for s, e in spans[name]:
            if s <= t < e:
                return name
    return "none"


def summarize(path: str) -> Summary:
    from jax.profiler import ProfileData

    return reduce(list(ProfileData.from_file(path).planes))


def reduce(planes) -> Summary:
    """The summary of a trace's planes (``ProfileData.planes``)."""
    spans = _host_spans(planes)
    if not spans["window"]:
        raise RuntimeError("no 'window' span in the trace")
    w0 = min(s for s, _ in spans["window"])
    w1 = max(e for _, e in spans["window"])
    out = Summary(window_s=(w1 - w0) / 1e9)
    for k, v in spans.items():
        a = np.array(v, np.int64).reshape(-1, 2)
        us, ue = _union(a[:, 0], a[:, 1])
        out.host_s[k] = float((ue - us).sum()) / 1e9
    busy_total = 0.0
    gaps: List[Tuple[int, int]] = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        out.n_devices += 1
        op_iv: List[Tuple[int, int]] = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, PROGRAMS_LINE):
                continue
            ops = line.name == OPS_LINE
            table = out.ops_s if ops else out.programs_s
            for ev in line.events:
                s = max(int(ev.start_ns), w0)
                e = min(int(ev.start_ns) + int(ev.duration_ns), w1)
                if e <= s:
                    continue
                name = _op(ev.name) if ops else _program(ev.name)
                table[name] = table.get(name, 0.0) + (e - s) / 1e9
                if ops:
                    op_iv.append((s, e))
        a = np.array(op_iv, np.int64).reshape(-1, 2)
        bs, be = _union(a[:, 0], a[:, 1])
        busy_total += float((be - bs).sum()) / 1e9
        idle_s = np.append(w0, be)
        idle_e = np.append(bs, w1)
        keep = idle_e > idle_s
        gaps += list(zip(idle_s[keep].tolist(), idle_e[keep].tolist()))
    if out.n_devices:
        out.busy_s = busy_total / out.n_devices
        scale = 1.0 / out.n_devices
        out.ops_s = {k: v * scale for k, v in out.ops_s.items()}
        out.programs_s = {k: v * scale for k, v in out.programs_s.items()}
    gaps.sort(key=lambda g: g[0] - g[1])
    out.gaps = [(_owner(spans, (a + b) // 2), (b - a) / 1e9) for a, b in gaps[:TOP]]
    return out
