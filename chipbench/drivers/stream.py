"""Driver ``stream``: batch clustering of a graph read from a file, through
``StreamClusterer``, in a closed loop.

Set-up makes the cell's edges from the seed and writes the file its traffic
names (in a directory of the run's own inside the checkout, removed at the
end), then warms up: two batches through a fresh clusterer compile (or
load from the compile cache) the cell's one batch shape.  The window then
runs passes back to back; a pass is ``StreamClusterer(config).fit(source)``,
``finalize()`` and ``.labels`` (canonical labels on the host, as users get
them), each from fresh state.  :class:`WindowSource` ends the pass still
running at the deadline, at the next batch boundary; that pass is finalized
and counted too.

After the window every pass's labels and ``edges_seen`` are compared with
the plain reference of the configuration's rule, run over the same rows of
the generated edges (not of the file, so the file's reading is checked as
well).
"""

from __future__ import annotations

import contextlib
import gc
import os
import shutil
import sys
import time
from typing import Iterator, List, Optional

import jax
import numpy as np
from repro.cluster import ClusterConfig, StreamClusterer
from repro.cluster.api import DEFAULT_BATCH_EDGES
from repro.compile_cache import use_compile_cache
from repro.graph.sources import BinaryFileSource, EdgeSource

from chipbench import graphgen, reference, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA_DIR = os.path.join(ROOT, ".chipbench_data")

# The compared numbers are exact: one wrong label or edge count is a fault.
LIMITS = {"label_mismatches": 0, "edges_seen_gap": 0}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _span(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


class WindowSource(EdgeSource):
    """The program's source ``base``, read up to the first batch boundary at
    or after ``deadline``; counts the rows it delivers and the host time
    spent inside ``base``'s slice iterator.  The pipeline reads ahead of
    the device, so a pass whose read ended before the deadline runs to its
    end."""

    def __init__(self, base: EdgeSource, deadline: float, batch_rows: int, trace: bool):
        self.base = base
        self.deadline = deadline
        self.batch_rows = batch_rows
        self.trace = trace
        self.rows = 0  # rows delivered
        self.read_s = 0.0
        self.cut = False  # ended by the deadline, not by the file's end

    def iter_slices(self, start: int = 0) -> Iterator[np.ndarray]:
        it = iter(self.base.iter_slices(start))
        rows = start
        while True:
            t0 = time.perf_counter()
            with _span("source_read", self.trace):
                sl = next(it, None)
            self.read_s += time.perf_counter() - t0
            if sl is None:
                return
            if time.perf_counter() >= self.deadline:
                room = -rows % self.batch_rows
                if room == 0 and rows > start:
                    self.cut = True
                    return
                if room:
                    sl = sl[:room]
            rows += sl.shape[0]
            self.rows = rows
            yield sl


def write_input(traffic: dict, edges: np.ndarray, run_dir: str) -> str:
    """Write ``edges`` in the traffic's format; returns the path."""
    fmt = traffic["format"]
    if fmt != "binary":
        raise ValueError(f"unknown file format {fmt!r}")
    path = os.path.join(run_dir, "edges.bin")
    graphgen.write_binary(path, edges)
    return path


def cluster_config(conf: dict, override: Optional[dict] = None) -> ClusterConfig:
    kwargs = dict(conf["cluster"])
    kwargs.update(override or {})
    return ClusterConfig(n=int(conf["n"]), **kwargs)


def make_edges(conf: dict, seed: int) -> np.ndarray:
    """The configuration's edges from the seed, in arrival order."""
    scale, edgefactor = int(conf["scale"]), int(conf["edgefactor"])
    if (int(conf["n"]), int(conf["m"])) != (1 << scale, edgefactor << scale):
        raise ValueError("n and m disagree with the scale and edge factor")
    return graphgen.kronecker_edges(scale, edgefactor, conf["initiator"], seed)


def reference_labels(conf: dict, edges: np.ndarray, stops: List[int]) -> dict:
    """``{rows: canonical labels}`` of the configuration's rule."""
    rule = conf["guarantee"]["rule"]
    n, v_max = int(conf["n"]), int(conf["cluster"]["v_max"])
    if rule == "sequential":
        cs = reference.sequential(edges, n, v_max, stops)
    elif rule == "jacobi":
        cs = reference.jacobi(edges, n, v_max, int(conf["guarantee"]["chunk"]), stops)
    else:
        raise ValueError(f"unknown rule {rule!r}")
    return {r: reference.canonical(c) for r, c in cs.items()}


def compare(conf: dict, edges: np.ndarray, passes: List[dict]) -> dict:
    """Every pass's labels and ``edges_seen`` against the reference.  A pass
    the deadline cut is due the rows it read; any other pass, the file."""
    t0 = time.perf_counter()
    m = edges.shape[0]
    ref = reference_labels(conf, edges, sorted({p["rows"] for p in passes}))
    mism, gap, failed = 0, 0, 0
    for p in passes:
        want = ref[p["rows"]]
        got = np.asarray(p["labels"])
        bad = int(np.count_nonzero(got != want)) if got.shape == want.shape else want.size
        due = reference.live_count(edges[: p["rows"] if p["cut"] else m])
        g = abs(int(p["edges_seen"]) - due)
        mism, gap = max(mism, bad), max(gap, g)
        failed += bool(bad or g)
    log(f"reference: {time.perf_counter() - t0:.3f} s for {len(passes)} passes")
    checks = {
        "label_mismatches": {"value": mism, "limit": LIMITS["label_mismatches"]},
        "edges_seen_gap": {"value": gap, "limit": LIMITS["edges_seen_gap"]},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return {"checks": checks, "failed": failed, "correct": correct}


def one_pass(cfg: ClusterConfig, source: WindowSource) -> dict:
    """Fit, finalize and canonical labels; the pass's numbers."""
    sc = StreamClusterer(cfg)
    sc.fit(source)
    # the pass's last dispatches finish before finalize is timed, so the
    # span holds the copy to the host and the labels, not device work
    sc.state.block_until_ready()
    t0 = time.perf_counter()
    with _span("finalize", source.trace):
        result = sc.finalize()
        labels = result.labels
    finalize_s = time.perf_counter() - t0
    return {
        "rows": source.rows,
        "cut": source.cut,
        "read_s": source.read_s,
        "finalize_s": finalize_s,
        "dispatches": sc.stream_dispatches,
        "edges_seen": sc.edges_seen,
        "labels": labels,
    }


def memory_peak_bytes() -> int:
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()
    ]
    return int(max(peaks))


class _Builds:
    """Programs compiled or loaded from the compile cache (JAX's backend
    compile event); one in the window means a shape was not warmed up."""

    count = 0

    @classmethod
    def listen(cls) -> None:
        if not getattr(cls, "_listening", False):
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._listening = True

    @classmethod
    def _on(cls, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            cls.count += 1


def run(
    cell: dict,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    override: Optional[dict] = None,
) -> dict:
    """One run of the cell; the record the metric readers and ``run.py``
    read.  ``override`` replaces configuration knobs (controls, tests)."""
    conf, traffic = cell["config_data"], cell["traffic_data"]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    use_compile_cache()
    _Builds.listen()

    t_init = time.perf_counter()
    edges = make_edges(conf, seed)
    t_edges = time.perf_counter()
    run_dir = os.path.join(DATA_DIR, f"run-{os.getpid()}")
    os.makedirs(run_dir, exist_ok=True)
    path = write_input(traffic, edges, run_dir)
    cfg = cluster_config(conf, override)
    batch_rows = cfg.batch_edges or DEFAULT_BATCH_EDGES
    batch_rows = -(-batch_rows // cfg.chunk) * cfg.chunk
    t_file = time.perf_counter()

    # warm-up: two batches through a fresh clusterer (first and later
    # dispatches), the only shape the window uses
    warm = WindowSource(BinaryFileSource(path), 0.0, 2 * batch_rows, False)
    one_pass(cfg, warm)
    del warm
    setup_s = time.perf_counter() - t_start
    builds_setup = _Builds.count
    log(
        f"setup: {setup_s:.3f} s: imports and chip {t_init - t_start:.3f} s, "
        f"edges {t_edges - t_init:.3f} s, file {t_file - t_edges:.3f} s, "
        f"warm-up {time.perf_counter() - t_file:.3f} s; batch {batch_rows} rows"
    )

    tdir = os.path.join(run_dir, "trace")
    if trace:
        xplane.start(tdir)
    passes: List[dict] = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with _span("window", trace):
        while True:
            src = WindowSource(BinaryFileSource(path), deadline, batch_rows, trace)
            with _span("pass", trace):
                passes.append(one_pass(cfg, src))
            if src.cut or time.perf_counter() >= deadline:
                break
    t1 = time.perf_counter()
    summary = None
    if trace:
        summary = xplane.stop(tdir)
        log(f"trace: reduced in {time.perf_counter() - t1:.3f} s")
    window_s = t1 - t0
    builds_window = _Builds.count - builds_setup
    live = sum(reference.live_count(edges[: p["rows"]]) for p in passes)
    log(
        f"window: {window_s:.3f} s, {len(passes)} passes, {live} live edges, "
        f"{builds_window} programs compiled or loaded"
    )
    peak = memory_peak_bytes()
    gc.collect()

    verdict = compare(conf, edges, passes)
    record = {
        "device_kind": jax.devices()[0].device_kind,
        "passes": [{k: v for k, v in p.items() if k != "labels"} for p in passes],
        "live_edges": live,
        "trace": summary,
        "end_to_end": {"edges_per_s": live / window_s, "setup_s": setup_s},
        "device": {"memory_peak_bytes": peak},
        "attempted": len(passes),
        **verdict,
    }
    if summary is not None:
        record["device"]["busy_s"] = summary.busy_s
        record["device"]["window_s"] = summary.window_s
        record["breakdown"] = summary.breakdown()
    shutil.rmtree(run_dir, ignore_errors=True)
    return record
