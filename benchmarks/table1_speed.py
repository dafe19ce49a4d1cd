"""Paper Table 1 — execution time vs graph size (+ the `cat` lower bound).

The paper streams SNAP graphs of 1e6..1.8e9 edges; offline we run synthetic
Chung–Lu streams at 1e5..1e7 edges, assert linear scaling in m (the paper's
complexity claim), and report per-edge throughput so the Friendster-scale
runtime is a direct extrapolation.  The `stream_read` row reproduces the
paper's `cat` comparison: a pass over the edge stream that does no clustering
work (memory-bandwidth lower bound).

Each stream is produced by a segment generator (``chung_lu_segments``, O(segment)
memory), spooled once to a binary edge file, and both the `cat` pass and the
clusterer then stream that *same file* through ``BinaryFileSource`` +
``BatchPipeline`` — so `stream_read` stays a genuine pass over stored bytes
(page-cache/memory-bandwidth bound, as in the paper) and the STR rows measure
clustering an on-disk stream, not RNG throughput.  The edge list never
materializes on the heap; each STR row reports the measured peak edge-buffer
bytes next to the ``3n``-int state, which is the paper's memory claim made
visible.  Baselines (Louvain/LabelProp) are inherently non-streaming and
materialize a small stream once.
"""

from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from repro.cluster import (
    BatchPipeline,
    BinaryFileSource,
    ClusterConfig,
    GeneratorSource,
    cluster,
)
from repro.core.labelprop import label_propagation
from repro.core.louvain import louvain
from repro.graph.generators import chung_lu_segments
from repro.graph.stream import state_bytes


def _time(fn, *args, repeat=1, warm=True):
    if warm:
        fn(*args)  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return (time.perf_counter() - t0) / repeat


def _spool(n, m, seed, segment, path):
    """Generate the stream segment-by-segment and write it to ``path`` —
    O(segment) memory end to end."""
    gen = GeneratorSource(
        chung_lu_segments(n, seed=seed), m, segment_edges=segment
    )
    return BinaryFileSource.write(path, gen)


def run(sizes=(100_000, 1_000_000, 5_000_000), v_max=64, baselines_at=300_000,
        batch_edges=1 << 18):
    rows = []
    with tempfile.TemporaryDirectory(prefix="table1_streams_") as tmpdir:
        rows = _run_sizes(tmpdir, sizes, v_max, baselines_at, batch_edges)
    # linearity check + Friendster extrapolation for the streaming tier
    str_rows = [r for r in rows if r["algo"] == "STR-chunked"]
    if len(str_rows) >= 2:
        a, b = str_rows[0], str_rows[-1]
        scale = (b["seconds"] / a["seconds"]) / (b["m"] / a["m"])
        # a dimensionless ratio, not a throughput — kept out of edges_per_s
        # so baseline diffs never treat it as a measured-throughput row
        rows.append({"algo": "STR-linearity(t ratio / m ratio)", "m": b["m"],
                     "linearity_ratio": scale})
        rows.append({
            "algo": "STR-friendster-extrapolation(1.8e9 edges)",
            "m": 1_806_067_135,
            "seconds": 1_806_067_135 / b["edges_per_s"],
            "edges_per_s": b["edges_per_s"],
            # projected from the measured per-edge rate, not a run — the
            # baseline diff skips it when comparing measured values
            "extrapolated": True,
        })
    return rows


def _run_sizes(tmpdir, sizes, v_max, baselines_at, batch_edges):
    rows = []
    for m in sizes:
        n = max(m // 10, 1000)
        path = os.path.join(tmpdir, f"chung_lu_{m}.bin")
        src = _spool(n, m, seed=m % 97, segment=min(batch_edges, m), path=path)
        chunked_cfg = ClusterConfig(n=n, v_max=v_max, backend="chunked",
                                    chunk=4096, batch_edges=batch_edges)

        def stream_read(source):
            # the paper's `cat`: touch every stored edge, cluster nothing
            acc = np.int32(0)
            for batch in BatchPipeline(source, batch_edges):
                acc ^= np.bitwise_xor.reduce(batch.edges, axis=None)
            return acc

        t_read = _time(stream_read, src)
        res = cluster(src, chunked_cfg)  # warmup/compile + buffer measurement
        t_str = _time(lambda s: cluster(s, chunked_cfg), src, warm=False)
        rows.append(
            {"algo": "stream_read(cat)", "m": m, "seconds": t_read,
             "edges_per_s": m / t_read}
        )
        rows.append(
            {"algo": "STR-chunked", "m": m, "seconds": t_str,
             "edges_per_s": m / t_str,
             "peak_buffer_bytes": res.info["peak_buffer_bytes"],
             "state_bytes": state_bytes(n)}
        )
        if m <= baselines_at:
            edges = src.materialize()  # baselines are not streaming
            dense_cfg = ClusterConfig(n=n, v_max=v_max, backend="dense")
            t_oracle = _time(lambda e: cluster(e, dense_cfg), edges)
            t_lv = _time(lambda e: louvain(e, n, seed=0), edges)
            t_lp = _time(lambda e: label_propagation(e, n, sweeps=3), edges)
            rows.append({"algo": "STR-sequential(paper)", "m": m,
                         "seconds": t_oracle, "edges_per_s": m / t_oracle})
            rows.append({"algo": "Louvain", "m": m, "seconds": t_lv,
                         "edges_per_s": m / t_lv})
            rows.append({"algo": "LabelProp", "m": m, "seconds": t_lp,
                         "edges_per_s": m / t_lp})
        os.remove(path)  # spooled stream no longer needed; bounds disk use
    return rows


def main():
    for r in run():
        if "linearity_ratio" in r:
            print(f"{r['algo']:42s} m={r['m']:>12,d} "
                  f"ratio={r['linearity_ratio']:.3f}")
            continue
        extra = ""
        if "peak_buffer_bytes" in r:
            extra = (f"  buf={r['peak_buffer_bytes']/1e6:.1f}MB "
                     f"state={r['state_bytes']/1e6:.1f}MB")
        print(f"{r['algo']:42s} m={r['m']:>12,d} {r['seconds']:10.3f}s "
              f"{r['edges_per_s']:>14,.0f} edges/s{extra}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
