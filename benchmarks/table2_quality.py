"""Paper Table 2 — detection quality (avg F1 / NMI) vs baselines.

SNAP ground-truth graphs are not available offline; we use SBM streams with
planted communities in two regimes mirroring the paper's datasets: many small
communities (SNAP-like: Amazon/DBLP ground truth averages ~10-30 nodes) and
fewer large ones.  STR runs the one-pass multi-v_max sweep (paper §2.5) with
density-based selection; the best-in-sweep entry is also reported (upper
bound of the selector).  Distributed STR (8 shards) quantifies the 2-level
merge quality cost.  All STR tiers run through ``repro.cluster``.  The
stream is produced by a segment generator (``sbm_segments``) and
materialized exactly once for the F1/NMI/Q *evaluation*, which reads the
whole graph by definition; the clustering tiers themselves all stream
(every backend is resumable/out-of-core since PR 3), and that ingestion
path is measured in ``table1_speed`` and the ``streaming_tiers`` smoke
rows instead.
"""

from __future__ import annotations

import time

import numpy as np

from repro.cluster import (
    ClusterConfig,
    GeneratorSource,
    avg_f1,
    canonical_labels,
    cluster,
    modularity,
    nmi,
)
from repro.core.labelprop import label_propagation
from repro.core.louvain import louvain
from repro.graph.generators import sbm_segments

REGIMES = {
    "sbm-small-comm": dict(n=20_000, k=1000, avg_degree=10, p_intra=0.7),
    "sbm-large-comm": dict(n=10_000, k=100, avg_degree=16, p_intra=0.8),
}

V_MAXES = (8, 16, 32, 64, 128, 256, 512, 1024)


def run(regimes=None):
    rows = []
    for regime, kw in (REGIMES if regimes is None else regimes).items():
        n, k = kw["n"], kw["k"]
        m = int(n * kw["avg_degree"] / 2)
        segment, truth = sbm_segments(n, k, p_intra=kw["p_intra"], seed=11)
        source = GeneratorSource(segment, m, segment_edges=1 << 15)
        edges = source.materialize()  # one copy: clusterers + evaluation

        def add(name, labels, seconds, **extra):
            labels = canonical_labels(labels)
            rows.append({
                "regime": regime, "algo": name,
                "f1": avg_f1(labels, truth), "nmi": nmi(labels, truth),
                "modularity": modularity(edges, labels), "seconds": seconds,
                **extra,
            })

        def refine_fields(info):
            # the refinement memory/fidelity claim, visible per row
            return dict(
                refine_sketch_peak_bytes=info["refine_sketch_peak_bytes"],
                refine_dropped_weight=info["refine_dropped_weight"],
                refine_supernodes=info["refine_supernodes"],
                refine_communities=info["refine_communities"],
                refine_replay_rows=info["refine_replay_rows"],
            )

        t0 = time.perf_counter()
        sweep = cluster(edges, ClusterConfig(
            n=n, backend="multiparam", v_maxes=V_MAXES, criterion="density"))
        t1 = time.perf_counter()
        add("STR(sweep,density-pick)", sweep.labels, t1 - t0)

        sweep_labels = sweep.info["sweep_labels"]
        f1s = [
            avg_f1(canonical_labels(np.asarray(sweep_labels[a])), truth)
            for a in range(len(V_MAXES))
        ]
        best = int(np.argmax(f1s))
        add(f"STR(best v_max={V_MAXES[best]})", np.asarray(sweep_labels[best]),
            t1 - t0)

        # the refinement tiers (DESIGN.md §11): same one-pass sweep, plus a
        # contracted-supergraph refinement at finalize — sketch-only
        # (louvain) and sketch+buffered-replay, the quality acceptance row
        t0 = time.perf_counter()
        ref_lv = cluster(edges, ClusterConfig(
            n=n, backend="multiparam", v_maxes=V_MAXES, criterion="density",
            refine="louvain"))
        add("STR(sweep)+refine(louvain)", ref_lv.labels,
            time.perf_counter() - t0, refine=ref_lv.config.refine,
            **refine_fields(ref_lv.info))

        t0 = time.perf_counter()
        ref_rp = cluster(edges, ClusterConfig(
            n=n, backend="multiparam", v_maxes=V_MAXES, criterion="density",
            refine="labelprop+replay"))
        add("STR(sweep)+refine", ref_rp.labels, time.perf_counter() - t0,
            refine=ref_rp.config.refine, **refine_fields(ref_rp.info))

        t0 = time.perf_counter()
        dist = cluster(edges, ClusterConfig(
            n=n, v_max=V_MAXES[best], backend="distributed", n_shards=8,
            chunk=2048))
        add("STR-distributed(8 shards)", dist.labels, time.perf_counter() - t0)

        t0 = time.perf_counter()
        add("Louvain", louvain(edges, n, seed=0), time.perf_counter() - t0)
        t0 = time.perf_counter()
        add("LabelProp", label_propagation(edges, n, sweeps=3),
            time.perf_counter() - t0)
    return rows


def main():
    cur = None
    for r in run():
        if r["regime"] != cur:
            cur = r["regime"]
            print(f"\n--- {cur} ---")
        print(f"{r['algo']:28s} F1={r['f1']:.3f} NMI={r['nmi']:.3f} "
              f"Q={r['modularity']:.3f} ({r['seconds']:.2f}s)")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
