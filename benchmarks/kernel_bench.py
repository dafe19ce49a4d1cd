"""Kernel-tier micro-benchmarks (CPU; interpret-mode Pallas is a correctness
vehicle, not a perf proxy — TPU perf is covered by the §Roofline analysis).

Clustering tiers are exercised through the unified ``repro.cluster`` API so
the benchmark measures exactly what callers get.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.cluster import ClusterConfig, cluster
from repro.graph.generators import chung_lu_stream
from repro.kernels.seg_volume.ops import seg_volume
from repro.kernels.seg_volume.ref import seg_volume_ref


def _t(fn, *args):
    out = fn(*args)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    t0 = time.perf_counter()
    out = fn(*args)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return time.perf_counter() - t0


def run():
    rows = []
    n, m = 20_000, 200_000
    edges = chung_lu_stream(n, m, seed=1)
    scan_cfg = ClusterConfig(n=n, v_max=64, backend="scan")
    t_scan = _t(lambda e: cluster(e, scan_cfg), edges)
    rows.append({"name": "cluster_scan(1edge/step)", "us_per_call": t_scan * 1e6,
                 "derived": f"{m/t_scan:,.0f} edges/s"})
    for chunk in (512, 4096):
        cfg = ClusterConfig(n=n, v_max=64, backend="chunked", chunk=chunk)
        t_c = _t(lambda e: cluster(e, cfg), edges)
        rows.append({"name": f"cluster_chunked(B={chunk})",
                     "us_per_call": t_c * 1e6,
                     "derived": f"{m/t_c:,.0f} edges/s"})
    # Pallas-tier fused paths (interpret mode on CPU, hence the smaller
    # stream): the megabatch DMA kernel and the wavefront variant — visible
    # here so kernel-level regressions surface outside the smoke suite.
    m_pal = 50_000
    edges_pal = chung_lu_stream(n, m_pal, seed=2)
    mega_cfg = ClusterConfig(n=n, v_max=64, backend="pallas", chunk=1024,
                             batch_edges=1024, megabatch_k=8)
    t_mb = _t(lambda e: cluster(e, mega_cfg), edges_pal)
    rows.append({"name": "cluster_pallas_megabatch(K=8,B=1024)",
                 "us_per_call": t_mb * 1e6,
                 "derived": f"{m_pal/t_mb:,.0f} edges/s"})
    wave_cfg = mega_cfg.replace(wavefront=16)
    t_wf = _t(lambda e: cluster(e, wave_cfg), edges_pal)
    rows.append({"name": "cluster_pallas_wavefront(K=8,B=1024,W=16)",
                 "us_per_call": t_wf * 1e6,
                 "derived": f"{m_pal/t_wf:,.0f} edges/s"})
    lab = jnp.asarray(np.random.default_rng(0).integers(0, 1024, 65536))
    w = jnp.ones(65536, jnp.float32)
    t_ref = _t(lambda l: seg_volume_ref(l, w, 1024), lab)
    rows.append({"name": "seg_volume_scatter_ref", "us_per_call": t_ref * 1e6,
                 "derived": ""})
    return rows


def main():
    for r in run():
        print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
