"""Paper §2.5 — one-pass multi-v_max sweep vs A independent passes.

Both sides run through ``repro.cluster`` and both *stream*: the sweep is one
``multiparam`` call over a ``GeneratorSource`` (edge residency O(batch),
sweep state ``(2A+1) n`` ints), the baseline is A separate streamed ``scan``
calls over the same source.  Each row reports the measured peak edge-buffer
bytes next to the full edge-list bytes the old materializing sweep paid.
"""

from __future__ import annotations

import time

from repro.cluster import ClusterConfig, GeneratorSource, cluster
from repro.graph.generators import sbm_segments
from repro.graph.stream import edge_list_bytes


def run(n=5000, a_values=(4, 8), batch_edges=1 << 12):
    segment, _ = sbm_segments(n, 100, seed=3)
    m = int(n * 12 / 2)
    source = GeneratorSource(segment, m, segment_edges=batch_edges)
    rows = []
    for A in a_values:
        vms = tuple(2 ** (i + 3) for i in range(A))
        sweep_cfg = ClusterConfig(
            n=n, backend="multiparam", v_maxes=vms, batch_edges=batch_edges
        )
        # one streamed pass, A parameters
        res = cluster(source, sweep_cfg).block_until_ready()
        t0 = time.perf_counter()
        res = cluster(source, sweep_cfg).block_until_ready()
        t_sweep = time.perf_counter() - t0
        # A independent streamed passes
        scan_cfg = ClusterConfig(
            n=n, v_max=int(vms[0]), backend="scan", batch_edges=batch_edges
        )
        cluster(source, scan_cfg).block_until_ready()
        t0 = time.perf_counter()
        for v in vms:
            cluster(
                source, scan_cfg.replace(v_max=int(v))
            ).block_until_ready()
        t_sep = time.perf_counter() - t0
        rows.append({
            "A": A, "sweep_s": t_sweep, "separate_s": t_sep,
            "speedup": t_sep / t_sweep,
            "peak_buffer_bytes": res.info["peak_buffer_bytes"],
            "edge_list_bytes": edge_list_bytes(m, 4),
            "sweep_state_bytes": (2 * A + 1) * n * 4,
        })
    return rows


def main():
    for r in run():
        print(f"A={r['A']:2d}  one-pass {r['sweep_s']:.2f}s  "
              f"separate {r['separate_s']:.2f}s  speedup {r['speedup']:.2f}x  "
              f"buf={r['peak_buffer_bytes']/1e3:.0f}kB "
              f"(edge list {r['edge_list_bytes']/1e3:.0f}kB, "
              f"state {r['sweep_state_bytes']/1e3:.0f}kB)")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
