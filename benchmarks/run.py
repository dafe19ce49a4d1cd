"""Benchmark entry point — one section per paper table/figure plus the
roofline summary.  Prints ``name,us_per_call,derived`` CSV lines per section.

    PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import sys
import time


def main() -> None:
    t_start = time.time()

    print("# === Table 1: execution time vs graph size (paper §4.4) ===")
    from benchmarks import table1_speed
    for r in table1_speed.run():
        if "linearity_ratio" in r:
            print(f"{r['algo']},0,m={r['m']};ratio={r['linearity_ratio']:.3f}")
            continue
        derived = f"m={r['m']};{r['edges_per_s']:.0f} edges/s"
        if "peak_buffer_bytes" in r:
            # the paper's memory claim, measured: resident edge buffer
            # (O(batch)) alongside the 3n-int state
            derived += (f";edge_buf={r['peak_buffer_bytes']/1e6:.1f}MB"
                        f";state={r['state_bytes']/1e6:.1f}MB")
        print(f"{r['algo']},{r['seconds']*1e6:.0f},{derived}")

    print("\n# === Table 2: detection quality F1/NMI (paper §4.4) ===")
    from benchmarks import table2_quality
    for r in table2_quality.run():
        print(f"{r['regime']}/{r['algo']},{r['seconds']*1e6:.0f},"
              f"F1={r['f1']:.3f};NMI={r['nmi']:.3f};Q={r['modularity']:.3f}")

    print("\n# === Memory footprint: 3n ints vs edge list (paper §4.4) ===")
    from benchmarks import memory_footprint
    for r in memory_footprint.run():
        print(f"memory/{r['dataset']},0,"
              f"state={r['state_int64_MB']:.1f}MB;"
              f"edges={r['edge_list_int64_MB']:.1f}MB;ratio={r['ratio']:.1f}x")

    print("\n# === Multi-v_max one-pass sweep (paper §2.5) ===")
    from benchmarks import multiparam_bench
    for r in multiparam_bench.run():
        print(f"multiparam/A={r['A']},{r['sweep_s']*1e6:.0f},"
              f"separate={r['separate_s']*1e6:.0f}us;speedup={r['speedup']:.2f}x")

    print("\n# === Kernel micro-benchmarks ===")
    from benchmarks import kernel_bench
    for r in kernel_bench.run():
        print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']}")

    print("\n# === Roofline summary (from dry-run artifacts) ===")
    try:
        from benchmarks import roofline
        cells = roofline.load_cells("single")
        for c in cells:
            if c["status"] != "ok":
                print(f"roofline/{c['arch']}/{c['shape']},0,skipped")
                continue
            r = c["roofline"]
            print(
                f"roofline/{c['arch']}/{c['shape']},"
                f"{r['roofline_s']*1e6:.0f},"
                f"dominant={r['dominant']};fraction={r['roofline_fraction']:.4f}"
            )
    except Exception as e:  # dry-run artifacts absent
        print(f"roofline,0,unavailable({e})", file=sys.stderr)

    print(f"\n# total benchmark wall time: {time.time()-t_start:.0f}s")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
