"""CI benchmark smoke: tiny-size runs of the paper tables, written as a
``BENCH_*.json`` artifact so the perf trajectory is recorded per commit.

Sizes are deliberately small (seconds, not minutes, on a CI CPU runner) —
the artifact's value is the *trend* of edges/s, peak edge-buffer bytes, and
quality across commits, not absolute numbers.  ``streaming_tiers`` rows
record the memory frontier of the two wide-state tiers (multiparam sweep,
sharded distributed): measured peak edge-buffer bytes vs the bytes the
stream would occupy materialized, next to each tier's state bytes.
``compressed_stream`` rows record the ingest-bandwidth frontier: on-disk
bytes/edge and decode throughput for the raw vs delta+varint codecs (the
dvc ratio staying under 0.5x raw is checked structurally — it is a format
property, not a runner-speed number).  ``device_pipeline`` rows record the
dispatch-amortisation frontier: edges/s and exact dispatches-per-million-
edges for per-batch vs fused megabatch ingestion (``lax.scan``-over-chunks
and double-buffered-DMA Pallas), with the ~K-fold dispatch reduction and
the no-new-buffers counters asserted in-suite.

    PYTHONPATH=src python -m benchmarks.smoke [--out BENCH_smoke.json]
                                              [--baseline BENCH_smoke.json]

``--baseline`` diffs the fresh report against a committed baseline
*structurally* (suites, row identities, memory-claim fields) and exits
non-zero on drift — numbers vary per runner, shape must not.
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time


def streaming_tiers():
    """Out-of-core rows for the wide-state tiers: peak buffer vs state."""
    from repro.cluster import ClusterConfig, GeneratorSource, cluster
    from repro.graph.generators import chung_lu_segments
    from repro.graph.stream import edge_list_bytes, state_bytes

    rows = []
    n, batch = 20_000, 1 << 13
    # m must dominate the pipeline's (prefetch + 1) batch buffers for the
    # out-of-core claim to be visible at smoke scale
    src = GeneratorSource(chung_lu_segments(n, seed=13), 120_000,
                          segment_edges=batch)
    A = 4
    cfg = ClusterConfig(n=n, backend="multiparam",
                        v_maxes=(16, 64, 256, 1024), batch_edges=batch)
    cluster(src, cfg).block_until_ready()  # warmup/compile
    t0 = time.time()
    res = cluster(src, cfg).block_until_ready()
    dt = time.time() - t0
    rows.append({
        "tier": "multiparam", "m": src.n_edges, "A": A, "seconds": dt,
        "edges_per_s": src.n_edges / dt,
        "peak_buffer_bytes": res.info["peak_buffer_bytes"],
        "state_bytes": (2 * A + 1) * n * 4,
        "edge_list_bytes": edge_list_bytes(src.n_edges, 4),
    })

    src = GeneratorSource(chung_lu_segments(n, seed=17), 400_000,
                          segment_edges=batch)
    dcfg = ClusterConfig(n=n, v_max=64, backend="distributed", n_shards=4,
                         chunk=4096, batch_edges=batch)
    cluster(src, dcfg).block_until_ready()
    t0 = time.time()
    res = cluster(src, dcfg).block_until_ready()
    dt = time.time() - t0
    rows.append({
        "tier": "distributed", "m": src.n_edges, "n_shards": 4, "seconds": dt,
        "edges_per_s": src.n_edges / dt,
        "peak_buffer_bytes": res.info["peak_buffer_bytes"],
        "state_bytes": 3 * 4 * n * 4,  # 3Pn ints, P = 4
        "edge_list_bytes": edge_list_bytes(src.n_edges, 4),
    })
    return rows


def device_pipeline():
    """Fused megabatch dispatch rows (DESIGN.md §10 device pipelining).

    The per-batch loop pays one jitted dispatch + one host→device transfer
    + one Python round-trip per ``BatchPipeline`` batch; megabatch mode
    stages K batches into one ``(K, B, 2)`` buffer on the prefetch thread
    and dispatches once (``chunked``: one ``lax.scan`` over all chunks;
    ``pallas``: one double-buffered-DMA kernel launch).  Rows record
    edges/s and *dispatches per million edges* — the dispatch counts are
    exact integers (hardware-independent), so the ~K-fold amortisation is
    asserted here and structurally checked against the baseline; the
    speedup ratio is recorded for the trajectory.

    Deliberately dispatch-bound shapes (small batches): the point is to
    measure the overhead the fused path removes, not the Jacobi compute.
    """
    import jax

    from repro.cluster import ClusterConfig, GeneratorSource, cluster
    from repro.graph.generators import chung_lu_segments
    from repro.graph.pipeline import pad_template_allocs

    # (mode, backend, m, batch_edges=chunk, megabatch_k)
    cases = [
        ("chunked-per-batch", "chunked", 400_000, 512, None),
        ("chunked-fused-scan", "chunked", 400_000, 512, 64),
        ("pallas-per-batch", "pallas", 100_000, 1024, None),
        ("pallas-megabatch-dma", "pallas", 100_000, 1024, 16),
    ]
    n = 10_000
    rows = []
    base_eps = {}
    for mode, backend, m, B, k in cases:
        src = GeneratorSource(chung_lu_segments(n, seed=29), m,
                              segment_edges=1 << 13)
        cfg = ClusterConfig(n=n, v_max=64, backend=backend, chunk=B,
                            batch_edges=B, megabatch_k=k)
        cluster(src, cfg).block_until_ready()  # warmup/compile
        live_before = len(jax.live_arrays())
        allocs_before = pad_template_allocs()
        t0 = time.time()
        res = cluster(src, cfg).block_until_ready()
        dt = time.time() - t0
        # Allocation counters: the PAD template must not regrow per batch,
        # and (with donated state buffers) a steady-state run must not
        # accumulate device arrays — both deterministic, both asserted.
        if pad_template_allocs() != allocs_before:
            raise RuntimeError(
                f"{mode}: PAD template reallocated during steady-state run")
        live_after = len(jax.live_arrays())
        if live_after - live_before > 16:
            raise RuntimeError(
                f"{mode}: device buffers grew {live_before} -> {live_after} "
                "across one run — donation/lifetime regression")
        batches = res.info["stream_batches"]
        dispatches = res.info["stream_dispatches"]
        want = batches if k is None else -(-batches // k)
        if dispatches != want:
            raise RuntimeError(
                f"{mode}: {dispatches} dispatches for {batches} batches "
                f"(megabatch_k={k}) — expected {want}")
        row = {
            "mode": mode, "backend": backend, "m": m, "batch_edges": B,
            "megabatch_k": k, "seconds": dt, "edges_per_s": m / dt,
            "dispatches": dispatches,
            "dispatches_per_m_edges": dispatches / (m / 1e6),
            "peak_buffer_bytes": res.info["peak_buffer_bytes"],
        }
        if k is None:
            base_eps[backend] = m / dt
        else:
            row["speedup_vs_per_batch"] = (m / dt) / base_eps[backend]
        rows.append(row)
    return rows


def kernel_wavefront():
    """Wavefront kernel-path rows (DESIGN.md §12 conflict-free batching).

    Measures the bit-exact tier's wave-vectorised apply against the
    sequential per-edge scan over the *same* staged megabatches — the exact
    work the wavefront subsystem replaces.  On CPU the Pallas kernel only
    runs in interpret mode (an emulator, not a perf vehicle), so the
    wavefront side is measured via the pure-JAX wave-apply reference path
    (``repro.core.wavefront`` — the math the kernel shares) and the
    sequential side via the same ``lax.scan`` step the kernel's fallback
    uses; labels are asserted bit-identical in-suite.  The planner runs
    host-side up front (its cost is its own column — in production it rides
    the pipeline's prefetch thread, overlapped with device work).

    The ``speedup_vs_sequential`` ratio is same-runner and is checked
    against the >= 2x floor in the baseline diff; the planner counters
    (mean wave width, fallback rate, leftover rows) are structural.
    """
    import functools

    import jax
    import numpy as np

    from repro.core.state import ClusterState
    from repro.core.streaming import _edge_update
    from repro.core.wavefront import wavefront_update_megabatch
    from repro.graph.generators import chung_lu_stream
    from repro.graph.wavefront import plan_waves

    import jax.numpy as jnp

    n, m, v_max = 10_000, 100_000, 64
    K, B, W = 16, 1024, 16
    M = K * B
    edges = chung_lu_stream(n, m, seed=29)
    megas = [edges[t * M : (t + 1) * M] for t in range(m // M)]
    m_run = len(megas) * M

    @functools.partial(jax.jit, donate_argnums=(0,))
    def seq_mega(state, flat, vm):
        (d, c, v), _ = jax.lax.scan(
            functools.partial(_edge_update, v_max=vm),
            (state.d, state.c, state.v),
            flat,
        )
        return ClusterState(d=d, c=c, v=v, edges_seen=state.edges_seen)

    def run_seq():
        s = ClusterState.init(n).to_device()
        t0 = time.time()
        for flat in megas:
            s = seq_mega(s, jnp.asarray(flat), jnp.int32(v_max))
        s.block_until_ready()
        return time.time() - t0, s

    plans = [plan_waves(flat, W) for flat in megas]

    def run_wave():
        s = ClusterState.init(n).to_device()
        stats = None
        t0 = time.time()
        for p in plans:
            s, st = wavefront_update_megabatch(
                s, jnp.asarray(p.waves), jnp.asarray(p.leftover),
                jnp.asarray(p.meta), jnp.int32(v_max),
            )
            stats = st if stats is None else stats + st
        s.block_until_ready()
        return time.time() - t0, s, np.asarray(stats)

    run_seq()  # warmup/compile
    run_wave()
    t_seq, s_seq = min(run_seq(), run_seq(), key=lambda r: r[0])
    t_wave, s_wave, stats = min(run_wave(), run_wave(), key=lambda r: r[0])
    if not (
        np.array_equal(np.asarray(s_seq.c), np.asarray(s_wave.c))
        and np.array_equal(np.asarray(s_seq.v), np.asarray(s_wave.v))
    ):
        raise RuntimeError(
            "wavefront labels diverged from the sequential kernel path")
    live, fall = int(stats[0]), int(stats[1])
    waves = sum(p.n_waves for p in plans)
    rows_in_waves = sum(p.rows_in_waves for p in plans)
    return [
        {
            "mode": "sequential-scan", "m": m_run, "megabatch_k": K,
            "batch_edges": B, "seconds": t_seq, "edges_per_s": m_run / t_seq,
        },
        {
            "mode": "wavefront", "m": m_run, "megabatch_k": K,
            "batch_edges": B, "width": W, "seconds": t_wave,
            "edges_per_s": m_run / t_wave,
            "speedup_vs_sequential": t_seq / t_wave,
            "waves": waves,
            "mean_wave_width": rows_in_waves / waves if waves else 0.0,
            "fallback_rate": fall / live if live else 0.0,
            "leftover_rows": sum(p.leftover_rows for p in plans),
            "plan_seconds": sum(p.plan_seconds for p in plans),
        },
    ]


def fleet():
    """Multi-tenant fleet rows (DESIGN.md §13 vmapped fleet engine).

    T independent tenant streams advanced by ONE donated dispatch per fleet
    step (stacked ``(T, n)`` FleetState, vmapped chunked update) vs the
    obvious alternative — a Python loop of T single-stream ``partial_fit``
    calls per step, paying T dispatches.  Deliberately dispatch-bound
    shapes, like ``device_pipeline``: small per-tenant graphs and batches
    are exactly the serving regime the fleet engine exists for (thousands
    of small per-user graphs), and per-tenant compute is identical on both
    sides, so the ratio isolates the T-fold dispatch amortisation.

    The headline metric is **tenants/s**: fleet size over the wall time to
    drain every tenant's whole stream.  Per-tenant labels are asserted
    bit-identical between the two paths in-suite (the fleet contract);
    ``dispatches_per_fleet_step == 1`` and the >= 5x speedup floor are
    checked against the baseline — dispatch counts are exact integers, and
    the ratio is same-runner so it travels across machines.
    """
    import numpy as np

    from repro.cluster import ClusterConfig, FleetClusterer, StreamClusterer
    from repro.graph.generators import sbm_segments

    T, n, B, steps = 256, 512, 64, 16
    # T independent SBM tenants from one base seed via per-tenant offsets
    streams = []
    for t in range(T):
        seg, _ = sbm_segments(n, 32, seed=31, seed_offset=t)
        streams.append(seg(0, B * steps))
    cfg = ClusterConfig(n=n, v_max=32, backend="chunked", chunk=B,
                        batch_edges=B, tenants=T)
    m_total = T * B * steps

    FleetClusterer(cfg).fit(streams)  # warmup/compile
    fc = FleetClusterer(cfg)
    t0 = time.time()
    fc.fit(streams)
    fc.state.block_until_ready()
    t_fleet = time.time() - t0
    res = fc.finalize()
    if res.info["dispatches_per_fleet_step"] != 1.0:
        raise RuntimeError(
            f"fleet step took {res.info['dispatches_per_fleet_step']} "
            "dispatches — the one-dispatch-per-step claim regressed")

    scfg = cfg.replace(tenants=None)
    StreamClusterer(scfg).partial_fit(streams[0][:B])  # warmup/compile
    scs = [StreamClusterer(scfg) for _ in range(T)]
    t0 = time.time()
    for s in range(steps):
        for t in range(T):
            scs[t].partial_fit(streams[t][s * B : (s + 1) * B])
    scs[-1].state.block_until_ready()
    t_loop = time.time() - t0

    # the fleet contract: per-tenant rows bit-identical to the looped runs
    for t in range(0, T, 17):
        if not np.array_equal(
            res.raw_labels[t], np.asarray(scs[t].state.to_numpy().c)
        ):
            raise RuntimeError(
                f"fleet tenant {t} labels diverged from its single-stream "
                "run")
    return [
        {
            "mode": "looped-partial-fit", "tenants": T, "n": n,
            "batch_edges": B, "fleet_steps": steps, "m": m_total,
            "seconds": t_loop, "tenants_per_s": T / t_loop,
            "edges_per_s": m_total / t_loop,
            "dispatches": T * steps,
        },
        {
            "mode": "fleet-vmap", "tenants": T, "n": n,
            "batch_edges": B, "fleet_steps": res.info["fleet_steps"],
            "m": m_total, "seconds": t_fleet,
            "tenants_per_s": T / t_fleet,
            "edges_per_s": m_total / t_fleet,
            "dispatches": res.info["stream_dispatches"],
            "dispatches_per_fleet_step":
                res.info["dispatches_per_fleet_step"],
            "peak_staging_bytes": res.info["peak_staging_bytes"],
            "speedup_vs_looped": t_loop / t_fleet,
        },
    ]


def compressed_stream():
    """Codec rows: on-disk bytes/edge and decode throughput, raw vs dvc.

    The stream is the delta codec's target regime — sorted-by-source with
    community locality (the SNAP/CSR-ish on-disk layout) — so the row
    records the bandwidth trade the codec exists for: fewer stream bytes
    for vectorized decode compute.
    """
    import os
    import tempfile

    import numpy as np

    from repro.graph.codecs import DeltaVarintCodec, RawCodec
    from repro.graph.sources import CodecFileSource

    n, m = 20_000, 400_000
    rng = np.random.default_rng(23)
    i = np.sort(rng.integers(0, n, m).astype(np.int64))
    j = (i + rng.integers(-64, 65, m)) % n
    edges = np.stack([i, np.where(j == i, (j + 1) % n, j)], 1).astype(np.int32)

    rows = []
    with tempfile.TemporaryDirectory() as d:
        # dvc-v1 rides along so the decode-fast-path win (DVE2 fixed-width
        # columns vs the per-byte varint loop) stays visible per commit;
        # dvc-v3 is the device-decodable lane layout (DESIGN.md §14)
        for name, codec in (
            ("raw", RawCodec()),
            ("dvc", DeltaVarintCodec()),
            ("dvc-v1", DeltaVarintCodec(version=1)),
            ("dvc-v3", DeltaVarintCodec(version=3)),
        ):
            path = os.path.join(d, f"s.{name}")
            t0 = time.time()
            src = CodecFileSource.write(path, edges, codec)
            enc_s = time.time() - t0
            # Corrected decode measurement: stage every slice into a
            # preallocated int32 buffer — the copy-out cost pipeline
            # staging actually pays.  The legacy sum-reduction fields below
            # let raw memmap slices ride lazy page faults + a cheap
            # reduction instead of a real materialization, flattering the
            # raw row; both field sets are kept for one release so baseline
            # trajectories can cross over.
            stage = np.empty((m, 2), np.int32)
            t0 = time.time()
            pos = 0
            for sl in src.iter_slices(0):
                k = len(sl)
                stage[pos : pos + k] = sl
                pos += k
            copy_s = time.time() - t0
            assert pos == m and np.array_equal(stage, edges)
            t0 = time.time()
            sink = 0
            for sl in src.iter_slices(0):
                # legacy loop (deprecated, one-release overlap): reduces
                # every row but never materializes the staging buffer
                sink += int(np.asarray(sl, np.int64).sum())
            dec_s = time.time() - t0
            assert sink == int(edges.astype(np.int64).sum())
            nbytes = os.path.getsize(path)
            rows.append({
                "codec": name, "m": m,
                "bytes_per_edge": nbytes / m,
                "ratio_vs_raw": nbytes / (8 * m),
                "encode_s": enc_s,
                # raw-equivalent stream bandwidth the encoder sustains
                "encode_mb_per_s": 8 * m / enc_s / 1e6,
                "decode_s": dec_s,  # deprecated: sum-reduction loop
                "decode_mb_per_s": 8 * m / dec_s / 1e6,  # deprecated
                "decode_copyout_s": copy_s,
                # raw-equivalent bandwidth of a real copy-out decode
                "decode_copyout_mb_per_s": 8 * m / copy_s / 1e6,
            })
    return rows


def resilience():
    """Fault-tolerance rows (DESIGN.md §15): what the robustness machinery
    costs when nothing fails, and what it accounts for when something does.

    ``plain`` vs ``hardened`` is a same-runner ratio with identical
    clustering compute on both sides — unchecksummed framing with retries
    disabled vs checksummed DVC blocks + RetryPolicy + stall watchdog — so
    ``overhead_ratio`` isolates the per-block crc32 and the retry/heartbeat
    bracketing.  The <5% ceiling is gated against the baseline (best-of-N
    wall times keep the ratio stable across runners).  The ``quarantine``
    and ``autosave`` rows pin the accounting counters structurally:
    ``edges_lost`` must equal the planted corruption exactly
    (``loss_exact``), and a 400k-row fit at ``autosave_every=64k`` must
    actually autosave — a silently disabled counter shows up as baseline
    drift, not a green run.
    """
    import os
    import tempfile

    import numpy as np

    from repro.cluster import ClusterConfig, cluster
    from repro.cluster.api import StreamClusterer
    from repro.graph.codecs import DeltaVarintCodec
    from repro.graph.faults import corrupt_blocks
    from repro.graph.sources import CodecFileSource

    n, m = 20_000, 800_000
    rng = np.random.default_rng(31)
    edges = rng.integers(0, n, size=(m, 2)).astype(np.int32)
    base = dict(n=n, v_max=64, backend="chunked", chunk=4096,
                batch_edges=1 << 14)

    def timed_fit(path, cfg):
        sc = StreamClusterer(cfg)
        t0 = time.time()
        sc.fit(CodecFileSource(path))
        dt = time.time() - t0
        return dt, sc.finalize()

    rows = []
    with tempfile.TemporaryDirectory() as d:
        plain_path = os.path.join(d, "p.dvc")
        CodecFileSource.write(plain_path, edges,
                              DeltaVarintCodec(checksum=False))
        hard_path = os.path.join(d, "h.dvc")
        CodecFileSource.write(hard_path, edges, DeltaVarintCodec())

        plain_cfg = ClusterConfig(**base, retries=0)
        hard_cfg = ClusterConfig(**base, retries=3, stall_timeout=60.0)
        timed_fit(plain_path, plain_cfg)  # warmup: jit compile + page cache
        timed_fit(hard_path, hard_cfg)
        # The gated e2e ratio is the median of back-to-back pairwise
        # ratios: each pair sees the same machine load, and the median
        # discards load-spike outliers that would flake a 5% gate.  In
        # steady state the prefetch thread fully overlaps decode with the
        # jitted update, so the machinery's cost vanishes from e2e
        # throughput — which is exactly the claim.
        ratios, plain_s, hard_s = [], [], []
        for _ in range(7):
            p_dt, plain_out = timed_fit(plain_path, plain_cfg)
            h_dt, hard_out = timed_fit(hard_path, hard_cfg)
            ratios.append(h_dt / p_dt)
            plain_s.append(p_dt)
            hard_s.append(h_dt)
        mid = sorted(ratios)[2:-2]  # trimmed mean of the middle 3 of 7
        overhead = sum(mid) / len(mid)
        plain_s, hard_s = min(plain_s), min(hard_s)
        assert np.array_equal(plain_out.labels, hard_out.labels)

        # Un-gated trajectory field: the raw ingest drain (no clustering
        # dispatch) shows what the per-block crc32 + retry wrapper cost
        # before pipeline overlap hides them — worth watching per commit
        # even though only the e2e ratio is a claim.
        from repro.graph.errors import RetryPolicy
        from repro.graph.pipeline import BatchPipeline

        def drain_s(path, retry, stall):
            pipe = BatchPipeline(CodecFileSource(path),
                                 base["batch_edges"], retry=retry,
                                 stall_timeout=stall)
            t0 = time.time()
            rows_seen = sum(b.n_rows for b in pipe.batches())
            dt = time.time() - t0
            assert rows_seen == m
            return dt

        drain_s(plain_path, None, None)  # warmup (page cache)
        drain_s(hard_path, RetryPolicy(), 60.0)
        plain_drain, hard_drain = [], []
        for _ in range(3):
            plain_drain.append(drain_s(plain_path, None, None))
            hard_drain.append(drain_s(hard_path, RetryPolicy(), 60.0))
        plain_drain, hard_drain = min(plain_drain), min(hard_drain)
        rows.append({
            "mode": "plain", "m": m, "fit_s": plain_s,
            "edges_per_s": m / plain_s,
        })
        rows.append({
            "mode": "hardened", "m": m, "fit_s": hard_s,
            "edges_per_s": m / hard_s,
            # fault-free e2e cost of checksums + retry/stall machinery
            "overhead_ratio": overhead,
            # raw ingest-drain cost before pipeline overlap (not gated)
            "drain_overhead_ratio": hard_drain / plain_drain,
            "drain_s": hard_drain,
            "plain_drain_s": plain_drain,
            "ingest_retries": hard_out.info.get("ingest_retries", 0),
            "ingest_stalls": hard_out.info.get("ingest_stalls", 0),
        })

        # exact-loss accounting under planted block corruption
        qpath = os.path.join(d, "q.dvc")
        CodecFileSource.write(qpath, edges,
                              DeltaVarintCodec(block_edges=1 << 13))
        planted = corrupt_blocks(qpath, seed=0, n_blocks=4)
        t0 = time.time()
        qout = cluster(qpath, ClusterConfig(**base, on_corrupt="quarantine"))
        q_s = time.time() - t0
        rows.append({
            "mode": "quarantine", "m": m, "fit_s": q_s,
            "edges_per_s": m / q_s,
            "blocks_quarantined": qout.info["blocks_quarantined"],
            "edges_lost": qout.info["edges_lost"],
            "planted_rows_lost": planted["rows_lost"],
            "loss_exact": qout.info["edges_lost"] == planted["rows_lost"],
        })

        # autosave cadence: checkpoints from inside fit, counted in info
        adir = os.path.join(d, "autosave")
        sc = StreamClusterer(ClusterConfig(
            **base, autosave_every=1 << 16, autosave_dir=adir))
        t0 = time.time()
        sc.fit(CodecFileSource(plain_path))
        a_s = time.time() - t0
        aout = sc.finalize()
        assert np.array_equal(aout.labels, plain_out.labels)
        rows.append({
            "mode": "autosave", "m": m, "fit_s": a_s,
            "edges_per_s": m / a_s,
            "autosaves": aout.info.get("autosaves", 0),
        })
    return rows


def device_ingest():
    """Device-resident compressed ingest rows (DESIGN.md §14).

    Two row families.  *Staging* rows time the host-side cost of the
    ingest leg — what the producer thread pays per edge to hand the device
    a ready buffer (``prefetch=0`` so both paths pay their producer on the
    timed thread).  The host-decode path pays codec block decode plus the
    stacking memcpy into the ``(K * B, 2)`` slab; the compressed path pays
    only the block memcpy into the payload slab plus descriptor assembly.
    That host-cost ratio is ``speedup_vs_host`` and carries the >= 3x
    floor in the baseline diff: in steady state device decode overlaps
    staging of the next megabatch (DESIGN.md §14), so the host-side cost
    *is* the sustained ingest rate wherever the accelerator decodes at
    device bandwidth.  On this CPU-only runner the decode kernel runs as
    the jitted pure-JAX reference; its wall time is reported separately as
    ``emulated_decode_rows_per_s`` (an emulation artifact, not a device
    number, and not part of the gated ratio).

    *End-to-end* rows run the same ``.dvc`` stream through
    ``StreamClusterer.fit`` with ``device_decode`` off/on; labels are
    asserted bit-identical and the dispatch counts equal in-suite (the §14
    contract).  The fallback-segment rate (varint/u8 blocks the device
    cannot decode) is structural in the baseline diff.
    """
    import os
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from repro.cluster import ClusterConfig, StreamClusterer
    from repro.core.decode import decode_megabatch
    from repro.graph.codecs import DeltaVarintCodec
    from repro.graph.pipeline import BatchPipeline
    from repro.graph.sources import CodecFileSource

    # adjacency-ordered local stream (small positive j deltas — the shape
    # DVE3 fixed blocks are built for) with one far-edge burst so exactly
    # one codec block exercises the raw-fallback staging path
    n, m = 20_000, 400_000
    rng = np.random.default_rng(23)
    i = np.sort(rng.integers(0, n - 65, m).astype(np.int64))
    edges = np.stack([i, i + rng.integers(1, 65, m)], 1).astype(np.int32)
    edges[m // 2 : m // 2 + 128, 1] = rng.integers(0, n, 128)
    B, K = 1 << 13, 16

    rows = []
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "s.dvc3")
        # unchecksummed framing on purpose: this suite's ratio isolates
        # the §14 variable (host decode vs block memcpy), and the crc32
        # pass of the checksummed default is the same cost on both sides
        # of the §15 claim — its drain cost is tracked separately as
        # resilience.drain_overhead_ratio, not folded into this one
        CodecFileSource.write(
            path, edges,
            DeltaVarintCodec(block_edges=B, version=3, checksum=False))

        def drain_host():
            pipe = BatchPipeline(CodecFileSource(path), B, prefetch=0)
            t0 = time.time()
            staged = 0
            for mb in pipe.megabatches(K):
                staged += mb.n_rows
            return staged / (time.time() - t0)

        def drain_device():
            # host-side cost only: compressed staging to a device-ready
            # payload + descriptor table (the decode itself rides the
            # device, overlapped with staging the next megabatch)
            pipe = BatchPipeline(CodecFileSource(path), B, prefetch=0)
            t0 = time.time()
            staged = fb_segs = segs = 0
            cmegas = []
            for cm in pipe.compressed_megabatches(K):
                staged += cm.n_rows
                segs += cm.n_desc
                fb_segs += int(np.count_nonzero(
                    cm.desc[: cm.n_desc, 0] == 2))  # D_KIND == DESC_RAW
                cmegas.append(cm)
            return staged / (time.time() - t0), fb_segs, segs, cmegas

        def emulate_decode(cmegas):
            # CPU-only stand-in for the device kernel: jitted reference
            # decode over the staged slabs (reported, never gated)
            staged = [(jnp.asarray(cm.payload), jnp.asarray(cm.desc),
                       cm.window, cm.out_rows, cm.n_rows) for cm in cmegas]
            for pay, de, w, o, _ in staged:  # warmup/compile
                decode_megabatch(pay, de, w, o).block_until_ready()
            t0 = time.time()
            out, rows_done = None, 0
            for pay, de, w, o, nr in staged:
                out = decode_megabatch(pay, de, w, o)
                rows_done += nr
            out.block_until_ready()
            return rows_done / (time.time() - t0)

        drain_host()  # warmup (page cache)
        drain_device()  # warmup
        host_eps = max(drain_host(), drain_host())
        (dev_eps, fb_segs, segs, cmegas) = max(
            drain_device(), drain_device(), key=lambda r: r[0])
        emu_rps = emulate_decode(cmegas)
        rows.append({
            "mode": "staging-host-decode", "m": m, "batch_edges": B,
            "megabatch_k": K, "edges_per_s": host_eps,
            "decode_mb_per_s": 8 * host_eps / 1e6,
        })
        rows.append({
            "mode": "staging-device-decode", "m": m, "batch_edges": B,
            "megabatch_k": K, "edges_per_s": dev_eps,
            "decode_mb_per_s": 8 * dev_eps / 1e6,
            "speedup_vs_host": dev_eps / host_eps,
            "emulated_decode_rows_per_s": emu_rps,
            "fallback_segments": fb_segs,
            "fallback_segment_rate": fb_segs / segs if segs else 0.0,
        })

        # end-to-end fit(): same stream, device_decode off vs on
        base = ClusterConfig(n=n, v_max=64, backend="chunked", chunk=B,
                             batch_edges=B, megabatch_k=K)
        dd = base.replace(device_decode=True)
        for cfg in (base, dd):  # warmup/compile
            StreamClusterer(cfg).fit(CodecFileSource(path))
        results = {}
        for mode, cfg in (("host", base), ("device", dd)):
            sc = StreamClusterer(cfg)
            t0 = time.time()
            sc.fit(CodecFileSource(path))
            sc.state.block_until_ready()
            dt = time.time() - t0
            results[mode] = (sc.finalize(), dt)
        res_h, t_h = results["host"]
        res_d, t_d = results["device"]
        if not np.array_equal(res_h.labels, res_d.labels):
            raise RuntimeError(
                "device_decode labels diverged from the host-decode path")
        if res_h.info["stream_dispatches"] != res_d.info["stream_dispatches"]:
            raise RuntimeError(
                f"device_decode changed the dispatch count: "
                f"{res_h.info['stream_dispatches']} -> "
                f"{res_d.info['stream_dispatches']}")
        rows.append({
            "mode": "fit-host-decode", "m": m, "batch_edges": B,
            "megabatch_k": K, "seconds": t_h, "edges_per_s": m / t_h,
            "dispatches": res_h.info["stream_dispatches"],
        })
        rows.append({
            "mode": "fit-device-decode", "m": m, "batch_edges": B,
            "megabatch_k": K, "seconds": t_d, "edges_per_s": m / t_d,
            "dispatches": res_d.info["stream_dispatches"],
            "speedup_vs_host": t_h / t_d,
            "decoded_megabatches":
                res_d.info["device_decoded_megabatches"],
            "fallback_rows": res_d.info["device_fallback_rows"],
            "fallback_segment_rate":
                res_d.info["device_fallback_segment_rate"],
        })
    return rows


def run():
    from benchmarks import memory_footprint, table1_speed, table2_quality

    t0 = time.time()
    speed = table1_speed.run(
        sizes=(20_000, 80_000), baselines_at=20_000, batch_edges=1 << 14
    )

    # one tiny quality regime (module-level REGIMES is benchmark-scale)
    quality = table2_quality.run(regimes={
        "sbm-smoke": dict(n=2_000, k=100, avg_degree=10, p_intra=0.8),
    })

    return {
        "suite": "smoke",
        "python": platform.python_version(),
        "machine": platform.machine(),
        "wall_s": round(time.time() - t0, 2),
        "table1_speed": speed,
        "table2_quality": quality,
        "streaming_tiers": streaming_tiers(),
        "device_pipeline": device_pipeline(),
        "kernel_wavefront": kernel_wavefront(),
        "compressed_stream": compressed_stream(),
        "device_ingest": device_ingest(),
        "fleet": fleet(),
        "resilience": resilience(),
        "memory": memory_footprint.run(),
    }


def check_against_baseline(report: dict, baseline: dict) -> list:
    """Structural diff: same suites, same row identities, memory-claim
    fields present.  Values are runner-dependent and not compared."""
    problems = []
    for key in ("table1_speed", "table2_quality", "streaming_tiers",
                "device_pipeline", "kernel_wavefront", "compressed_stream",
                "device_ingest", "fleet", "resilience", "memory"):
        if (key in baseline) != (key in report):
            problems.append(f"suite {key!r} appeared/disappeared")

    def ids(rows, field):
        return sorted({r[field] for r in rows if field in r})

    if "table1_speed" in baseline and "table1_speed" in report:
        got, want = ids(report["table1_speed"], "algo"), ids(
            baseline["table1_speed"], "algo")
        if got != want:
            problems.append(f"table1 algos changed: {want} -> {got}")
    if "table2_quality" in baseline and "table2_quality" in report:
        got, want = ids(report["table2_quality"], "algo"), ids(
            baseline["table2_quality"], "algo")
        if got != want:
            problems.append(f"table2 algos changed: {want} -> {got}")
        # quality deltas: the regimes are seeded and every tier is
        # deterministic, so F1/NMI/Q are comparable across runners — a drop
        # beyond tolerance fails CI exactly like a perf-claim regression.
        # Rows tagged "extrapolated" are projections, not measurements, and
        # are skipped from all value comparisons.
        tol = 0.05
        base_rows = {(r.get("regime"), r.get("algo")): r
                     for r in baseline["table2_quality"]
                     if not r.get("extrapolated")}
        for row in report["table2_quality"]:
            if row.get("extrapolated"):
                continue
            base = base_rows.get((row.get("regime"), row.get("algo")))
            if base is None:
                continue
            for field in ("f1", "nmi", "modularity"):
                if field not in row:
                    problems.append(
                        f"table2 {row.get('algo')!r} lost {field!r}")
                elif field in base and row[field] < base[field] - tol:
                    problems.append(
                        f"table2 {row.get('regime')}/{row.get('algo')}: "
                        f"{field} {base[field]:.3f} -> {row[field]:.3f} — "
                        "quality regressed")
            if "refine_sketch_peak_bytes" in base and \
                    "refine_sketch_peak_bytes" not in row:
                problems.append(
                    f"table2 {row.get('algo')!r} lost the refinement "
                    "memory claim (refine_sketch_peak_bytes)")
    if "streaming_tiers" in baseline and "streaming_tiers" in report:
        got, want = ids(report["streaming_tiers"], "tier"), ids(
            baseline["streaming_tiers"], "tier")
        if got != want:
            problems.append(f"streaming tiers changed: {want} -> {got}")
        for row in report.get("streaming_tiers", []):
            for field in ("peak_buffer_bytes", "state_bytes",
                          "edge_list_bytes"):
                if field not in row:
                    problems.append(
                        f"streaming tier {row.get('tier')!r} lost {field!r}")
            if row.get("peak_buffer_bytes", 0) >= row.get(
                    "edge_list_bytes", float("inf")):
                problems.append(
                    f"tier {row.get('tier')!r} buffered the whole stream "
                    f"({row.get('peak_buffer_bytes')} B)")
    if "device_pipeline" in baseline and "device_pipeline" in report:
        got, want = ids(report["device_pipeline"], "mode"), ids(
            baseline["device_pipeline"], "mode")
        if got != want:
            problems.append(f"device_pipeline modes changed: {want} -> {got}")
        by_backend = {}
        for row in report.get("device_pipeline", []):
            for field in ("edges_per_s", "dispatches",
                          "dispatches_per_m_edges", "peak_buffer_bytes"):
                if field not in row:
                    problems.append(
                        f"device_pipeline {row.get('mode')!r} lost {field!r}")
            by_backend.setdefault(row.get("backend"), {})[
                "mega" if row.get("megabatch_k") else "per_batch"] = row
        for backend, pair in by_backend.items():
            # the dispatch-amortisation claim itself: exact integer counts,
            # hardware-independent — the fused path must dispatch at most
            # half as often per edge as the per-batch baseline
            if "mega" in pair and "per_batch" in pair:
                mega = pair["mega"].get("dispatches_per_m_edges")
                per = pair["per_batch"].get("dispatches_per_m_edges")
                if mega is not None and per is not None and mega * 2 > per:
                    problems.append(
                        f"device_pipeline {backend!r}: fused path dispatches "
                        f"{mega:.1f}/Medge vs per-batch {per:.1f}/Medge — "
                        "amortisation claim regressed")
    if "kernel_wavefront" in baseline and "kernel_wavefront" in report:
        got, want = ids(report["kernel_wavefront"], "mode"), ids(
            baseline["kernel_wavefront"], "mode")
        if got != want:
            problems.append(f"kernel_wavefront modes changed: {want} -> {got}")
        for row in report.get("kernel_wavefront", []):
            if row.get("mode") != "wavefront":
                continue
            for field in ("edges_per_s", "speedup_vs_sequential",
                          "mean_wave_width", "fallback_rate",
                          "leftover_rows", "plan_seconds"):
                if field not in row:
                    problems.append(f"kernel_wavefront lost {field!r}")
            # the perf claim itself: a same-runner ratio over identical
            # staged megabatches, so it travels across machines — the
            # wavefront path must hold at least 2x over the sequential scan
            speedup = row.get("speedup_vs_sequential")
            if speedup is not None and speedup < 2.0:
                problems.append(
                    f"kernel_wavefront speedup_vs_sequential {speedup:.2f} "
                    "< 2.0 — wavefront throughput claim regressed")
            mw = row.get("mean_wave_width")
            if mw is not None and not 1.0 <= mw <= row.get("width", 1e9):
                problems.append(
                    f"kernel_wavefront mean_wave_width {mw} out of range")
            fr = row.get("fallback_rate")
            if fr is not None and not 0.0 <= fr <= 1.0:
                problems.append(
                    f"kernel_wavefront fallback_rate {fr} out of range")
    if "device_ingest" in baseline and "device_ingest" in report:
        got, want = ids(report["device_ingest"], "mode"), ids(
            baseline["device_ingest"], "mode")
        if got != want:
            problems.append(f"device_ingest modes changed: {want} -> {got}")
        for row in report.get("device_ingest", []):
            if row.get("mode") == "staging-device-decode":
                for field in ("edges_per_s", "decode_mb_per_s",
                              "speedup_vs_host", "fallback_segment_rate",
                              "emulated_decode_rows_per_s"):
                    if field not in row:
                        problems.append(f"device_ingest lost {field!r}")
                # the §14 perf claim itself: a same-runner host-side cost
                # ratio over the identical compressed stream, so it travels
                # across machines — compressed staging must keep the host
                # at least 3x cheaper per edge than host-decode staging
                speedup = row.get("speedup_vs_host")
                if speedup is not None and speedup < 3.0:
                    problems.append(
                        f"device_ingest speedup_vs_host {speedup:.2f} < 3.0 "
                        "— compressed-ingest throughput claim regressed")
                fr = row.get("fallback_segment_rate")
                if fr is not None and not 0.0 <= fr <= 1.0:
                    problems.append(
                        f"device_ingest fallback_segment_rate {fr} out of "
                        "range")
            if row.get("mode") == "fit-device-decode":
                for field in ("edges_per_s", "dispatches",
                              "decoded_megabatches", "fallback_rows",
                              "fallback_segment_rate"):
                    if field not in row:
                        problems.append(f"device_ingest lost {field!r}")
    if "fleet" in baseline and "fleet" in report:
        got, want = ids(report["fleet"], "mode"), ids(baseline["fleet"],
                                                      "mode")
        if got != want:
            problems.append(f"fleet modes changed: {want} -> {got}")
        for row in report.get("fleet", []):
            if row.get("mode") != "fleet-vmap":
                continue
            for field in ("tenants", "tenants_per_s", "edges_per_s",
                          "dispatches", "dispatches_per_fleet_step",
                          "peak_staging_bytes", "speedup_vs_looped"):
                if field not in row:
                    problems.append(f"fleet lost {field!r}")
            # one donated dispatch per fleet step — exact integer counts,
            # hardware-independent; the fleet engine's structural claim
            dpfs = row.get("dispatches_per_fleet_step")
            if dpfs is not None and dpfs != 1.0:
                problems.append(
                    f"fleet dispatches_per_fleet_step {dpfs} != 1.0 — "
                    "single-dispatch claim regressed")
            # the perf claim itself: a same-runner ratio (identical per-
            # tenant compute on both sides) so it travels across machines —
            # one fleet dispatch must beat T looped partial_fit calls >= 5x
            speedup = row.get("speedup_vs_looped")
            if speedup is not None and speedup < 5.0:
                problems.append(
                    f"fleet speedup_vs_looped {speedup:.2f} < 5.0 — "
                    "tenants/s claim regressed")
    if "resilience" in baseline and "resilience" in report:
        got, want = ids(report["resilience"], "mode"), ids(
            baseline["resilience"], "mode")
        if got != want:
            problems.append(f"resilience modes changed: {want} -> {got}")
        for row in report.get("resilience", []):
            if row.get("mode") == "hardened":
                for field in ("overhead_ratio", "ingest_retries",
                              "ingest_stalls"):
                    if field not in row:
                        problems.append(f"resilience lost {field!r}")
                # the fault-free cost claim: checksummed framing + retry/
                # stall machinery must stay under 5% of hardware-off
                # edges/s (same-runner ratio, best-of-N on both sides)
                ratio = row.get("overhead_ratio")
                if ratio is not None and ratio >= 1.05:
                    problems.append(
                        f"resilience overhead_ratio {ratio:.3f} >= 1.05 — "
                        "fault-free robustness cost regressed")
            if row.get("mode") == "quarantine":
                for field in ("blocks_quarantined", "edges_lost",
                              "planted_rows_lost", "loss_exact"):
                    if field not in row:
                        problems.append(f"resilience lost {field!r}")
                # accounting exactness is deterministic, so it gates:
                # edges_lost must equal the planted corruption, bit-exact
                if row.get("loss_exact") is not True:
                    problems.append(
                        f"resilience edges_lost {row.get('edges_lost')} != "
                        f"planted {row.get('planted_rows_lost')} — "
                        "quarantine accounting regressed")
            if row.get("mode") == "autosave":
                if "autosaves" not in row:
                    problems.append("resilience lost 'autosaves'")
                elif row["autosaves"] < 1:
                    problems.append(
                        "resilience autosaves == 0 — autosave cadence "
                        "silently disabled")
    if "compressed_stream" in baseline and "compressed_stream" in report:
        got, want = ids(report["compressed_stream"], "codec"), ids(
            baseline["compressed_stream"], "codec")
        if got != want:
            problems.append(f"codecs changed: {want} -> {got}")
        for row in report.get("compressed_stream", []):
            for field in ("bytes_per_edge", "ratio_vs_raw",
                          "decode_mb_per_s", "decode_copyout_mb_per_s",
                          "encode_mb_per_s"):
                if field not in row:
                    problems.append(
                        f"codec {row.get('codec')!r} lost {field!r}")
            # the bandwidth claim itself: the compressed stream must stay
            # under half the raw bytes/edge (hardware-independent; a row
            # missing the field entirely is reported by the loop above)
            ratio = row.get("ratio_vs_raw")
            if (
                str(row.get("codec", "")).startswith("dvc")
                and ratio is not None
                and ratio >= 0.5
            ):
                problems.append(
                    f"{row.get('codec')} ratio_vs_raw {ratio:.3f} >= 0.5 — "
                    "compression claim regressed")
    return problems


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_smoke.json")
    ap.add_argument("--baseline", default=None,
                    help="committed BENCH_smoke.json to diff against")
    args = ap.parse_args(argv)
    report = run()
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, default=float)
    print(f"wrote {args.out} ({report['wall_s']}s)", file=sys.stderr)
    for r in report["table1_speed"]:
        if "linearity_ratio" in r:
            print(f"smoke/{r['algo']},0,ratio={r['linearity_ratio']:.3f}")
            continue
        print(f"smoke/{r['algo']},{r['seconds']*1e6:.0f},"
              f"{r['edges_per_s']:.0f} edges/s")
    for r in report["streaming_tiers"]:
        print(f"smoke/{r['tier']},buf={r['peak_buffer_bytes']},"
              f"state={r['state_bytes']},edges={r['edge_list_bytes']}")
    for r in report["device_pipeline"]:
        extra = (f",x{r['speedup_vs_per_batch']:.2f}"
                 if "speedup_vs_per_batch" in r else "")
        print(f"smoke/pipeline-{r['mode']},{r['edges_per_s']:.0f} edges/s,"
              f"{r['dispatches_per_m_edges']:.1f} disp/Medge{extra}")
    for r in report["kernel_wavefront"]:
        extra = (f",x{r['speedup_vs_sequential']:.2f}"
                 f",width={r['mean_wave_width']:.1f}"
                 f",fallback={r['fallback_rate']:.3f}"
                 if r["mode"] == "wavefront" else "")
        print(f"smoke/wavefront-{r['mode']},{r['edges_per_s']:.0f} "
              f"edges/s{extra}")
    for r in report["compressed_stream"]:
        print(f"smoke/codec-{r['codec']},{r['bytes_per_edge']:.2f} B/edge,"
              f"{r['decode_copyout_mb_per_s']:.0f} MB/s decode,"
              f"{r['encode_mb_per_s']:.0f} MB/s encode")
    for r in report["device_ingest"]:
        extra = (f",x{r['speedup_vs_host']:.2f}"
                 f",fallback={r['fallback_segment_rate']:.3f}"
                 if "speedup_vs_host" in r else "")
        print(f"smoke/ingest-{r['mode']},{r['edges_per_s']:.0f} edges/s"
              f"{extra}")
    for r in report["fleet"]:
        extra = (f",x{r['speedup_vs_looped']:.2f}"
                 f",staging={r['peak_staging_bytes']}"
                 if "speedup_vs_looped" in r else "")
        print(f"smoke/fleet-{r['mode']},{r['tenants_per_s']:.0f} tenants/s,"
              f"{r['dispatches']} disp{extra}")
    for r in report["resilience"]:
        extra = ""
        if "overhead_ratio" in r:
            extra = f",overhead=x{r['overhead_ratio']:.3f}"
        elif "edges_lost" in r:
            extra = (f",quarantined={r['blocks_quarantined']}"
                     f",lost={r['edges_lost']}"
                     f"/{r['planted_rows_lost']}")
        elif "autosaves" in r:
            extra = f",autosaves={r['autosaves']}"
        print(f"smoke/resilience-{r['mode']},{r['edges_per_s']:.0f} "
              f"edges/s{extra}")
    if args.baseline:
        try:
            with open(args.baseline) as f:
                baseline = json.load(f)
        except FileNotFoundError:
            print(f"baseline {args.baseline!r} not found — commit a "
                  "BENCH_smoke.json baseline (see --out)", file=sys.stderr)
            return 1
        problems = check_against_baseline(report, baseline)
        for p in problems:
            print(f"baseline drift: {p}", file=sys.stderr)
        if problems:
            return 1
        print("baseline diff: structure unchanged", file=sys.stderr)
    return 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main())
