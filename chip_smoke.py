#!/usr/bin/env python3
"""Smoke run of the clusterer's main paths on one TPU chip.

    python chip_smoke.py

Runs in one process (the chip belongs to one process) and fails, before any
phase, when JAX finds no TPU.  Phases, each checked by the repo's own means:

a. Paper path: the default ``chunked`` backend streams a Chung–Lu graph at
   com-LiveJournal's published shape (SNAP: n = 3,997,962, m = 34,681,189;
   degree exponent 2.5 assumed) through ``StreamClusterer.fit``.  Checks the
   edge count, the degree and volume sums, the label range, and that the
   same jitted chunked update gives bit-identical state on the chip and on
   the host CPU over the stream's first 2^21 edges.
b. Bit-exact path: the compiled ``pallas`` kernel at n = 1,048,576, through
   ``fit`` with ``megabatch_k`` and through ``partial_fit`` per batch; both
   must equal the ``dense`` reference bit for bit.
c. The other kernels at small shapes: the fleet against standalone runs,
   ``device_decode`` on a DVE3 file against host decode, and the wavefront
   path against the sequential kernel.  Mosaic refuses the wavefront
   kernel on a v5e, so on the chip that configuration must raise the
   compiler's error (never interpret, never fall back).

Timings printed here are smoke timings, not benchmarks.  The last line of
standard output is a JSON object naming the device.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import numpy as np  # noqa: E402

@dataclasses.dataclass(frozen=True)
class Sizes:
    n_paper: int = 3_997_962  # com-LiveJournal nodes (SNAP)
    m_paper: int = 34_681_189  # com-LiveJournal edges (SNAP)
    prefix: int = 1 << 21  # chip-vs-CPU prefix of phase (a)
    paper_batch: int = 1 << 20
    paper_k: int = 8
    n_exact: int = 1 << 20  # 12 MiB of state
    m_exact: int = 1 << 20
    exact_batch: int = 1 << 16
    exact_k: int = 4
    n_small: int = 4096
    m_small: int = 20_000
    small_batch: int = 2048
    tenants: int = 16


V_MAX = 64  # the paper-path default of benchmarks/table1_speed.py


def log(msg: str) -> None:
    print(msg, flush=True)


def _live_rows(edges: np.ndarray) -> int:
    i, j = edges[:, 0], edges[:, 1]
    return int(np.count_nonzero((i != -1) & (j != -1) & (i != j)))


def check(ok: bool, what: str) -> None:
    """A failed check fails the run (``assert`` would vanish under -O)."""
    if not ok:
        raise AssertionError(what)


def _assert_same_state(got, want, what: str) -> None:
    for leaf in ("d", "c", "v"):
        a = np.asarray(getattr(got, leaf))
        b = np.asarray(getattr(want, leaf))
        bad = int(np.count_nonzero(a != b)) if a.shape == b.shape else -1
        check(bad == 0, f"{what}: {leaf} differs in {bad} entries")


def _warm_seconds(run):
    """Device time of ``run()`` after a first call has compiled it; returns
    ``(result, seconds)``, waiting for the device inside the timed span."""
    run().block_until_ready()
    t0 = time.perf_counter()
    out = run().block_until_ready()
    return out, time.perf_counter() - t0


def _chung_lu(n: int, m: int, seed: int, segment: int = 1 << 16):
    from repro.graph.generators import chung_lu_segments
    from repro.graph.sources import GeneratorSource

    return GeneratorSource(
        chung_lu_segments(n, gamma=2.5, seed=seed), m, segment_edges=segment
    )


def _prefix(source, rows: int) -> np.ndarray:
    out, have = [], 0
    for sl in source.iter_slices(0):
        out.append(sl[: rows - have])
        have += out[-1].shape[0]
        if have >= rows:
            break
    return np.concatenate(out)


def phase_paper_path(sizes: Sizes, seed: int) -> dict:
    """(a) chunked tier at com-LiveJournal's shape, n never cut."""
    import jax

    from repro.cluster import ClusterConfig, StreamClusterer
    from repro.core.chunked import chunked_update_megabatch
    from repro.core.state import ClusterState
    from repro.graph.generators import chung_lu_segments
    from repro.graph.sources import GeneratorSource

    n, m = sizes.n_paper, sizes.m_paper
    segment = chung_lu_segments(n, gamma=2.5, seed=seed)
    live_rows = [0]

    def counted(start: int, length: int) -> np.ndarray:
        # the live rows the stream delivers, counted on the host as they
        # are generated; the device must report the same edges_seen
        rows = segment(start, length)
        live_rows[0] += _live_rows(rows)
        return rows

    source = GeneratorSource(counted, m, segment_edges=1 << 16)
    cfg = ClusterConfig(
        n=n,
        v_max=V_MAX,
        backend="chunked",
        batch_edges=sizes.paper_batch,
        megabatch_k=sizes.paper_k,
    )
    log(f"(a) chunked: n={n} m={m} (not cut) v_max={V_MAX} "
        f"batch={sizes.paper_batch} K={sizes.paper_k}")
    t0 = time.perf_counter()
    sc = StreamClusterer(cfg).fit(source)
    sc.state.block_until_ready()
    wall = time.perf_counter() - t0
    log(f"(a) smoke timing, not a benchmark: {wall:.3f} s wall, "
        f"{m / wall:.0f} edges/s (generation and staging included)")

    live = live_rows[0]
    state = sc.state.to_numpy()
    d = state.d.astype(np.int64)
    v = state.v.astype(np.int64)
    c = state.c
    check(int(state.edges_seen) == live,
          f"(a) edges_seen {int(state.edges_seen)} != live rows {live}")
    check(int(d.sum()) == 2 * live, f"(a) sum(d) {int(d.sum())} != 2*{live}")
    check(int(v.sum()) == int(d.sum()),
          f"(a) sum(v) {int(v.sum())} != sum(d) {int(d.sum())}")
    check(int(c.min()) >= 0 and int(c.max()) < n,
          f"(a) labels outside [0, {n}): [{int(c.min())}, {int(c.max())}]")

    # chip vs host CPU: the same jitted update on the stream's prefix
    rows = min(sizes.prefix, m)
    k = -(-rows // sizes.paper_batch)
    slab = np.full((k * sizes.paper_batch, 2), -1, np.int32)
    slab[:rows] = _prefix(_chung_lu(n, rows, seed), rows)
    slab = slab.reshape(k, sizes.paper_batch, 2)
    cpu = jax.devices("cpu")[0]
    slab_dev = jax.numpy.asarray(slab)
    on_chip, t_dev = _warm_seconds(
        lambda: chunked_update_megabatch(
            ClusterState.init(n), slab_dev, np.int32(V_MAX), chunk=cfg.chunk
        )
    )
    on_cpu = chunked_update_megabatch(
        jax.device_put(ClusterState.init(n), cpu),
        jax.device_put(slab, cpu),
        jax.device_put(np.int32(V_MAX), cpu),
        chunk=cfg.chunk,
    )
    check(next(iter(on_cpu.d.devices())).platform == "cpu",
          "(a) the reference prefix did not run on the CPU")
    _assert_same_state(on_chip, on_cpu, "(a) chip vs CPU prefix")
    communities = int(np.unique(c).shape[0])
    log(f"(a) ok: edges_seen={live} sum(d)=sum(v)={int(d.sum())} "
        f"communities={communities}; chip == CPU on the first {rows} edges")
    log(f"(a) smoke timing: the chunked update alone, warm, took "
        f"{t_dev:.4f} s for those {rows} edges ({rows / t_dev:.0f} edges/s)")
    return {"wall_s": wall, "edges": m, "communities": communities,
            "update_s": t_dev}


def phase_exact_path(sizes: Sizes, seed: int) -> dict:
    """(b) compiled pallas kernel, megabatch and per-batch, vs dense."""
    import jax

    from repro.cluster import ClusterConfig, StreamClusterer
    from repro.core.state import ClusterState
    from repro.core.streaming import dense_update
    from repro.graph.pipeline import pad_batch
    from repro.kernels.edge_stream.ops import pallas_update_megabatch

    n, m, B = sizes.n_exact, sizes.m_exact, sizes.exact_batch
    edges = _prefix(_chung_lu(n, m, seed + 1), m)
    ref = dense_update(ClusterState.init(n, numpy=True), edges, V_MAX)
    cfg = ClusterConfig(
        n=n, v_max=V_MAX, backend="pallas", batch_edges=B,
        megabatch_k=sizes.exact_k,
    )
    t0 = time.perf_counter()
    mega = StreamClusterer(cfg).fit(edges)
    mega.state.block_until_ready()
    t_mega = time.perf_counter() - t0
    check(mega.stream_megabatches > 0, "(b) fit took no megabatch path")
    _assert_same_state(mega.state, ref, "(b) pallas megabatch vs dense")

    per = StreamClusterer(cfg.replace(megabatch_k=None))
    t0 = time.perf_counter()
    for start in range(0, m, B):
        per.partial_fit(pad_batch(edges[start : start + B], B))
    per.state.block_until_ready()
    t_per = time.perf_counter() - t0
    _assert_same_state(per.state, ref, "(b) pallas per-batch vs dense")
    check(int(per.state.edges_seen) == int(ref.edges_seen),
          "(b) per-batch edges_seen differs from dense")
    slab = jax.numpy.asarray(pad_batch(edges, -(-m // B) * B).reshape(-1, B, 2))
    kernel, t_kernel = _warm_seconds(
        lambda: pallas_update_megabatch(
            ClusterState.init(n), slab, V_MAX, chunk=cfg.chunk
        )
    )
    _assert_same_state(kernel, ref, "(b) pallas kernel alone vs dense")
    log(f"(b) ok: n={n} m={m}: megabatch ({mega.stream_dispatches} "
        f"dispatches, {t_mega:.3f} s) and per-batch ({per.stream_dispatches} "
        f"dispatches, {t_per:.3f} s) == dense (smoke timings, compiles "
        f"included)")
    log(f"(b) smoke timing: one warm kernel launch over all {m} edges took "
        f"{t_kernel:.4f} s ({t_kernel / m * 1e9:.0f} ns per edge)")
    return {"megabatch_s": t_mega, "per_batch_s": t_per,
            "kernel_s": t_kernel}


def _dve3_stream(edges: np.ndarray, n: int, seed: int) -> np.ndarray:
    """A source-sorted stream whose DVE3 blocks take both device paths: a
    first half of short-range edges (``|j - i| <= 100``, fixed u1 lanes)
    and then ``edges`` sorted by source (far endpoints: raw fallback)."""
    rng = np.random.default_rng(seed)
    m = edges.shape[0]
    i = np.sort(rng.integers(0, n, m))
    j = np.clip(i + rng.choice([-1, 1], m) * rng.integers(1, 101, m), 0, n - 1)
    order = np.argsort(edges[:, 0], kind="stable")
    return np.concatenate([np.stack([i, j], 1).astype(np.int32),
                           edges[order]])


def _decode_kernel_seconds(path: str, n: int, B: int, v_max: int):
    """Warm seconds of the fused decode kernel and of the sequential kernel
    on the first megabatch of ``path`` (compressed vs host-decoded)."""
    import jax.numpy as jnp

    from repro.core.state import ClusterState
    from repro.graph.pipeline import BatchPipeline
    from repro.graph.sources import CodecFileSource
    from repro.kernels.edge_stream.ops import (
        pallas_decode_update_megabatch,
        pallas_update_megabatch,
    )

    def first(kind):
        pipe = BatchPipeline(CodecFileSource(path), B, prefetch=0)
        return next(getattr(pipe, kind)(4))

    cm, mb = first("compressed_megabatches"), first("megabatches")
    payload, desc = jnp.asarray(cm.payload), jnp.asarray(cm.desc)
    edges = jnp.asarray(mb.edges)
    _, t_dec = _warm_seconds(lambda: pallas_decode_update_megabatch(
        ClusterState.init(n), payload, desc, v_max))
    _, t_seq = _warm_seconds(lambda: pallas_update_megabatch(
        ClusterState.init(n), edges, v_max))
    return t_dec, t_seq, cm.n_rows


def phase_other_kernels(sizes: Sizes, seed: int) -> dict:
    """(c) fleet, wavefront and device decode at small compiled shapes."""
    import jax

    from repro.cluster import (
        ClusterConfig,
        FleetClusterer,
        StreamClusterer,
    )
    from repro.graph.codecs import DeltaVarintCodec
    from repro.graph.generators import chung_lu_segments
    from repro.graph.sources import CodecFileSource, GeneratorSource

    n, m, B, T = sizes.n_small, sizes.m_small, sizes.small_batch, sizes.tenants
    result = {}
    base = ClusterConfig(n=n, v_max=V_MAX // 4, backend="pallas",
                         batch_edges=B)

    def tenant(t):
        return GeneratorSource(
            chung_lu_segments(n, seed=seed, seed_offset=t), m // 4 + 97 * t,
            segment_edges=1024,
        )

    fleet = FleetClusterer(base.replace(tenants=T)).fit(
        [tenant(t) for t in range(T)]
    ).finalize()
    for t in range(T):
        alone = StreamClusterer(base).fit(tenant(t)).finalize()
        _assert_same_state(fleet.tenant(t).state, alone.state,
                           f"(c) fleet tenant {t} vs standalone")
    log(f"(c) fleet: T={T} n={n} every tenant == its standalone run")
    result["fleet"] = "ok"

    edges = _prefix(_chung_lu(n, m, seed + 2, segment=1024), m)
    mega_cfg = base.replace(megabatch_k=4)
    seq = StreamClusterer(mega_cfg).fit(edges).finalize()
    try:
        wave = StreamClusterer(mega_cfg.replace(wavefront=16)).fit(edges)
    except NotImplementedError as exc:
        # Mosaic refuses the wavefront kernel on a v5e: on the chip this
        # configuration must raise the compiler's error, never fall back
        if jax.default_backend() != "tpu":
            raise
        log(f"(c) wavefront: refused by the compiler on this chip: "
            f"{str(exc).strip().splitlines()[0]}")
        result["wavefront"] = "refused"
    else:
        wave = wave.finalize()
        _assert_same_state(wave.state, seq.state, "(c) wavefront vs kernel")
        log(f"(c) wavefront: W=16 == sequential kernel "
            f"({wave.info['wavefront_waves']} waves)")
        result["wavefront"] = "ok"

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "stream.dvc")
        # 4096-row blocks at full size: each is decoded in two sub-windows
        CodecFileSource.write(
            path, _dve3_stream(edges, n, seed),
            DeltaVarintCodec(block_edges=2 * B, version=3),
        )
        host = StreamClusterer(mega_cfg).fit(CodecFileSource(path)).finalize()
        dev = StreamClusterer(mega_cfg.replace(device_decode=True)).fit(
            CodecFileSource(path)
        ).finalize()
        t_dec, t_seq, rows = _decode_kernel_seconds(path, n, B, V_MAX // 4)
    info = dev.info
    check(info["device_decoded_megabatches"] > 0,
          "(c) device_decode staged no compressed megabatch")
    check(info["device_fallback_segment_rate"] < 1.0,
          "(c) device_decode decoded no DVE3 lanes on the device")
    _assert_same_state(dev.state, host.state,
                       "(c) device decode vs host decode")
    log(f"(c) device_decode: == host decode "
        f"({info['device_decoded_megabatches']} megabatches, segment "
        f"fallback rate {info['device_fallback_segment_rate']})")
    log(f"(c) smoke timing: on the first megabatch ({rows} rows of u1 "
        f"lanes), warm, the fused decode kernel took {t_dec:.6f} s "
        f"({t_dec / rows * 1e9:.0f} ns per edge) and the sequential kernel "
        f"on the host-decoded rows {t_seq:.6f} s "
        f"({t_seq / rows * 1e9:.0f} ns per edge)")
    result["device_decode"] = "ok"
    return result


def run(sizes: Sizes, seed: int = 0) -> dict:
    from repro.compile_cache import use_compile_cache

    cache = use_compile_cache()
    out = {
        "a": phase_paper_path(sizes, seed),
        "b": phase_exact_path(sizes, seed),
        "c": phase_other_kernels(sizes, seed),
    }
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache: {cache} holds {entries} entries")
    return out


def main() -> int:
    import jax

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    log(f"device: {json.dumps(device)}")
    if dev.platform != "tpu":
        log("no TPU found: chip_smoke.py measures the chip or nothing")
        return 1
    t0 = time.perf_counter()
    run(Sizes())
    log(f"all phases ok in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
