"""Fault-tolerance suite (DESIGN.md §15): deterministic chaos injection,
retry/quarantine accounting, kill-and-resume bit-identity, torn-artifact
restores, and fleet tenant isolation.

The chaos seed comes from ``CHAOS_SEED`` (default 0) so the CI chaos matrix
re-runs the same tests under different planted fault schedules — each seed
is fully deterministic, so failures reproduce locally with the same env.
"""

import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointCorruptError, CheckpointManager
from repro.cluster import ClusterConfig, cluster
from repro.cluster.api import StreamClusterer
from repro.cluster.fleet import FleetClusterer
from repro.dist.fault_tolerance import HeartbeatMonitor, PreemptionHandler
from repro.graph.codecs import DeltaVarintCodec
from repro.graph.errors import (
    CorruptStreamError,
    RetryPolicy,
    SourceDeadError,
    StallError,
    TransientReadError,
    retrying_slices,
)
from repro.graph.faults import (
    ChaosSource,
    FaultInjector,
    corrupt_blocks,
    list_blocks,
    truncate_blocks,
)
from repro.graph.pipeline import BatchPipeline
from repro.graph.sources import ArraySource, CodecFileSource

CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "0"))
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_dvc(path, edges, block_edges=1024):
    with open(path, "wb") as f:
        DeltaVarintCodec(block_edges=block_edges).encode(iter([edges]), f)
    return str(path)


def _edges(m, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, n, size=(m, 2), dtype=np.int32)


# ---------------------------------------------------------------------------
# RetryPolicy / retrying_slices / stall watchdog
# ---------------------------------------------------------------------------


def test_retry_policy_backoff_and_classes():
    p = RetryPolicy(max_retries=4, backoff_base=0.01, backoff_cap=0.03)
    assert [p.delay(k) for k in (1, 2, 3, 4)] == [0.01, 0.02, 0.03, 0.03]
    assert p.is_retryable(TransientReadError("x"))
    assert p.is_retryable(OSError("x"))
    assert not p.is_retryable(SourceDeadError("gone"))  # never retried
    assert not p.is_retryable(ValueError("corrupt"))
    with pytest.raises(ValueError):
        RetryPolicy(max_retries=-1)


def test_retrying_slices_resets_attempts_between_faults():
    # 5 independent transients with budget 1 each: consecutive counting
    # would die at the second fault, per-fault counting survives all 5
    edges = _edges(5000, 100)
    src = ChaosSource(
        ArraySource(edges),
        FaultInjector(CHAOS_SEED, transients=5, stall_seconds=0.0).plan(5000),
    )
    policy = RetryPolicy(max_retries=1, backoff_base=0.0)
    got = np.concatenate(
        list(
            retrying_slices(
                src.resume, src.cursor_at, src.cursor_at(0), policy
            )
        )
    )
    assert np.array_equal(got, edges)


def test_pipeline_retry_bit_identical_and_counted():
    edges = _edges(20_000, 200)
    plan = FaultInjector(CHAOS_SEED, transients=3, stall_seconds=0.0).plan(
        20_000
    )
    chaos = ChaosSource(ArraySource(edges), plan)
    pipe = BatchPipeline(chaos, 1024, retry=RetryPolicy(backoff_base=0.0))
    got = np.concatenate([b.edges[: b.n_rows] for b in pipe.batches()])
    assert np.array_equal(got, edges)
    assert pipe.retries == 3


def test_pipeline_retry_disabled_propagates():
    edges = _edges(4000, 100)
    plan = FaultInjector(CHAOS_SEED, transients=1, stall_seconds=0.0).plan(4000)
    pipe = BatchPipeline(
        ChaosSource(ArraySource(edges), plan), 512, retry=None
    )
    with pytest.raises(TransientReadError):
        list(pipe.batches())


def test_pipeline_stall_watchdog():
    class Wedged(ArraySource):
        def iter_slices(self, start=0):
            yield self.edges[start : start + 256]
            time.sleep(5.0)
            yield self.edges[start + 256 :]

    pipe = BatchPipeline(
        Wedged(_edges(4000, 50)), 256, retry=None, stall_timeout=0.2
    )
    t0 = time.monotonic()
    with pytest.raises(StallError):
        list(pipe.batches())
    assert time.monotonic() - t0 < 3.0  # raised promptly, no 5 s hang


# ---------------------------------------------------------------------------
# Deterministic fault plans / ChaosSource
# ---------------------------------------------------------------------------


def test_fault_plans_reproducible_by_seed():
    mk = lambda: FaultInjector(
        CHAOS_SEED, transients=4, stalls=2, die=True
    ).plan(123_456)
    assert mk() == mk()
    other = FaultInjector(
        CHAOS_SEED + 1, transients=4, stalls=2, die=True
    ).plan(123_456)
    assert mk() != other


def test_chaos_source_death_is_permanent():
    edges = _edges(6000, 100)
    plan = FaultInjector(CHAOS_SEED, die=True).plan(6000)
    src = ChaosSource(ArraySource(edges), plan)
    with pytest.raises(SourceDeadError):
        np.concatenate(list(src.iter_slices(0)))
    with pytest.raises(SourceDeadError):
        list(src.iter_slices(0))  # still dead; retries are useless
    assert not RetryPolicy().is_retryable(SourceDeadError("gone"))


# ---------------------------------------------------------------------------
# Quarantine accounting: exact planted loss, e2e through cluster()
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["chunked", "pallas"])
def test_corrupt_blocks_exact_loss_e2e(tmp_path, backend):
    n = 500
    edges = _edges(30_000, n, seed=3)
    path = _write_dvc(tmp_path / "e.dvc", edges, block_edges=512)
    planted = corrupt_blocks(path, seed=CHAOS_SEED, n_blocks=3)
    cfg = ClusterConfig(
        n=n,
        v_max=40,
        backend=backend,
        chunk=256,
        batch_edges=2048,
        on_corrupt="quarantine",
    )
    out = cluster(path, cfg)
    assert out.info["edges_lost"] == planted["rows_lost"]
    # adjacent corrupt blocks can merge into one resync gap event
    assert 1 <= out.info["blocks_quarantined"] <= 3
    # the surviving rows are exactly the non-quarantined ones, in order
    lost = set()
    for _, first, k in planted["blocks"]:
        lost.update(range(first, first + k))
    keep = np.array([r for r in range(len(edges)) if r not in lost])
    ref = cluster(edges[keep], cfg.replace(on_corrupt="raise"))
    assert np.array_equal(out.labels, ref.labels)


def test_truncated_tail_exact_loss_e2e(tmp_path):
    n = 400
    edges = _edges(20_000, n, seed=4)
    path = _write_dvc(tmp_path / "t.dvc", edges, block_edges=512)
    planted = truncate_blocks(path, n_blocks=5)
    cfg = ClusterConfig(
        n=n, v_max=40, backend="chunked", chunk=256, batch_edges=2048,
        on_corrupt="quarantine",
    )
    out = cluster(path, cfg)
    assert out.info["edges_lost"] == planted["rows_lost"]
    ref = cluster(
        edges[: planted["first_lost_row"]], cfg.replace(on_corrupt="raise")
    )
    assert np.array_equal(out.labels, ref.labels)


def test_corrupt_block_raises_typed_without_quarantine(tmp_path):
    edges = _edges(10_000, 300, seed=5)
    path = _write_dvc(tmp_path / "r.dvc", edges, block_edges=512)
    corrupt_blocks(path, seed=CHAOS_SEED, n_blocks=1)
    cfg = ClusterConfig(
        n=300, v_max=40, backend="chunked", chunk=256, batch_edges=2048
    )
    with pytest.raises(CorruptStreamError):
        cluster(path, cfg)


def test_quarantine_counts_idempotent_across_passes(tmp_path):
    edges = _edges(12_000, 300, seed=6)
    path = _write_dvc(tmp_path / "i.dvc", edges, block_edges=512)
    planted = corrupt_blocks(path, seed=CHAOS_SEED, n_blocks=2)
    src = CodecFileSource(path, on_corrupt="quarantine")
    a = np.concatenate(list(src.iter_slices(0)))
    b = np.concatenate(list(src.iter_slices(0)))  # second pass, same source
    assert np.array_equal(a, b)
    assert src.edges_lost == planted["rows_lost"]  # not double-counted
    # adjacent corrupt blocks merge into one resync gap, so the event
    # count is bounded by the planted count, never inflated by re-walks
    assert 1 <= src.blocks_quarantined <= 2


# ---------------------------------------------------------------------------
# Autosave + crash recovery (SIGTERM drain and hard SIGKILL)
# ---------------------------------------------------------------------------

_CHILD = textwrap.dedent(
    """
    import os, sys
    import numpy as np
    from repro.cluster import ClusterConfig
    from repro.cluster.api import StreamClusterer
    from repro.dist.fault_tolerance import PreemptionHandler
    from repro.graph.faults import ChaosSource, FaultInjector
    from repro.graph.sources import CodecFileSource

    path, ckpt, backend, seed = sys.argv[1:5]
    src = CodecFileSource(path)
    plan = FaultInjector(
        int(seed), transients=2, stalls=60, stall_seconds=0.05
    ).plan(src.n_edges)
    cfg = ClusterConfig(
        n=500, v_max=40, backend=backend, chunk=256, batch_edges=1024,
        autosave_every=2048, autosave_dir=ckpt,
    )
    pre = PreemptionHandler()
    pre.install()
    sc = StreamClusterer(cfg)
    print("READY", flush=True)
    sc.fit(ChaosSource(src, plan), preemption=pre)
    if pre.preempted:
        print("PREEMPTED", sc.stream_offset, flush=True)
        sys.exit(0)
    sc.save(ckpt)
    print("DONE", sc.stream_offset, flush=True)
    """
)


def _spawn_child(path, ckpt, backend):
    return subprocess.Popen(
        [sys.executable, "-c", _CHILD, path, ckpt, backend, str(CHAOS_SEED)],
        stdout=subprocess.PIPE,
        text=True,
        env={
            **os.environ,
            "PYTHONPATH": os.path.join(ROOT, "src"),
            "JAX_PLATFORMS": "cpu",
        },
    )


def _resume_and_compare(ckpt, path, edges, backend):
    """Restore from the newest valid autosave generation, drain the rest of
    the (fault-free) file, and demand bit-identity with an uninterrupted
    fault-free run."""
    sc = StreamClusterer.restore(ckpt)
    assert sc.stream_offset % 1024 == 0  # exact batch-boundary cursor
    sc.fit(CodecFileSource(path))
    out = sc.finalize()
    cfg = ClusterConfig(
        n=500, v_max=40, backend=backend, chunk=256, batch_edges=1024,
    )
    ref = cluster(edges, cfg)
    assert np.array_equal(out.labels, ref.labels)
    return out


@pytest.mark.parametrize("backend", ["chunked", "pallas"])
def test_sigterm_drain_then_resume_bit_identical(tmp_path, backend):
    edges = _edges(24_000, 500, seed=7)
    path = _write_dvc(tmp_path / "s.dvc", edges)
    ckpt = str(tmp_path / "ck")
    proc = _spawn_child(path, ckpt, backend)
    try:
        assert proc.stdout.readline().strip() == "READY"
        time.sleep(1.0)  # land mid-stream (pacing stalls keep it there)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, out
    # drained cleanly: either preempted mid-stream or already finished
    assert out.splitlines()[-1].split()[0] in ("PREEMPTED", "DONE"), out
    _resume_and_compare(ckpt, path, edges, backend)


@pytest.mark.parametrize("backend", ["chunked", "pallas"])
def test_sigkill_then_resume_bit_identical(tmp_path, backend):
    edges = _edges(24_000, 500, seed=8)
    path = _write_dvc(tmp_path / "k.dvc", edges)
    ckpt = str(tmp_path / "ck")
    proc = _spawn_child(path, ckpt, backend)
    try:
        assert proc.stdout.readline().strip() == "READY"
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                break  # finished before we could kill it — still resumable
            if os.path.isdir(ckpt) and any(
                e.startswith("step_") for e in os.listdir(ckpt)
            ):
                proc.kill()  # SIGKILL: no drain, no atexit, nothing
                break
            time.sleep(0.02)
        proc.wait(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
    _resume_and_compare(ckpt, path, edges, backend)


# ---------------------------------------------------------------------------
# Torn checkpoint artifacts: typed errors + generation fallback
# ---------------------------------------------------------------------------


def _save_generations(tmp_path, steps=(10, 20)):
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=5)
    for s in steps:
        mgr.save(s, {"x": np.arange(s, dtype=np.int64)})
    return mgr


def test_truncated_manifest_falls_back_a_generation(tmp_path):
    mgr = _save_generations(tmp_path)
    man = tmp_path / "ck" / "step_20" / "manifest.json"
    man.write_text(man.read_text()[: 17])  # torn mid-JSON
    with pytest.raises(CheckpointCorruptError):
        mgr.restore({"x": np.zeros(1, np.int64)}, step=20)
    restored = mgr.restore({"x": np.zeros(1, np.int64)})  # newest valid
    assert np.array_equal(restored["x"], np.arange(10))


def test_missing_leaf_is_typed_and_falls_back(tmp_path):
    mgr = _save_generations(tmp_path)
    os.remove(tmp_path / "ck" / "step_20" / "x.npy")
    with pytest.raises(CheckpointCorruptError, match="missing"):
        mgr.restore({"x": np.zeros(1, np.int64)}, step=20)
    restored = mgr.restore({"x": np.zeros(1, np.int64)})
    assert np.array_equal(restored["x"], np.arange(10))


def test_bitflipped_leaf_fails_checksum_and_falls_back(tmp_path):
    mgr = _save_generations(tmp_path)
    leaf = tmp_path / "ck" / "step_20" / "x.npy"
    raw = bytearray(leaf.read_bytes())
    raw[-3] ^= 0xFF  # flip a payload byte; shape/header stay plausible
    leaf.write_bytes(bytes(raw))
    with pytest.raises(CheckpointCorruptError, match="checksum"):
        mgr.restore({"x": np.zeros(1, np.int64)}, step=20)
    restored = mgr.restore({"x": np.zeros(1, np.int64)})
    assert np.array_equal(restored["x"], np.arange(10))


def test_every_generation_corrupt_raises_aggregate(tmp_path):
    mgr = _save_generations(tmp_path)
    for s in (10, 20):
        os.remove(tmp_path / "ck" / f"step_{s}" / "x.npy")
    with pytest.raises(CheckpointCorruptError, match="every checkpoint"):
        mgr.restore({"x": np.zeros(1, np.int64)})


def test_dvc_truncated_midblock_is_typed(tmp_path):
    # plain (unchecksummed) framing: mid-block truncation must surface as a
    # typed CorruptStreamError, never a bare ValueError with no class
    edges = _edges(8000, 200, seed=9)
    path = str(tmp_path / "p.dvc")
    with open(path, "wb") as f:
        DeltaVarintCodec(block_edges=512, checksum=False).encode(
            iter([edges]), f
        )
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 37)
    src = CodecFileSource(path)
    with pytest.raises(CorruptStreamError):
        np.concatenate(list(src.iter_slices(0)))


# ---------------------------------------------------------------------------
# CheckpointManager swap-window crash regression
# ---------------------------------------------------------------------------


def test_crash_between_aside_and_rename_leaves_old_generation(
    tmp_path, monkeypatch
):
    """The historical bug: rmtree(final) before rename(tmp, final) had a
    window with *zero* complete generations on disk.  The aside-rename swap
    must leave the previous generation recoverable at every instant."""
    d = str(tmp_path / "ck")
    mgr = CheckpointManager(d)
    mgr.save(7, {"x": np.arange(5, dtype=np.int64)})

    import shutil as _shutil

    real_rename = os.rename
    calls = {"n": 0}

    def crashing_rename(a, b):
        real_rename(a, b)
        if b.endswith(".old"):
            calls["n"] += 1
            raise RuntimeError("simulated crash after renaming aside")

    monkeypatch.setattr(os, "rename", crashing_rename)
    with pytest.raises(RuntimeError, match="simulated crash"):
        mgr.save(7, {"x": np.arange(99, dtype=np.int64)})
    monkeypatch.setattr(os, "rename", real_rename)
    assert calls["n"] == 1

    # a fresh manager heals the orphaned .old back into place and the
    # previous generation restores intact
    mgr2 = CheckpointManager(d)
    restored = mgr2.restore({"x": np.zeros(1, np.int64)}, step=7)
    assert np.array_equal(restored["x"], np.arange(5))
    assert not any(e.endswith(".old") for e in os.listdir(d))
    # and the manager is fully functional afterwards
    mgr2.save(7, {"x": np.arange(9, dtype=np.int64)})
    assert np.array_equal(
        mgr2.restore({"x": np.zeros(1, np.int64)}, step=7)["x"], np.arange(9)
    )


def test_crash_after_swap_drops_stale_aside(tmp_path, monkeypatch):
    d = str(tmp_path / "ck")
    mgr = CheckpointManager(d)
    mgr.save(3, {"x": np.arange(4, dtype=np.int64)})

    import shutil

    real_rmtree = shutil.rmtree

    def crashing_rmtree(p, *a, **k):
        if p.endswith(".old"):
            raise RuntimeError("simulated crash before dropping aside")
        return real_rmtree(p, *a, **k)

    monkeypatch.setattr(shutil, "rmtree", crashing_rmtree)
    with pytest.raises(RuntimeError, match="simulated crash"):
        mgr.save(3, {"x": np.arange(8, dtype=np.int64)})
    monkeypatch.setattr(shutil, "rmtree", real_rmtree)

    # both generations exist; the NEW one won the swap, so recovery keeps
    # it and drops the stale aside
    mgr2 = CheckpointManager(d)
    restored = mgr2.restore({"x": np.zeros(1, np.int64)}, step=3)
    assert np.array_equal(restored["x"], np.arange(8))
    assert not any(e.endswith(".old") for e in os.listdir(d))


# ---------------------------------------------------------------------------
# PreemptionHandler / HeartbeatMonitor satellites
# ---------------------------------------------------------------------------


def test_preemption_install_returns_and_uninstall_restores():
    sentinel_calls = []

    def sentinel(signum, frame):
        sentinel_calls.append(signum)

    prev0 = signal.signal(signal.SIGUSR1, sentinel)
    try:
        h = PreemptionHandler()
        displaced = h.install(signals=(signal.SIGUSR1,))
        assert displaced[signal.SIGUSR1] is sentinel
        os.kill(os.getpid(), signal.SIGUSR1)
        assert h.preempted and not sentinel_calls
        h.uninstall()
        assert signal.getsignal(signal.SIGUSR1) is sentinel
        os.kill(os.getpid(), signal.SIGUSR1)
        assert sentinel_calls == [signal.SIGUSR1]
    finally:
        signal.signal(signal.SIGUSR1, prev0)


def test_heartbeat_median_is_true_median():
    mon = HeartbeatMonitor(window=10)
    for d in (0.1, 0.2, 0.9):  # mean 0.4 — a mean would misreport
        mon._durations.append(d)
    assert mon.median == pytest.approx(0.2)
    mon._durations.append(0.3)
    assert mon.median == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# Fleet tenant isolation
# ---------------------------------------------------------------------------


def test_fleet_one_dead_tenant_survivors_bit_identical():
    T, n = 16, 300
    rng = np.random.default_rng(CHAOS_SEED)
    streams = [
        rng.integers(0, n, size=(int(rng.integers(2000, 5000)), 2), dtype=np.int32)
        for _ in range(T)
    ]
    dead = int(rng.integers(T))
    plan = FaultInjector(CHAOS_SEED, die=True).plan(len(streams[dead]))
    sources = [
        ChaosSource(ArraySource(s), plan) if t == dead else ArraySource(s)
        for t, s in enumerate(streams)
    ]
    cfg = ClusterConfig(
        n=n, v_max=30, backend="chunked", chunk=128, batch_edges=512,
        tenants=T, on_tenant_fault="quarantine",
    )
    out = FleetClusterer(cfg).fit(sources).finalize()
    assert out.info["tenants_quarantined"] == [dead]
    assert "SourceDeadError" in out.info["tenant_faults"][dead]
    solo_cfg = cfg.replace(tenants=None, on_tenant_fault="raise")
    for t in range(T):
        if t == dead:
            continue
        solo = StreamClusterer(solo_cfg).fit(ArraySource(streams[t])).finalize()
        assert np.array_equal(out.tenant(t).labels, solo.labels), t
    # the dead tenant dispatched at most its pre-death prefix
    assert out.info["tenant_rows"][dead] <= plan.die_row


def test_fleet_default_policy_raises_on_dead_tenant():
    T, n = 4, 100
    rng = np.random.default_rng(CHAOS_SEED + 1)
    streams = [
        rng.integers(0, n, size=(3000, 2), dtype=np.int32) for _ in range(T)
    ]
    plan = FaultInjector(CHAOS_SEED, die=True).plan(3000)
    sources = [
        ChaosSource(ArraySource(s), plan) if t == 1 else ArraySource(s)
        for t, s in enumerate(streams)
    ]
    cfg = ClusterConfig(
        n=n, v_max=30, backend="chunked", chunk=128, batch_edges=512, tenants=T
    )
    with pytest.raises(SourceDeadError):
        FleetClusterer(cfg).fit(sources)


# ---------------------------------------------------------------------------
# Chaos + retry through the one-call API (transients are invisible)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["chunked", "pallas"])
def test_transient_chaos_invisible_to_labels(tmp_path, backend):
    n = 400
    edges = _edges(16_000, n, seed=11)
    path = _write_dvc(tmp_path / "c.dvc", edges)
    plan = FaultInjector(CHAOS_SEED, transients=4, stall_seconds=0.0).plan(
        16_000
    )
    cfg = ClusterConfig(
        n=n, v_max=40, backend=backend, chunk=256, batch_edges=2048,
    )
    out = (
        StreamClusterer(cfg)
        .fit(ChaosSource(CodecFileSource(path), plan))
        .finalize()
    )
    ref = cluster(edges, cfg)
    assert np.array_equal(out.labels, ref.labels)
    assert out.info["ingest_retries"] == 4
    assert out.info["edges_lost"] == 0
