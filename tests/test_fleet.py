"""Fleet engine tests (DESIGN.md §13).

The contract under test:

* **bit-identity** — a ``T``-tenant fleet run produces, for every tenant,
  labels/degrees/volumes bit-identical to ``T`` independent single-stream
  runs of the same backend and batch geometry, for every fleet-capable
  backend (``chunked`` / ``scan`` / ``pallas``) and over adversarial
  tenant-size mixes (empty tenants, sub-batch tenants, ragged tails);
* **router soundness** — ``TenantRouter`` never reorders within a tenant:
  each tenant's dispatched slab rows concatenate to exactly its stream,
  with exactly the batch boundaries a standalone ``BatchPipeline`` would
  produce, and the staging residency account drains back to zero;
* **one-checkpoint resume** — suspending mid-stream and restoring from the
  single fleet checkpoint (stacked state + per-tenant row vector) finishes
  with bit-identical labels to the uninterrupted run;
* **ragged-fleet no-ops** — tenants that are idle in a fleet step (all-PAD
  slab rows) are not perturbed: an all-idle fleet dispatch leaves every
  state row bit-identical, on every fleet path.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from hypothesis_compat import given, settings, st  # noqa: E402

from repro.cluster import (  # noqa: E402
    ClusterConfig,
    FleetClusterer,
    FleetState,
    TenantRouter,
    cluster,
    cluster_fleet,
)
from repro.core.fleet import fleet_update_chunked, fleet_update_scan  # noqa: E402
from repro.graph.generators import chung_lu_segments  # noqa: E402
from repro.graph.pipeline import PAD, BatchPipeline  # noqa: E402
from repro.graph.sources import GeneratorSource, as_source  # noqa: E402
from repro.kernels.edge_stream.ops import pallas_fleet_update  # noqa: E402

FLEET_BACKENDS = ("chunked", "scan", "pallas")


def _streams(sizes, n, seed):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, n, size=(m, 2)).astype(np.int32) for m in sizes
    ]


def _config(backend, n, T, v_max=8, batch_edges=32):
    return ClusterConfig(
        n=n,
        v_max=v_max,
        backend=backend,
        chunk=16,
        batch_edges=batch_edges,
        tenants=T,
    )


def _assert_fleet_matches_singles(backend, streams, n, v_max=8):
    T = len(streams)
    cfg = _config(backend, n, T, v_max=v_max)
    res = FleetClusterer(cfg).fit(streams).finalize()
    single_cfg = cfg.replace(tenants=None)
    for t, stream in enumerate(streams):
        ref = cluster(stream, single_cfg)
        got = res.tenant(t)
        assert np.array_equal(got.labels, ref.labels), (backend, t)
        assert np.array_equal(
            np.asarray(got.state.d), np.asarray(ref.state.d)
        ), (backend, t)
        assert np.array_equal(
            np.asarray(got.state.v), np.asarray(ref.state.v)
        ), (backend, t)


# ---------------------------------------------------------------------------
# Bit-identity: fleet == T independent single-stream runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", FLEET_BACKENDS)
def test_fleet_bit_identical_to_single_stream_runs(backend):
    # 16 tenants spanning the adversarial size mix: empty, sub-batch,
    # exactly one batch, batch+1, many ragged batches
    sizes = [0, 1, 3, 17, 31, 32, 33, 40, 64, 65, 90, 100, 129, 150, 200, 7]
    streams = _streams(sizes, n=64, seed=0)
    _assert_fleet_matches_singles(backend, streams, n=64)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    sizes=st.lists(st.integers(0, 120), min_size=16, max_size=16),
)
def test_property_fleet_bit_identical(seed, sizes):
    streams = _streams(sizes, n=48, seed=seed)
    for backend in FLEET_BACKENDS:
        _assert_fleet_matches_singles(backend, streams, n=48, v_max=6)


def test_fleet_generator_sources_with_seed_offsets():
    # per-tenant seed offsets: T independent generator streams from one
    # base seed, drained out-of-core through the router
    n, T, rows = 64, 5, 200
    sources = [
        GeneratorSource(chung_lu_segments(n, seed=9, seed_offset=t), rows)
        for t in range(T)
    ]
    cfg = _config("chunked", n, T)
    res = FleetClusterer(cfg).fit(sources).finalize()
    single_cfg = cfg.replace(tenants=None)
    for t in range(T):
        src = GeneratorSource(
            chung_lu_segments(n, seed=9, seed_offset=t), rows
        )
        ref = cluster(src, single_cfg)
        assert np.array_equal(res.tenant(t).labels, ref.labels), t
    # distinct offsets produced distinct streams (not T copies of one run)
    assert not np.array_equal(res.raw_labels[0], res.raw_labels[1])


# ---------------------------------------------------------------------------
# Router soundness
# ---------------------------------------------------------------------------

def test_router_matches_standalone_pipeline_boundaries():
    sizes = [0, 5, 32, 33, 100, 64]
    streams = _streams(sizes, n=50, seed=3)
    B = 32
    router = TenantRouter(streams, B)
    got = [[] for _ in streams]
    for slab in router.fleet_slabs():
        for t in range(len(streams)):
            k = int(slab.n_rows[t])
            rows = slab.edges[t]
            if k:
                got[t].append(rows[:k].copy())
            # PAD tail beyond the real rows, always
            assert np.all(rows[k:] == PAD)
    assert router._inflight_bytes == 0
    for t, stream in enumerate(streams):
        ref = [
            b.edges[: b.n_rows].copy()
            for b in BatchPipeline(as_source(stream), B).batches()
        ]
        assert len(got[t]) == len(ref), t
        for g, r in zip(got[t], ref):
            assert np.array_equal(g, r), t


def test_router_resume_reproduces_remaining_rows():
    sizes = [40, 7, 90, 0]
    streams = _streams(sizes, n=30, seed=4)
    router = TenantRouter(streams, 16)
    slabs = list(router.fleet_slabs())
    # stop after 2 fleet steps; resume from the dispatched-row vector
    rows = np.zeros(len(streams), np.int64)
    for slab in slabs[:2]:
        rows += slab.n_rows
    resumed = list(TenantRouter(streams, 16).fleet_slabs(rows))
    per_tenant = lambda ss, t: np.concatenate(
        [s.edges[t, : int(s.n_rows[t])] for s in ss]
        or [np.zeros((0, 2), np.int32)]
    )
    for t in range(len(streams)):
        assert np.array_equal(
            per_tenant(resumed, t), per_tenant(slabs[2:], t)
        ), t


def test_router_rates_schedule_is_deterministic_and_complete():
    sizes = [100, 25, 50]
    streams = _streams(sizes, n=40, seed=5)
    for rates in ([1, 1, 1], [4, 1, 2]):
        a = list(TenantRouter(streams, 16, rates=rates).fleet_slabs())
        b = list(TenantRouter(streams, 16, rates=rates).fleet_slabs())
        assert len(a) == len(b)
        for sa, sb in zip(a, b):
            assert np.array_equal(sa.edges, sb.edges)
        delivered = np.sum([s.n_rows for s in a], axis=0)
        assert np.array_equal(delivered, sizes)


def test_router_validation():
    with pytest.raises(ValueError):
        TenantRouter([], 16)
    with pytest.raises(ValueError):
        TenantRouter([np.zeros((4, 2), np.int32)], 0)
    with pytest.raises(ValueError):
        TenantRouter([np.zeros((4, 2), np.int32)], 16, rates=[1, 2])
    router = TenantRouter([np.zeros((4, 2), np.int32)], 16)
    with pytest.raises(ValueError):
        list(router.fleet_slabs([9]))  # resume row beyond the stream


# ---------------------------------------------------------------------------
# One-checkpoint suspend / resume
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", FLEET_BACKENDS)
def test_fleet_checkpoint_resume_bit_identical(backend, tmp_path):
    sizes = [0, 3, 17, 40, 64, 129, 200, 5]
    streams = _streams(sizes, n=64, seed=1)
    cfg = _config(backend, 64, len(streams))
    full = FleetClusterer(cfg).fit(streams).finalize()

    fc = FleetClusterer(cfg).fit(streams, max_steps=2)
    d = str(tmp_path / backend)
    fc.save(d)
    fc2 = FleetClusterer.restore(d)
    assert np.array_equal(fc2.tenant_rows, fc.tenant_rows)
    assert np.array_equal(fc2.edges_seen, fc.edges_seen)
    res = fc2.fit(streams).finalize()
    assert np.array_equal(res.raw_labels, full.raw_labels)
    assert np.array_equal(
        np.asarray(res.state.v), np.asarray(full.state.v)
    )
    assert np.array_equal(
        np.asarray(res.state.d), np.asarray(full.state.d)
    )


def test_fleet_restore_rejects_single_stream_checkpoint(tmp_path):
    from repro.cluster import StreamClusterer

    cfg = ClusterConfig(n=16, v_max=4, backend="chunked", chunk=8)
    sc = StreamClusterer(cfg)
    sc.partial_fit(np.array([[0, 1], [1, 2]], np.int32))
    d = str(tmp_path / "single")
    sc.save(d)
    with pytest.raises(ValueError, match="tenant_rows"):
        FleetClusterer.restore(d)


def test_fleet_chunked_matches_single_streams_across_move_paths():
    # the tenants' chunks take different move paths under one vmap:
    # tenant 0's fits one round of MOVE_SLOTS winners, tenant 1's (a fresh
    # state, every row wins) the chunk-wide pass, tenant 2's two rounds
    import jax.numpy as jnp

    from repro.core.chunked import MOVE_SLOTS, chunked_update
    from repro.core.state import ClusterState
    from test_chunked_moves import (
        CHUNK,
        V_MAX,
        case_exactly_slots,
        case_fresh,
        case_slots_plus_one,
        reference,
    )

    rng = np.random.default_rng(11)
    cases = [case_exactly_slots(rng), case_fresh(rng), case_slots_plus_one(rng)]
    winners = [reference(*state, e, V_MAX)[-1][0] for state, e, _ in cases]
    assert winners[0] <= MOVE_SLOTS < winners[2] <= CHUNK // 2 < winners[1], winners

    def as_device(d, c, v, edges_seen):
        return dict(
            d=jnp.asarray(d, jnp.int32),
            c=jnp.asarray(c, jnp.int32),
            v=jnp.asarray(v, jnp.int32),
            edges_seen=jnp.asarray(edges_seen, jnp.int32),
        )

    fleet = FleetState(
        **as_device(*(np.stack([s[k] for s, _, _ in cases]) for k in range(3)), [0] * 3)
    )
    edges = jnp.asarray(np.stack([e for _, e, _ in cases]), jnp.int32)
    got = fleet_update_chunked(fleet, edges, jnp.int32(V_MAX), chunk=CHUNK).to_numpy()
    for t, (state, e, _) in enumerate(cases):
        single = chunked_update(
            ClusterState(**as_device(*state, 0)),
            jnp.asarray(e, jnp.int32),
            jnp.int32(V_MAX),
            chunk=CHUNK,
        )
        for leaf in ("d", "c", "v", "edges_seen"):
            assert np.array_equal(
                np.asarray(getattr(got, leaf))[t], np.asarray(getattr(single, leaf))
            ), (t, leaf)


# ---------------------------------------------------------------------------
# Ragged fleets: idle tenants are bit-untouched
# ---------------------------------------------------------------------------

def test_all_idle_tenants_not_perturbed():
    # adversarial regression: an all-PAD slab dispatch must be a perfect
    # no-op on every fleet path — state rows bit-identical, edges_seen flat
    n, T, B = 32, 4, 16
    rng = np.random.default_rng(7)
    warm = rng.integers(0, n, size=(T, B, 2)).astype(np.int32)
    idle = np.full((T, B, 2), PAD, np.int32)
    import jax.numpy as jnp

    paths = {
        "chunked": lambda s, e: fleet_update_chunked(
            s, jnp.asarray(e), jnp.int32(5), chunk=8
        ),
        "scan": lambda s, e: fleet_update_scan(
            s, jnp.asarray(e), jnp.int32(5)
        ),
        "pallas": lambda s, e: pallas_fleet_update(
            s, jnp.asarray(e), 5, interpret=True
        ),
    }
    for name, step in paths.items():
        state = step(FleetState.init(n, T), warm)
        before = state.to_numpy()
        after = step(before.to_device(), idle).to_numpy()
        for leaf in ("d", "c", "v", "edges_seen"):
            assert np.array_equal(
                np.asarray(getattr(after, leaf)),
                np.asarray(getattr(before, leaf)),
            ), (name, leaf)


def test_partially_idle_fleet_steps_leave_idle_rows_pristine():
    # tenants 0 and 2 idle from the start; their rows must equal a fresh
    # init even after many fleet steps driven by the other tenants
    n = 40
    sizes = [0, 300, 0, 45]
    streams = _streams(sizes, n=n, seed=8)
    for backend in FLEET_BACKENDS:
        cfg = _config(backend, n, len(sizes), batch_edges=16)
        res = FleetClusterer(cfg).fit(streams).finalize()
        fresh = FleetState.init(n, 1, numpy=True)
        for t in (0, 2):
            for leaf in ("d", "c", "v"):
                assert np.array_equal(
                    np.asarray(getattr(res.state, leaf))[t],
                    np.asarray(getattr(fresh, leaf))[0],
                ), (backend, t, leaf)
        assert res.info["tenant_rows"][0] == 0


# ---------------------------------------------------------------------------
# API surface
# ---------------------------------------------------------------------------

def test_fleet_config_and_constructor_validation():
    with pytest.raises(ValueError, match="tenants"):
        ClusterConfig(n=8, v_max=2, tenants=0)
    with pytest.raises(ValueError, match="config.tenants"):
        FleetClusterer(ClusterConfig(n=8, v_max=2, backend="chunked"))
    with pytest.raises(ValueError, match="fleet"):
        FleetClusterer(
            ClusterConfig(n=8, v_max=2, backend="dense", tenants=2)
        )
    cfg = ClusterConfig(n=8, v_max=2, backend="chunked", tenants=2)
    with pytest.raises(ValueError, match="match"):
        FleetClusterer(cfg, state=FleetState.init(8, 3))
    with pytest.raises(ValueError, match="sources"):
        FleetClusterer(cfg).fit([np.zeros((2, 2), np.int32)])


def test_cluster_fleet_defaults_tenants_and_counts_dispatches():
    streams = _streams([10, 0, 33], n=24, seed=2)
    res = cluster_fleet(
        streams, ClusterConfig(n=24, v_max=4, backend="chunked", chunk=8,
                               batch_edges=16)
    )
    assert res.tenants == 3
    assert res.info["dispatches_per_fleet_step"] == 1.0
    assert res.info["stream_dispatches"] == res.info["fleet_steps"]
    assert res.info["peak_staging_bytes"] > 0
    assert res.labels.shape == (3, 24)
    # tenant() view exposes the standard edge-free metrics
    assert res.tenant(2).entropy is not None


def test_fleet_state_views():
    fs = FleetState.init(6, 3)
    assert fs.n == 6 and fs.tenants == 3
    entry = fs.entry(1)
    assert np.asarray(entry.c).shape == (6,)
    host = fs.to_numpy()
    assert isinstance(np.asarray(host.d), np.ndarray)
    assert host.to_device().d.shape == (3, 6)
