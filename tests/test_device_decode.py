"""Device-resident compressed ingest tests (DESIGN.md §14).

The contract under test:

* **slab identity** — the pure-JAX reference decode reconstructs, bit for
  bit, the ``(K * B, 2)`` PAD-carved slab the host-decode staging path
  stages for the same rows, on streams mixing every DVE3 width class,
  raw-fallback blocks, and a ragged tail;
* **kernel pinning** — the Pallas decode kernel and the fused
  decode→update kernel (run through the emulator) are pinned against that
  reference: identical slabs, identical post-update state (the CI
  interpret leg runs this file on the tier-1 matrix);
* **round-trip** — a cursor taken at *any* batch boundary — including one
  that lands inside a compressed megabatch's framing — restores
  bit-identical labels whether the run suspends/resumes under
  ``device_decode=True`` or ``False``, and whether the resumed session
  flips the knob (property test);
* **rejection** — a torn descriptor table (spliced rows, bad widths,
  truncated payload, non-tiling segments) raises instead of decoding
  garbage;
* **plumbing** — ``device_decode`` config guards, backend capability
  errors, and the §14 info counters.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from hypothesis_compat import given, settings, st  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from repro.cluster import (  # noqa: E402
    ClusterConfig,
    CodecFileSource,
    DeltaVarintCodec,
    StreamClusterer,
    cluster,
)
from repro.core.decode import (  # noqa: E402
    chunked_decode_update_megabatch,
    decode_megabatch,
)
from repro.core.state import ClusterState  # noqa: E402
from repro.graph.pipeline import (  # noqa: E402
    BatchPipeline,
    D_KIND,
    D_NROWS,
    D_ROW,
    D_W_I,
    D_W_J,
    DESC_FIXED,
)
from repro.kernels.edge_stream.kernel import (  # noqa: E402
    DECODE_ROWS,
    DESC_COLS,
)
from repro.kernels.edge_stream.ops import (  # noqa: E402
    pallas_decode_update_megabatch,
    pallas_update_megabatch,
)


def _mixed_stream(n, m, seed):
    """Adjacency-ordered stream with both DVE3 segment kinds live: small
    positive deltas (u1/u2 fixed blocks) plus two contiguous far-endpoint
    bursts, each confined to a stretch of the stream so the blocks they
    land in take the raw/varint fallback while the rest stay fixed."""
    rng = np.random.default_rng(seed)
    i = np.sort(rng.integers(0, n - 2, m))
    j = np.minimum(i + rng.integers(1, 9, m), n - 1)
    for at in (m // 3, (2 * m) // 3):
        burst = min(max(m // 16, 1), m - at)
        j[at : at + burst] = rng.integers(0, n, burst)
    j = np.where(j == i, np.minimum(i + 1, n - 1), j)
    return np.stack([i, j], 1).astype(np.int32)


def _width_stream(seg, seed):
    """Four ``seg``-row stretches whose DVE3 blocks take, in turn, u1/u1,
    u2/u2 (both signs of ``j - i``) and u1/u4 fixed lanes, then the raw
    fallback (random far endpoints); every id stays below ``WIDE_N``."""
    rng = np.random.default_rng(seed)
    steps = rng.integers([0, 64, 0, 0], [40, 200, 40, 40], (seg, 4)).T.ravel()
    steps[seg : 2 * seg : 256] = 10_000  # a few 3-byte varints: u2 wins
    i = 40_000 + np.cumsum(steps)
    off = np.concatenate([
        rng.integers(1, 60, seg),
        rng.choice([-1, 1], seg) * rng.integers(200, 30_000, seg),
        (1 << 20) + rng.integers(0, 1000, seg),
        rng.integers(0, WIDE_N, seg) - i[3 * seg :],
    ])
    return np.stack([i, i + off], 1).astype(np.int32)


WIDE_N = 1 << 21


def _write(tmp_path, edges, block_edges):
    path = str(tmp_path / "stream.dvc3")
    CodecFileSource.write(
        path, edges, DeltaVarintCodec(block_edges=block_edges, version=3)
    )
    return path


def _assert_states_equal(a, b):
    for field in ("d", "c", "v", "edges_seen"):
        assert np.array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        ), field


def _slab_pairs(path, B, K):
    """(host-staged slab, compressed megabatch) pairs over the stream."""
    host = BatchPipeline(CodecFileSource(path), B, prefetch=0)
    comp = BatchPipeline(CodecFileSource(path), B, prefetch=0)
    return list(
        zip(
            (np.asarray(mb.edges).reshape(-1, 2) for mb in host.megabatches(K)),
            comp.compressed_megabatches(K),
        )
    )


# ---------------------------------------------------------------------------
# Reference decode == host-staged slab
# ---------------------------------------------------------------------------

def test_decode_reference_matches_host_slab(tmp_path):
    edges = _mixed_stream(900, 20_000, 3)
    path = _write(tmp_path, edges, block_edges=1024)
    pairs = _slab_pairs(path, B=512, K=4)
    assert len(pairs) > 1  # exercises a ragged tail megabatch
    saw_raw = saw_fixed = False
    for ref, cm in pairs:
        kinds = np.asarray(cm.desc[: cm.n_desc, D_KIND])
        saw_fixed |= bool((kinds == DESC_FIXED).any())
        saw_raw |= bool((kinds != DESC_FIXED).any())
        dec = np.asarray(
            decode_megabatch(
                jnp.asarray(cm.payload), jnp.asarray(cm.desc),
                cm.window, cm.out_rows,
            )
        )
        assert dec.shape == ref.shape
        assert np.array_equal(dec, ref)
    assert saw_fixed and saw_raw  # the stream covered both segment kinds


# ---------------------------------------------------------------------------
# Pallas kernels pinned against the reference (emulator)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_edges", [512, 4096])
def test_fused_decode_update_kernel_pins_reference(tmp_path, block_edges):
    """The fused kernel (run through the emulator) equals host decode + the
    sequential kernel on every lane width and the raw fallback; 4096-row
    segments span two DECODE_ROWS sub-windows."""
    n, v_max = WIDE_N, 24
    edges = _width_stream(block_edges, 13)
    assert 0 <= edges.min() and edges.max() < n
    path = _write(tmp_path, edges, block_edges=block_edges)
    seq = ClusterState.init(n)
    fused = ClusterState.init(n)
    pairs = _slab_pairs(path, B=512, K=2 * block_edges // 512)
    segments = {
        (int(r[D_KIND]), int(r[D_W_I]), int(r[D_W_J]), int(r[D_NROWS]))
        for _, cm in pairs
        for r in cm.desc[: cm.n_desc]
    }
    assert {(DESC_FIXED, 1, 1), (DESC_FIXED, 2, 2), (DESC_FIXED, 1, 4)} <= {
        s[:3] for s in segments
    }
    assert any(s[0] != DESC_FIXED for s in segments)
    longest = max(s[3] for s in segments)
    assert (longest > DECODE_ROWS) == (block_edges > DECODE_ROWS)
    for ref, cm in pairs:
        seq = pallas_update_megabatch(
            seq, jnp.asarray(ref).reshape(1, cm.out_rows, 2), v_max,
            chunk=512,
        )
        fused = pallas_decode_update_megabatch(
            fused, jnp.asarray(cm.payload), jnp.asarray(cm.desc), v_max,
            interpret=True,
        )
    _assert_states_equal(seq, fused)


def test_chunked_fused_jit_matches_reference_composition(tmp_path):
    from repro.core.chunked import chunked_update_megabatch

    n, v_max = 300, 16
    edges = _mixed_stream(n, 3000, 17)
    path = _write(tmp_path, edges, block_edges=512)
    a = ClusterState.init(n)
    b = ClusterState.init(n)
    for ref, cm in _slab_pairs(path, B=256, K=3):
        a = chunked_update_megabatch(
            a, jnp.asarray(ref).reshape(1, cm.out_rows, 2),
            jnp.int32(v_max), chunk=256,
        )
        b = chunked_decode_update_megabatch(
            b, jnp.asarray(cm.payload), jnp.asarray(cm.desc), v_max,
            cm.window, cm.out_rows, chunk=256,
        )
    _assert_states_equal(a, b)


# ---------------------------------------------------------------------------
# End-to-end: device_decode on == off == in-memory, counters, dispatches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["chunked", "pallas"])
def test_fit_device_decode_bit_identical(tmp_path, backend):
    n = 500
    edges = _mixed_stream(n, 12_000, 29)
    path = _write(tmp_path, edges, block_edges=1024)
    base = ClusterConfig(
        n=n, v_max=32, backend=backend, batch_edges=1024, megabatch_k=4,
        chunk=1024,
    )
    oracle = cluster(edges, base.replace(megabatch_k=None))
    off = StreamClusterer(base).fit(CodecFileSource(path))
    on = StreamClusterer(base.replace(device_decode=True)).fit(
        CodecFileSource(path)
    )
    r_off, r_on = off.finalize(), on.finalize()
    assert np.array_equal(r_off.labels, r_on.labels)
    assert np.array_equal(oracle.labels, r_on.labels)
    assert (
        r_off.info["stream_dispatches"] == r_on.info["stream_dispatches"]
    )
    assert r_on.info["device_decoded_megabatches"] > 0
    assert 0.0 <= r_on.info["device_fallback_segment_rate"] <= 1.0
    assert r_on.info["device_fallback_rows"] >= 0


# ---------------------------------------------------------------------------
# Round-trip property: suspend at any batch boundary, resume either mode
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    cut=st.integers(1, 11),
    suspend_on=st.booleans(),
    resume_on=st.booleans(),
)
def test_cursor_round_trip_any_boundary(
    tmp_path_factory, seed, cut, suspend_on, resume_on
):
    """A checkpoint at batch boundary ``cut`` — usually *inside* a
    compressed megabatch's K-frame — resumes to labels bit-identical to
    the uninterrupted run, for every on/off combination of
    ``device_decode`` across the suspend/resume sessions."""
    tmp_path = tmp_path_factory.mktemp("roundtrip")
    n, B, K = 300, 256, 4
    edges = _mixed_stream(n, 12 * B, seed)
    path = _write(tmp_path, edges, block_edges=B)
    base = ClusterConfig(
        n=n, v_max=24, backend="chunked", batch_edges=B, megabatch_k=K,
        chunk=B,
    )
    cfg = lambda on: base.replace(device_decode=on)  # noqa: E731
    straight = (
        StreamClusterer(cfg(suspend_on)).fit(CodecFileSource(path)).finalize()
    )

    sc = StreamClusterer(cfg(suspend_on))
    sc.fit(CodecFileSource(path), max_batches=cut)
    ckpt = str(tmp_path / f"ckpt-{seed}-{cut}")
    sc.save(ckpt)
    sc2 = StreamClusterer.restore(ckpt, config=cfg(resume_on))
    assert sc2.stream_offset == sc.stream_offset
    sc2.fit(CodecFileSource(path))
    assert np.array_equal(sc2.finalize().labels, straight.labels)


# ---------------------------------------------------------------------------
# Torn descriptor tables are rejected
# ---------------------------------------------------------------------------

def _one_cmega(tmp_path, seed=5):
    edges = _mixed_stream(400, 4096, seed)
    path = _write(tmp_path, edges, block_edges=512)
    pipe = BatchPipeline(CodecFileSource(path), 512, prefetch=0)
    return next(iter(pipe.compressed_megabatches(4)))


def test_torn_descriptor_tables_rejected(tmp_path):
    cm = _one_cmega(tmp_path)
    cm.validate()  # the clean slab passes

    def tamper(**cols):
        d = cm.desc.copy()
        for col, val in cols.items():
            d[0, globals()[col]] = val
        return cm._replace(desc=d)

    torn = [
        cm._replace(n_desc=cm.desc.shape[0] + 1),  # n_desc past the table
        cm._replace(n_desc=cm.n_desc - 1),  # live row past n_desc
        tamper(D_KIND=9),  # unknown kind
        tamper(D_NROWS=0),  # empty live segment
        tamper(D_NROWS=cm.window + 1),  # wider than the decode window
        tamper(D_ROW=3),  # segments no longer tile [0, n_rows)
        tamper(D_W_I=3),  # width the device cannot decode
        cm._replace(payload=cm.payload[:8]),  # truncated payload
    ]
    for bad in torn:
        with pytest.raises(ValueError, match="torn"):
            bad.validate()


def test_partial_fit_cmegabatch_rejects_torn_table(tmp_path):
    cm = _one_cmega(tmp_path, seed=7)
    sc = StreamClusterer(
        ClusterConfig(
            n=400, v_max=16, backend="chunked", batch_edges=512,
            megabatch_k=4, chunk=512, device_decode=True,
        )
    )
    d = cm.desc.copy()
    d[0, D_ROW] += 1
    with pytest.raises(ValueError, match="torn"):
        sc.partial_fit_cmegabatch(cm._replace(desc=d))
    # the clean slab still ingests after the rejection
    sc.partial_fit_cmegabatch(cm)
    assert sc.stream_offset == cm.n_rows


# ---------------------------------------------------------------------------
# Config guards + capability errors
# ---------------------------------------------------------------------------

def test_device_decode_config_guards():
    with pytest.raises(ValueError, match="megabatch_k"):
        ClusterConfig(n=10, v_max=4, device_decode=True)
    with pytest.raises(ValueError, match="wavefront"):
        ClusterConfig(
            n=10, v_max=4, device_decode=True, megabatch_k=2,
            batch_edges=64, wavefront=8,
        )
    with pytest.raises(ValueError, match="refine"):
        ClusterConfig(
            n=10, v_max=4, device_decode=True, megabatch_k=2,
            batch_edges=64, refine="louvain",
        )


def test_backend_without_decode_fn_raises(tmp_path):
    cm = _one_cmega(tmp_path, seed=9)
    sc = StreamClusterer(
        ClusterConfig(
            n=400, v_max=16, backend="dense", batch_edges=512, megabatch_k=4
        )
    )
    with pytest.raises(ValueError, match="device decode"):
        sc.partial_fit_cmegabatch(cm)


def test_desc_cols_layout_shared_with_kernel():
    # the kernel and the pipeline must agree on the table layout
    from repro.graph.pipeline import DESC_COLS as PIPE_COLS

    assert DESC_COLS == PIPE_COLS
