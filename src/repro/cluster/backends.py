"""The seven built-in backends behind ``repro.cluster.cluster``.

Thin adapters from the registry's uniform contract onto the state-threading
tiers in ``repro.core`` / ``repro.kernels`` (DESIGN.md §3):

=========== ============================== ========== ========= =========
name        implementation                 state kind resumable bit-exact
=========== ============================== ========== ========= =========
oracle      dict Algorithm 1 (paper space) cluster    yes       yes
dense       numpy loop, node-id space      cluster    yes       yes
scan        jax.lax.scan, 1 edge/step      cluster    yes       yes
chunked     Jacobi chunks on the VPU       cluster    yes (†)   no
pallas      serial-in-VMEM Pallas kernel   cluster    yes       yes
multiparam  one-pass multi-v_max sweep     sweep      yes       yes (‡)
distributed sharded local + merge pass     sharded    yes       no
=========== ============================== ========== ========= =========

Every tier is resumable: *resumable + out-of-core is the invariant, not the
special case* — each backend's ``fn`` is pure state threading over one edge
batch, and the two wide-state tiers derive labels at finalize time via
``finalize_fn`` (selection for the sweep, the contracted merge for the
sharded tier).

† chunked partial_fit is deterministic but batch boundaries are Jacobi chunk
  boundaries, so labels depend on how the stream was batched.
‡ per sweep entry; the selected entry equals a scan run at that v_max.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import multiparam as _multiparam
from repro.core.chunked import chunked_update, chunked_update_megabatch
from repro.core.decode import chunked_decode_update_megabatch
from repro.core.distributed import merge_sharded_state, sharded_update
from repro.core.fleet import fleet_update_chunked, fleet_update_scan
from repro.core.state import ClusterState, ShardedState, SweepState
from repro.core.streaming import dense_update, oracle_init, oracle_update, scan_update
from repro.cluster.registry import BackendResult, register_backend
from repro.core.wavefront import wavefront_update_megabatch
from repro.kernels import interpret_mode
from repro.kernels.edge_stream.ops import (
    pallas_decode_update_megabatch,
    pallas_fleet_update,
    pallas_update,
    pallas_update_megabatch,
    pallas_wavefront_update,
)


# ---------------------------------------------------------------------------
# Sequential tiers (bit-exact with the paper's Algorithm 1)
# ---------------------------------------------------------------------------

@register_backend(
    "oracle",
    init_fn=lambda config: oracle_init(config.n),
    resumable=True,
    bit_exact=True,
    label_space="oracle",
    description="paper-faithful dictionary Algorithm 1 (pure Python)",
)
def _oracle(edges, config, state, mesh=None) -> BackendResult:
    state = oracle_update(state, np.asarray(edges), int(config.v_max))
    c = np.asarray(state.c)
    # Unseen nodes (label 0) become their own singletons, mirroring the dense
    # layout where an untouched node keeps its own id.
    labels = np.where(c > 0, c, config.n + 1 + np.arange(config.n))
    return BackendResult(state=state, labels=labels, info={})


@register_backend(
    "dense",
    resumable=True,
    bit_exact=True,
    description="dense-array Algorithm 1 (numpy loop, node-id label space)",
)
def _dense(edges, config, state, mesh=None) -> BackendResult:
    state = dense_update(state, np.asarray(edges), int(config.v_max))
    return BackendResult(state=state, labels=state.c, info={})


def _scan_fleet(edges, config, state) -> BackendResult:
    """Vmapped fleet ingest of one (T, B, 2) slab: per-tenant rows bit-exact
    with single-stream :func:`scan_update` over each tenant's own slabs."""
    state = fleet_update_scan(
        state.to_device(), jnp.asarray(edges), jnp.int32(config.v_max)
    )
    return BackendResult(state=state, labels=None, info={})


@register_backend(
    "scan",
    resumable=True,
    bit_exact=True,
    fleet_fn=_scan_fleet,
    description="jax.lax.scan port, one edge per step (on-device oracle)",
)
def _scan(edges, config, state, mesh=None) -> BackendResult:
    state = scan_update(
        state.to_device(), jnp.asarray(edges), jnp.int32(config.v_max)
    )
    return BackendResult(state=state, labels=state.c, info={})


def _pallas_megabatch(edges, config, state) -> BackendResult:
    """Fused (K, B, 2) ingest: one double-buffered-DMA kernel launch for the
    whole megabatch, state VMEM-resident throughout (bit-exact)."""
    state = pallas_update_megabatch(
        state.to_device(),
        jnp.asarray(edges),
        int(config.v_max),
        chunk=config.chunk,
    )
    return BackendResult(state=state, labels=state.c, info={})


def _pallas_wavefront(plan, config, state) -> BackendResult:
    """Wavefront ingest of one planned megabatch (DESIGN.md §12): vectorised
    node-disjoint waves with a runtime community-collision fallback, labels
    bit-identical to :func:`_pallas_megabatch` over the same stream.

    Off a TPU the Pallas kernel would trace every wave through the
    interpreter, so the pure-JAX reference apply runs instead — same wave
    math (``repro.core.wavefront``); the kernel itself is pinned against
    the reference by the wavefront test suite.  On a TPU the kernel is
    compiled, and Mosaic refuses it (``kernels/edge_stream/kernel.py``):
    this configuration raises the compiler's error there."""
    if interpret_mode():
        state, stats = wavefront_update_megabatch(
            state.to_device(),
            jnp.asarray(plan.waves),
            jnp.asarray(plan.leftover),
            jnp.asarray(plan.meta),
            int(config.v_max),
        )
    else:
        state, stats = pallas_wavefront_update(
            state.to_device(),
            jnp.asarray(plan.waves),
            jnp.asarray(plan.leftover),
            jnp.asarray(plan.meta),
            int(config.v_max),
            chunk=config.chunk,
        )
    return BackendResult(
        state=state, labels=state.c, info={"wavefront_stats": stats}
    )


def _pallas_decode(cmega, config, state) -> BackendResult:
    """Device-resident compressed ingest (DESIGN.md §14): one fused
    decode→update dispatch per :class:`~repro.graph.pipeline
    .CompressedMegaBatch` (``kernel.edge_stream_decode_update_kernel``
    decodes each row from the payload bytes straight into the per-edge
    update).  Labels bit-identical to host-decoding the same rows through
    :func:`_pallas_megabatch`."""
    state = pallas_decode_update_megabatch(
        state.to_device(),
        jnp.asarray(cmega.payload),
        jnp.asarray(cmega.desc),
        int(config.v_max),
    )
    return BackendResult(state=state, labels=state.c, info={})


def _pallas_fleet(edges, config, state) -> BackendResult:
    """Tenant-major fleet kernel: one launch ingests the whole (T, B, 2)
    slab, each tenant's state DMA'd HBM→VMEM→HBM (DESIGN.md §13);
    every tenant row bit-exact with the sequential single-stream tiers."""
    state = pallas_fleet_update(
        state.to_device(),
        jnp.asarray(edges),
        int(config.v_max),
        chunk=config.chunk,
    )
    return BackendResult(state=state, labels=None, info={})


@register_backend(
    "pallas",
    resumable=True,
    bit_exact=True,
    chunk_aligned=True,
    megabatch_fn=_pallas_megabatch,
    wavefront_fn=_pallas_wavefront,
    decode_fn=_pallas_decode,
    fleet_fn=_pallas_fleet,
    description="serial-in-VMEM Pallas kernel (bit-exact, TPU-native)",
)
def _pallas(edges, config, state, mesh=None) -> BackendResult:
    state = pallas_update(
        state.to_device(),
        jnp.asarray(edges),
        int(config.v_max),
        chunk=config.chunk,
    )
    return BackendResult(state=state, labels=state.c, info={})


# ---------------------------------------------------------------------------
# Parallel tiers (quality parity measured, not assumed)
# ---------------------------------------------------------------------------

def _chunked_megabatch(edges, config, state) -> BackendResult:
    """Fused (K, B, 2) ingest: one ``lax.scan`` over all K * B / chunk Jacobi
    chunks per dispatch.  Bit-identical to K sequential per-batch calls when
    B is a chunk multiple — which the pipeline guarantees for this
    chunk-aligned backend."""
    state = chunked_update_megabatch(
        state.to_device(),
        jnp.asarray(edges),
        jnp.int32(config.v_max),
        chunk=config.chunk,
    )
    return BackendResult(state=state, labels=state.c, info={})


def _chunked_decode(cmega, config, state) -> BackendResult:
    """Compressed ingest for the Jacobi tier: reference decode + the fused
    chunk scan under one jit (``repro.core.decode``) — one dispatch per
    megabatch, bit-identical to host-decoding the same rows through
    :func:`_chunked_megabatch` (the decoded slab is *defined* to equal the
    host-staged one, and B is a chunk multiple for this chunk-aligned
    backend, so chunk grouping is unchanged)."""
    state = chunked_decode_update_megabatch(
        state.to_device(),
        jnp.asarray(cmega.payload),
        jnp.asarray(cmega.desc),
        int(config.v_max),
        cmega.window,
        cmega.out_rows,
        chunk=config.chunk,
    )
    return BackendResult(state=state, labels=state.c, info={})


def _chunked_fleet(edges, config, state) -> BackendResult:
    """Vmapped fleet ingest of one (T, B, 2) slab: the Jacobi chunk scan
    batched over the tenant axis — per-tenant rows bit-identical to
    single-stream :func:`chunked_update` over each tenant's own slabs
    (chunk grouping restarts per slab, exactly as it restarts per batch)."""
    state = fleet_update_chunked(
        state.to_device(),
        jnp.asarray(edges),
        jnp.int32(config.v_max),
        chunk=config.chunk,
    )
    return BackendResult(state=state, labels=None, info={})


@register_backend(
    "chunked",
    resumable=True,
    bit_exact=False,
    chunk_aligned=True,
    megabatch_fn=_chunked_megabatch,
    decode_fn=_chunked_decode,
    fleet_fn=_chunked_fleet,
    description="Jacobi chunked tier (vectorised decisions, scatter conflict "
    "resolution)",
)
def _chunked(edges, config, state, mesh=None) -> BackendResult:
    state = chunked_update(
        state.to_device(),
        jnp.asarray(edges),
        jnp.int32(config.v_max),
        chunk=config.chunk,
    )
    return BackendResult(state=state, labels=state.c, info={})


def _multiparam_finalize(state: SweepState, config) -> BackendResult:
    """Edge-free selection over the sweep columns; the result's state is the
    selected column as a plain ClusterState (shared ``d``)."""
    sel = _multiparam.select_result(state, criterion=config.criterion)
    best = sel["best_index"]
    selected = state.entry(best)
    info = {
        "best_index": best,
        "best_v_max": sel["best_v_max"],
        "rows": sel["rows"],
        # host snapshot: multiparam_update donates the sweep state, so the
        # live (A, n) array would be consumed by the next partial_fit
        "sweep_labels": np.asarray(state.c),
    }
    return BackendResult(state=selected, labels=selected.c, info=info)


@register_backend(
    "multiparam",
    init_fn=lambda config: SweepState.init(config.n, config.v_maxes),
    resumable=True,
    bit_exact=True,
    state_kind="sweep",
    finalize_fn=_multiparam_finalize,
    description="one-pass multi-v_max sweep + edge-free selection (paper "
    "§2.5), state-threaded",
)
def _multiparam_backend(edges, config, state, mesh=None) -> BackendResult:
    state = _multiparam.multiparam_update(state.to_device(), jnp.asarray(edges))
    return BackendResult(state=state, labels=None, info={})


def _resolved_shards(config) -> int:
    # n_shards is the leading state axis; every API path pins it into the
    # config (api._resolve_config) before init_fn runs.  One resolver only.
    if config.n_shards is None:
        raise ValueError(
            "distributed init_fn needs config.n_shards pinned; go through "
            "repro.cluster.cluster / StreamClusterer, or set it explicitly"
        )
    return int(config.n_shards)


def _distributed_finalize(state: ShardedState, config) -> BackendResult:
    v_max2 = config.v_max2 if config.v_max2 is not None else config.v_max
    labels, merged = merge_sharded_state(
        state, int(v_max2), chunk=config.chunk
    )
    return BackendResult(
        state=merged, labels=labels, info={"n_shards": state.n_shards}
    )


@register_backend(
    "distributed",
    init_fn=lambda config: ShardedState.init(config.n, _resolved_shards(config)),
    resumable=True,
    bit_exact=False,
    state_kind="sharded",
    # NOT chunk_aligned: batches are this tier's unit of shard assignment, so
    # rounding batch_edges up to a chunk multiple would merge windows and
    # starve trailing shards (the chunked tier pads each batch internally).
    finalize_fn=_distributed_finalize,
    description="sharded local passes + contracted merge from per-shard "
    "states (batch-dealt, out-of-core)",
)
def _distributed(edges, config, state, mesh=None) -> BackendResult:
    state = sharded_update(
        state.to_device(),
        jnp.asarray(edges),
        jnp.int32(config.v_max),
        chunk=config.chunk,
    )
    return BackendResult(state=state, labels=None, info={})
