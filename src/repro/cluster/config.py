""":class:`ClusterConfig` — the single, validated knob surface of the API.

One config drives every backend (DESIGN.md §6).  Validation happens at
construction so a bad parameterization fails before any edges stream.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional, Tuple, Union


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    """Parameters of one clustering run.

    Args:
      n: number of nodes in the stream's id space (state is ``3n`` ints).
      v_max: the paper's volume threshold (required by every backend except
        ``multiparam``, which sweeps ``v_maxes`` instead).
      backend: registry key — one of ``repro.cluster.available_backends()``
        (``oracle`` / ``dense`` / ``scan`` / ``chunked`` / ``pallas`` /
        ``multiparam`` / ``distributed``).
      chunk: edges per device step for the ``chunked`` / ``pallas`` /
        ``distributed`` tiers (Jacobi batch size resp. DMA granularity).
      batch_edges: edges per ingest batch when streaming from an
        :class:`repro.graph.sources.EdgeSource` (host edge-buffer residency
        is O(batch_edges), the stream itself never materializes).  ``None``
        streams out-of-core sources at a default batch size and keeps
        in-memory arrays on the historical one-shot path; setting it forces
        batched ingestion even for arrays.  Every backend is resumable, so
        this applies uniformly — the sweep streams like any sequential
        tier, and the ``distributed`` tier deals batches onto shards (with
        ``batch_edges`` unset it defaults to one window per shard, capped
        at the default batch size).  Rounded up to a ``chunk`` multiple for
        the chunk-aligned tiers so batching never moves a Jacobi/DMA
        boundary.
      megabatch_k: stack this many consecutive ingest batches into one
        ``(K, batch_edges, 2)`` staging buffer and dispatch them to the
        device *fused* (one ``lax.scan``-over-chunks dispatch for
        ``chunked``, one double-buffered-DMA kernel launch for ``pallas``)
        — ~K-fold fewer dispatches/transfers, labels bit-identical to the
        per-batch path, checkpoint cursors still land on exact batch rows.
        ``None`` (default) keeps per-batch dispatch; set only for backends
        with a fused path (others ignore it).  Host staging memory grows to
        ``(prefetch + 1) * K * batch_edges`` rows — visible in the measured
        ``peak_buffer_bytes``.
      wavefront: wave width ``W`` for the conflict-free wavefront path of
        the ``pallas`` tier (DESIGN.md §12).  When set, the pipeline's
        prefetch thread plans each staged megabatch into contiguous waves
        of up to ``W`` node-disjoint edges and the device applies each wave
        vectorised (gathered loads / scattered stores), with a runtime
        community-collision check falling back to the sequential per-edge
        loop — labels stay bit-identical to every sequential tier.
        Requires ``megabatch_k`` (waves are planned per staged megabatch);
        backends without a wavefront path ignore it.  ``None`` (default)
        keeps the sequential megabatch kernel.  ``"auto"`` lets the planner
        pick ``W`` per megabatch from the observed node-disjoint run-length
        histogram (the width a fixed-``W`` sweep would have chosen for that
        megabatch's structure); the chosen widths surface as the
        ``wavefront_widths`` info counter.  Fixed integer widths plan
        bit-for-bit as before.
      prefetch: how many batches (or megabatches) the ingest pipeline
        produces ahead on its background thread (``None`` → 2, classic
        double buffering).  0 disables the prefetch thread entirely.
      v_maxes: multi-sweep thresholds for ``backend="multiparam"`` (paper
        §2.5: one pass, many parameters).
      criterion: edge-free sweep selector, ``"density"`` or ``"entropy"``.
      n_shards: stream shards for ``backend="distributed"`` (defaults to the
        visible device count — or the mesh's — at state-init time; pinned
        into the config then, since it is the leading axis of the
        :class:`~repro.core.state.ShardedState`).
      v_max2: merge-phase threshold for ``distributed`` (defaults to
        ``v_max``).  The merge clusters the cross-shard identity stream
        built from the per-shard states, so it only has effect when
        ``n_shards > 1`` — a single-shard run is exactly one chunked pass
        at ``v_max``.
      refine: post-stream refinement stage (``repro.cluster.refine``,
        DESIGN.md §11), dispatched at ``finalize()`` for every state kind:
        ``"louvain"`` or ``"labelprop"`` run weighted rounds on the
        contracted supergraph accumulated during the stream (plus
        modularity-scored community merge/split moves); a ``"+replay"``
        suffix (e.g. ``"louvain+replay"``) additionally re-plays the most
        recent ``K*batch_edges`` buffered edges through the refined labels
        — the split-capable stage — before they are discarded.  ``None``
        (default) keeps the raw streamed labels.  Requires a
        dense-label-space backend; runs with ``refine`` set always ingest
        through the streaming path so the sketch sees every batch.
      refine_rounds: refinement rounds on the supergraph (Louvain levels /
        label-propagation sweeps; ``None`` -> 10).
      refine_max_pairs: cap on inter-community sketch entries (``None`` ->
        2**20, a 16 MB ceiling at 16 B/entry).  Overflow evicts the
        lightest pairs into the sketch's ``dropped_weight`` counter —
        bounded memory, never silent truncation.
      wavefront_gap: dead-gap run-merging budget for the wavefront planner
        (DESIGN.md §12/§13).  When set, ``plan_waves`` packs only *live*
        rows into waves, merging contiguous live runs across interior dead
        gaps (PAD / self-loop rows) of up to this many rows — a gap longer
        than the budget closes the wave.  Dead rows are no-ops in every
        tier, so skipping them never reorders live work; occupancy rises on
        PAD-interleaved streams (ragged megabatch tails, fleet-style
        staging).  The plan's ``dead_rows_skipped`` counter surfaces as
        ``wavefront_dead_rows_skipped`` in the finalize info.  ``None``
        (default) keeps the historical plans: dead rows occupy wave slots.
        Requires ``wavefront``.
      device_decode: device-resident compressed ingest (DESIGN.md §14).
        When True and the source is a block-codec file
        (:class:`~repro.graph.sources.CodecFileSource` over a ``.dvc``),
        :meth:`StreamClusterer.fit` stages *compressed payload bytes* plus
        a descriptor table per megabatch instead of decoded edges, and the
        backend's ``decode_fn`` unpacks the DVE3 lanes on device — fused
        with the state update, one dispatch per megabatch, labels
        bit-identical to host decode.  Blocks the device cannot decode
        (varint/u8 fallback, mid-block resume remainders) are host-decoded
        and staged raw; the split surfaces as the ``device_decode_*`` info
        counters.  Requires ``megabatch_k`` and a backend with a
        ``decode_fn`` (``chunked`` / ``pallas``); sources without codec
        blocks (arrays, text files) fall back to host staging.
        Incompatible with ``wavefront`` and ``refine`` (both need
        host-visible decoded edges per megabatch).
      tenants: fleet size ``T`` for the multi-tenant fleet engine
        (``repro.cluster.fleet``, DESIGN.md §13) — the whole fleet's state
        is one ``(T, n)`` :class:`~repro.core.state.FleetState` advanced by
        a single donated dispatch per fleet step.  Only consumed by
        :class:`~repro.cluster.fleet.FleetClusterer` (single-stream entry
        points ignore it); requires a backend with a fleet path
        (``chunked`` / ``scan`` / ``pallas``).
      autosave_every: checkpoint the run from inside ``fit`` every this
        many ingested edges (rounded up to batch/megabatch boundaries —
        saves always land on exact resume cursors).  Requires
        ``autosave_dir``.  A killed run resumes from the newest valid
        generation via :meth:`StreamClusterer.restore` with labels
        bit-identical to an uninterrupted run.  ``None`` (default)
        disables autosave.
      autosave_dir: directory for autosave checkpoints (managed by
        :class:`repro.checkpoint.manager.CheckpointManager`: step-atomic
        swaps, per-leaf checksums, fallback to the previous generation on
        a torn newest one).
      on_corrupt: what a checksummed block-codec source does with a block
        that fails its checksum — ``"raise"`` (default, fail loudly) or
        ``"quarantine"`` (skip to the next sync marker, count the loss in
        the ``blocks_quarantined`` / ``edges_lost`` info counters, never
        silently wrong).  Quarantine needs the checksummed ``DVX``
        framing; plain sources ignore this knob.
      on_tenant_fault: fleet policy when one tenant's source dies
        mid-stream — ``"raise"`` (default) or ``"quarantine"`` (the dead
        tenant's remaining rows become PAD no-ops, surviving tenants
        stream on bit-identically; quarantined tenants surface in the
        fleet info).  Only consumed by
        :class:`~repro.cluster.fleet.FleetClusterer`.
      retries: max consecutive transient-read retries per fault in the
        ingest pipeline (``None`` -> 3; 0 disables retry).  Retries
        re-resume the source at the last delivered row, so a stream that
        survives its transients is bit-identical to a fault-free one;
        the attempt count surfaces as the ``ingest_retries`` info counter.
      stall_timeout: hard watchdog (seconds) on the ingest prefetch
        thread — a single produce exceeding it raises
        :class:`~repro.graph.errors.StallError` instead of hanging the
        run.  ``None`` (default) disables the hard watchdog (the
        heartbeat monitor still counts soft stragglers as
        ``ingest_stalls``).
    """

    n: int
    v_max: Optional[int] = None
    backend: str = "chunked"
    chunk: int = 1024
    batch_edges: Optional[int] = None
    megabatch_k: Optional[int] = None
    wavefront: Union[int, str, None] = None
    prefetch: Optional[int] = None
    v_maxes: Optional[Tuple[int, ...]] = None
    criterion: str = "density"
    n_shards: Optional[int] = None
    v_max2: Optional[int] = None
    refine: Optional[str] = None
    refine_rounds: Optional[int] = None
    refine_max_pairs: Optional[int] = None
    wavefront_gap: Optional[int] = None
    tenants: Optional[int] = None
    device_decode: bool = False
    autosave_every: Optional[int] = None
    autosave_dir: Optional[str] = None
    on_corrupt: str = "raise"
    on_tenant_fault: str = "raise"
    retries: Optional[int] = None
    stall_timeout: Optional[float] = None

    def __post_init__(self):
        from repro.cluster.registry import available_backends

        if self.backend not in available_backends():
            raise ValueError(
                f"unknown backend {self.backend!r}; registered backends: "
                f"{', '.join(available_backends())}"
            )
        if not isinstance(self.n, int) or self.n < 1:
            raise ValueError(f"n must be a positive int, got {self.n!r}")
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")
        if self.batch_edges is not None and self.batch_edges < 1:
            raise ValueError(
                f"batch_edges must be >= 1, got {self.batch_edges}"
            )
        if self.megabatch_k is not None and self.megabatch_k < 1:
            raise ValueError(
                f"megabatch_k must be >= 1, got {self.megabatch_k}"
            )
        if self.wavefront is not None:
            if isinstance(self.wavefront, str):
                if self.wavefront != "auto":
                    raise ValueError(
                        f"wavefront must be an int width or 'auto', got "
                        f"{self.wavefront!r}"
                    )
            elif self.wavefront < 1:
                raise ValueError(
                    f"wavefront must be >= 1, got {self.wavefront}"
                )
            if self.megabatch_k is None:
                raise ValueError(
                    "wavefront requires megabatch_k (waves are planned per "
                    "staged megabatch)"
                )
        if self.prefetch is not None and self.prefetch < 0:
            raise ValueError(
                f"prefetch must be >= 0, got {self.prefetch}"
            )
        if self.criterion not in ("density", "entropy"):
            raise ValueError(
                f"criterion must be 'density' or 'entropy', got "
                f"{self.criterion!r}"
            )
        if self.backend == "multiparam":
            if not self.v_maxes:
                raise ValueError("backend='multiparam' requires v_maxes")
            if any(int(v) < 1 for v in self.v_maxes):
                raise ValueError(f"v_maxes must be >= 1, got {self.v_maxes}")
            # normalise to a plain int tuple (hashable, JSON-friendly)
            object.__setattr__(self, "v_maxes", tuple(int(v) for v in self.v_maxes))
        else:
            if self.v_max is None or int(self.v_max) < 1:
                raise ValueError(
                    f"v_max must be >= 1 for backend={self.backend!r}, got "
                    f"{self.v_max!r}"
                )
        if self.n_shards is not None and self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")
        if self.v_max2 is not None and self.v_max2 < 1:
            raise ValueError(f"v_max2 must be >= 1, got {self.v_max2}")
        if self.refine is not None:
            from repro.cluster.refine import parse_refine

            parse_refine(self.refine)  # raises on a malformed spec
        if self.refine_rounds is not None and self.refine_rounds < 1:
            raise ValueError(
                f"refine_rounds must be >= 1, got {self.refine_rounds}"
            )
        if self.refine_max_pairs is not None and self.refine_max_pairs < 1:
            raise ValueError(
                f"refine_max_pairs must be >= 1, got {self.refine_max_pairs}"
            )
        if self.wavefront_gap is not None:
            if self.wavefront_gap < 0:
                raise ValueError(
                    f"wavefront_gap must be >= 0, got {self.wavefront_gap}"
                )
            if self.wavefront is None:
                raise ValueError(
                    "wavefront_gap requires wavefront (it is a planner knob)"
                )
        if self.tenants is not None and self.tenants < 1:
            raise ValueError(f"tenants must be >= 1, got {self.tenants}")
        if self.device_decode:
            if self.megabatch_k is None:
                raise ValueError(
                    "device_decode requires megabatch_k (compressed slabs "
                    "are staged per megabatch)"
                )
            if self.wavefront is not None:
                raise ValueError(
                    "device_decode is incompatible with wavefront (waves "
                    "are planned from host-decoded edges)"
                )
            if self.refine is not None:
                raise ValueError(
                    "device_decode is incompatible with refine (the "
                    "supergraph sketch observes host-decoded edges)"
                )
        if self.autosave_every is not None:
            if self.autosave_every < 1:
                raise ValueError(
                    f"autosave_every must be >= 1, got {self.autosave_every}"
                )
            if not self.autosave_dir:
                raise ValueError(
                    "autosave_every requires autosave_dir (where the "
                    "checkpoints go)"
                )
        if self.on_corrupt not in ("raise", "quarantine"):
            raise ValueError(
                f"on_corrupt must be 'raise' or 'quarantine', got "
                f"{self.on_corrupt!r}"
            )
        if self.on_tenant_fault not in ("raise", "quarantine"):
            raise ValueError(
                f"on_tenant_fault must be 'raise' or 'quarantine', got "
                f"{self.on_tenant_fault!r}"
            )
        if self.retries is not None and self.retries < 0:
            raise ValueError(f"retries must be >= 0, got {self.retries}")
        if self.stall_timeout is not None and self.stall_timeout <= 0:
            raise ValueError(
                f"stall_timeout must be > 0, got {self.stall_timeout}"
            )

    # ------------------------------------------------------------------
    def replace(self, **changes: Any) -> "ClusterConfig":
        return dataclasses.replace(self, **changes)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "ClusterConfig":
        raw = json.loads(text)
        # configs saved before Pallas interpret mode was derived from the
        # platform carry an ``interpret`` field; the platform decides now
        raw.pop("interpret", None)
        if raw.get("v_maxes") is not None:
            raw["v_maxes"] = tuple(raw["v_maxes"])
        return cls(**raw)
