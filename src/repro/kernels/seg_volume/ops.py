"""Jitted wrapper for the seg_volume kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.seg_volume.kernel import build_call


@functools.partial(
    jax.jit, static_argnames=("k", "block_b", "block_k", "interpret")
)
def seg_volume(
    labels: jax.Array,
    weights: jax.Array,
    k: int,
    block_b: int = 512,
    block_k: int = 256,
    interpret: Optional[bool] = None,
):
    """Weighted histogram of ``labels`` (B,) into ``k`` bins via MXU matmul.

    Out-of-range labels (e.g. PAD sinks) must be pre-masked to weight 0 and
    label 0 by the caller.
    """
    b = labels.shape[0]
    bp = -(-b // block_b) * block_b
    kp = -(-k // block_k) * block_k
    lab = jnp.zeros((1, bp), jnp.int32).at[0, :b].set(labels.astype(jnp.int32))
    wts = jnp.zeros((1, bp), jnp.float32).at[0, :b].set(
        weights.astype(jnp.float32)
    )
    call = build_call(bp, kp, block_b, block_k, interpret_mode(interpret))
    out = call(lab, wts)
    return out[0, :k]
