"""Jitted wrapper for the edge_decide kernel: 1-D edge vectors are retiled to
(rows, 128) lanes, padded as no-ops, and cropped back."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.edge_decide.kernel import build_call

_LANES = 128


def _retile(x, rows):
    flat = jnp.zeros(rows * _LANES, x.dtype)
    flat = jax.lax.dynamic_update_slice(flat, x, (0,))
    return flat.reshape(rows, _LANES)


@functools.partial(
    jax.jit, static_argnames=("v_max", "block_rows", "interpret")
)
def edge_decide(
    vci: jax.Array,
    vcj: jax.Array,
    di: jax.Array,
    dj: jax.Array,
    live: jax.Array,
    v_max: int,
    block_rows: int = 8,
    interpret: Optional[bool] = None,
):
    """Decision stage over a batch of edges.  All inputs (B,) int32.

    Returns (action, amount), each (B,) int32.
    """
    b = vci.shape[0]
    rows = -(-b // (_LANES * block_rows)) * block_rows
    args = [
        _retile(x.astype(jnp.int32), rows)
        for x in (vci, vcj, di, dj, live.astype(jnp.int32))
    ]
    call = build_call(rows, block_rows, v_max, interpret_mode(interpret))
    action, amount = call(*args)
    return action.reshape(-1)[:b], amount.reshape(-1)[:b]
