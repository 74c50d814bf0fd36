"""Pallas kernel: batched fully-associative TAT lookup.

The PB's hot loop (PBCS tag check, Section V-C) as a TPU kernel: a block
of request tags is compared against the whole Tag Address Table resident
in VMEM.  The engine does not call it; the tests check it against
``ref.tat_lookup_ref``.

Tiling: requests are tiled in ``(1, block_r)`` rows, one request per
lane; the TAT (tags + states) is small (16-1024 entries), held as an
``(n, 1)`` column fully VMEM-resident and broadcast to every program, so
the match reduction runs down the sublanes.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(req_ref, tat_ref, st_ref, idx_ref, out_st_ref):
    req = req_ref[...]                       # (1, block_r)
    tat = tat_ref[...]                       # (n, 1)
    st = st_ref[...]                         # (n, 1)
    # requests on the lanes, table entries on the sublanes
    match = (tat == req) & (st != 0)         # (n, block_r)
    # first match wins, like a priority encoder: the least matching
    # row, or n when nothing matches
    row = jax.lax.broadcasted_iota(jnp.int32, match.shape, 0)
    n = tat.shape[0]
    idx = jnp.min(jnp.where(match, row, n), axis=0, keepdims=True)
    idx_ref[...] = jnp.where(idx < n, idx, -1)
    out_st_ref[...] = jnp.sum(jnp.where(row == idx, st, 0), axis=0,
                              keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def tat_lookup_pallas(req_tags: jnp.ndarray, tat: jnp.ndarray,
                      states: jnp.ndarray, *, block_r: int = 256,
                      interpret: bool = True
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    r = req_tags.shape[0]
    n = tat.shape[0]
    assert r % block_r == 0, (r, block_r)
    grid = (r // block_r,)
    idx, st = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_r), lambda i: (0, i)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_r), lambda i: (0, i)),
            pl.BlockSpec((1, block_r), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, r), jnp.int32),
            jax.ShapeDtypeStruct((1, r), jnp.int32),
        ],
        interpret=interpret,
    )(req_tags.reshape(1, r), tat.reshape(n, 1), states.reshape(n, 1))
    return idx.reshape(r), st.reshape(r)
