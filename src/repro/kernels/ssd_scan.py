"""Pallas kernel: Mamba2 SSD chunked scan (dual form).

Grid: (batch, heads, chunks) with the chunk axis innermost/sequential;
the (P, N) recurrent state is VMEM scratch carried across chunks — the
inter-chunk recurrence costs one (P,N) elementwise update per chunk
while all heavy work (the Q x Q dual-attention contraction and the
Q x N / Q x P matmuls) runs on the MXU.

Layout: the wrapper reshapes to chunk-major
    x  (B, H, NC, Q, P)    dt (B, H, NC, Q)
    Bm (B, NC, Q, N)       Cm (B, NC, Q, N)
so every BlockSpec slice is contiguous.  Q=N=128 aligns the lane dim;
P=64 is the Mamba2 head dim (half-lane, still legal).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fin_ref, state_scr, *,
            n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)     # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)   # (Q, 1)
    a = a_ref[pl.program_id(1)]                # () this head's decay rate
    bm = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)       # (Q, N)
    q = x.shape[0]

    # the TPU lowering has no cumsum and no vector transpose: prefix
    # sums and the column -> row moves are masked (Q, Q) reductions
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = ii >= jj
    da = dt * a                                # (Q, 1) log-decay per step
    da_row = jnp.sum(jnp.where(ii == jj, da, 0.0), axis=0, keepdims=True)
    dt_row = jnp.sum(jnp.where(ii == jj, dt, 0.0), axis=0, keepdims=True)
    da_cum = jnp.sum(jnp.where(tri, da_row, 0.0), axis=1,
                     keepdims=True)            # (Q, 1)
    da_cum_row = jnp.sum(jnp.where(tri, 0.0, da), axis=0,
                         keepdims=True) + da_row   # (1, Q)
    total = jnp.sum(da_row, axis=1, keepdims=True)  # (1, 1)

    # intra-chunk dual form: L[i,j] = exp(sum_{j<k<=i} da_k), lower-tri
    L = jnp.where(tri, jnp.exp(da_cum - da_cum_row), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))  # (Q, Q)
    y = ((scores * L) * dt_row) @ x             # (Q, P)

    # carried-state contribution + state update
    state = state_scr[...]                      # (P, N)
    y += jnp.exp(da_cum) * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())))    # (Q, P)
    decay_to_end = jnp.exp(total - da_cum)      # (Q, 1)
    state_new = state * jnp.exp(total) + jax.lax.dot_general(
        x * (dt * decay_to_end), bm, (((0,), (0,)), ((), ())))  # (P, N)
    state_scr[...] = state_new

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _fin():
        fin_ref[0, 0] = state_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                    B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 128,
                    interpret: bool = True
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Same contract as ``repro.models.ssm.ssd_chunked``.

    x: (B, S, H, P)  dt: (B, S, H)  A: (H,)  B/C: (B, S, N)
    Returns (y: (B, S, H, P), final_state: (B, H, P, N)).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    xr = x.transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, p)
    dtr = dt.transpose(0, 2, 1).reshape(b, h, nc, chunk, 1)
    br = B.reshape(b, nc, chunk, n)
    cr = C.reshape(b, nc, chunk, n)
    a32 = A.astype(jnp.float32)

    kern = functools.partial(_kernel, n_chunks=nc)
    y, fin = pl.pallas_call(
        kern,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            # the (H,) decay rates sit whole in scalar memory
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, n), lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda ib, ih, ic: (ib, ic, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, chunk, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xr, dtr, a32, br, cr)
    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    return y, fin
