"""jit'd public wrappers with platform dispatch.

On TPU the Pallas kernels compile natively (``interpret=False``); on the
CPU backend they run in interpret mode, where the kernel body executes
in Python — the same semantics, used by the allclose tests against the
``ref`` oracles.  A shape that does not divide the kernel's block raises:
these wrappers never substitute the reference for the kernel.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.tat_lookup import tat_lookup_pallas


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _check_divides(what: str, size: int, block: int) -> None:
    if size % block:
        raise ValueError(f"{what} {size} is not a multiple of the kernel "
                         f"block {block}")


def tat_lookup(req_tags: jnp.ndarray, tat: jnp.ndarray,
               states: jnp.ndarray, *, block_r: int = 256
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    r = req_tags.shape[0]
    block_r = min(block_r, r)
    _check_divides("request count", r, block_r)
    return tat_lookup_pallas(req_tags, tat, states, block_r=block_r,
                             interpret=_interpret())


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    s = q.shape[2]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    _check_divides("sequence length", s, block_q)
    _check_divides("sequence length", s, block_k)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 128
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    s = x.shape[1]
    chunk = min(chunk, s)
    _check_divides("sequence length", s, chunk)
    return ssd_scan_pallas(x, dt, A, B, C, chunk=chunk,
                           interpret=_interpret())
