"""Pallas TPU kernels for the framework's compute hot spots.

    tat_lookup       — the paper's hot loop: batched fully-associative
                       tag match against the PB's Tag Address Table
    flash_attention  — blockwise online-softmax attention (32k prefill)
    ssd_scan         — Mamba2 chunked state-space-dual scan

Each kernel ships as ``<name>.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jit wrapper with platform dispatch) and ``ref.py``
(pure-jnp oracle); tests sweep shapes/dtypes against the oracle with the
kernels in interpret mode on the CPU backend, and
``tests/test_tpu_compile.py`` compiles each one for a described TPU v5e.
"""
from repro.kernels.ops import flash_attention, ssd_scan, tat_lookup

__all__ = ["flash_attention", "ssd_scan", "tat_lookup"]
