"""Pallas TPU kernel: blocked Matérn-5/2 ARD kernel-matrix assembly.

The GP surrogate's hot spot is the O(n m d) pairwise-distance + elementwise
transform.  TPU mapping (DESIGN.md: rethink for VMEM/MXU, don't port CUDA):

  * the distance matrix block is computed as  |a|^2 + |b|^2 - 2 a b^T, so the
    dominant cost is one (bn, d) x (d, bm) matmul per tile — MXU work, with
    bn = bm = 128 matching the systolic array;
  * each grid cell (i, j) holds one (128, 128) fp32 output tile in VMEM plus
    the two input panels — ~3 * 64 KiB for d = 128, far under the ~16 MiB
    VMEM budget, leaving headroom for double buffering;
  * the elementwise Matérn transform fuses into the same tile while it is
    VMEM-resident (one HBM round trip per tile total).

Inputs are pre-scaled by the ARD lengthscales in ``ops.py`` (keeps the kernel
a pure geometry primitive), and padded so n, m are multiples of the block and
d a multiple of 8 (fp32 sublane width).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call

SQRT5 = math.sqrt(5.0)

DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_M = 128


def _matern52_kernel(s_ref, a_ref, b_ref, o_ref):
    outputscale = s_ref[0, 0]
    a = a_ref[...]  # (bn, d) VMEM tile
    b = b_ref[...]  # (bm, d) VMEM tile
    # MXU: one matmul per tile; fp32 accumulation.  HIGHEST, because the
    # default single bf16 pass loses the distance to the cancellation below.
    ab = jax.lax.dot_general(
        a,
        b,
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    d2 = (
        jnp.sum(a * a, axis=-1)[:, None]
        + jnp.sum(b * b, axis=-1)[None, :]
        - 2.0 * ab
    )
    d2 = jnp.maximum(d2, 0.0)
    safe = jnp.where(d2 > 1e-24, d2, 1.0)
    r = jnp.where(d2 > 1e-24, jnp.sqrt(safe), 0.0)
    s = SQRT5 * r
    o_ref[...] = (outputscale * (1.0 + s + s * s / 3.0) * jnp.exp(-s)).astype(
        o_ref.dtype
    )


def matern52_pallas(
    a: jax.Array,
    b: jax.Array,
    outputscale: float,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    block_m: int = DEFAULT_BLOCK_M,
) -> jax.Array:
    """k(a, b) for pre-scaled a: (n, d), b: (m, d) -> (n, m)."""
    n, d = a.shape
    m, _ = b.shape
    bn = min(block_n, max(8, 1 << (n - 1).bit_length()))
    bm = min(block_m, max(8, 1 << (m - 1).bit_length()))
    n_pad = pl.cdiv(n, bn) * bn
    m_pad = pl.cdiv(m, bm) * bm
    d_pad = max(8, pl.cdiv(d, 8) * 8)
    a_p = jnp.zeros((n_pad, d_pad), a.dtype).at[:n, :d].set(a)
    b_p = jnp.zeros((m_pad, d_pad), b.dtype).at[:m, :d].set(b)
    s = jnp.asarray(outputscale, a.dtype).reshape(1, 1)

    out = pallas_call(
        _matern52_kernel,
        grid=(n_pad // bn, m_pad // bm),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((bn, d_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, d_pad), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, m_pad), a.dtype),
    )(s, a_p, b_p)
    return out[:n, :m]
