"""Pallas TPU kernel: tiled flash attention (causal / sliding-window, GQA).

TPU mapping (not a CUDA port — no warps/shared-memory banking here):

  * grid = (batch*heads, q_blocks, kv_blocks) with kv innermost, so the
    (bq, d) output tile and the (bq,) running softmax stats stay resident in
    VMEM scratch across the kv sweep — the online-softmax state never
    touches HBM;
  * q/k/v tiles stream HBM->VMEM via BlockSpec pipelining; (bq, bk) = (128,
    128) keeps the two matmuls per step on MXU-aligned shapes;
  * causal + sliding-window handled by skipping fully-masked kv blocks via
    ``pl.when`` (zero FLOPs spent there — the compiler pipeline still
    prefetches, matching TPU's preference for static grids) and masking the
    diagonal/window-edge blocks with iota comparisons;
  * GQA is resolved in the index maps: q-head g maps to kv-head
    g // group, no materialised ``jnp.repeat`` of K/V (saves Hq/Hkv x HBM
    traffic, the wrapper's whole point for 32k-token prefill).

Softmax statistics are kept in fp32 regardless of input dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import pallas_call

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, bq, d)
    k_ref,  # (1, bk, d)
    v_ref,  # (1, bk, d)
    o_ref,  # (1, bq, d)
    acc_ref,  # (bq, d) fp32 scratch
    m_ref,  # (bq, 128) fp32 scratch (max; lane-replicated)
    l_ref,  # (bq, 128) fp32 scratch (sum; lane-replicated)
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_k: int,
    seq_len: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # Static-shape block skip: with kv innermost we can't shrink the grid per
    # q block, but we can skip compute on fully-masked tiles.
    run = jnp.asarray(True)
    if causal:
        run = k_start <= q_start + block_q - 1  # some kv position visible
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _step():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        s = s * scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = cols < seq_len
        if causal:
            mask = mask & (cols <= rows)
        if window is not None:
            mask = mask & (cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]  # (bq,)
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        l = l_ref[:, 0]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> jax.Array:
    b, h, s, d = q.shape
    hkv = k.shape[1]
    assert h % hkv == 0, "GQA requires H % Hkv == 0"
    group = h // hkv
    if scale is None:
        scale = d**-0.5

    bq = min(block_q, s)
    bk = min(block_k, s)
    s_pad = pl.cdiv(s, max(bq, bk)) * max(bq, bk)
    if s_pad != s:
        pad = s_pad - s
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))

    qf = q.reshape(b * h, s_pad, d)
    kf = k.reshape(b * hkv, s_pad, d)
    vf = v.reshape(b * hkv, s_pad, d)

    def q_index(g, i, j):
        return (g, i, 0)

    def kv_index(g, i, j):
        # GQA: q-head g = bi * h + hi -> kv row bi * hkv + hi // group.
        bi = g // h
        hi = g % h
        return (bi * hkv + hi // group, j, 0)

    kernel = functools.partial(
        _flash_kernel,
        scale=float(scale),
        causal=causal,
        window=window,
        block_q=bq,
        block_k=bk,
        seq_len=s,
    )
    out = pallas_call(
        kernel,
        grid=(b * h, s_pad // bq, s_pad // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_index),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_index),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )(qf, kf, vf)
    return out.reshape(b, h, s_pad, d)[:, :, :s, :]
