"""Blocked causal/sliding-window GQA flash attention (Pallas, TPU target).

TPU adaptation of the FlashAttention tiling: the grid is
(batch, kv_head, q_block, kv_block) with the KV dimension INNERMOST and
declared "arbitrary" so the online-softmax state (m, l, acc) lives in VMEM
scratch across kv iterations of the same q block. Block shapes are
(BQ, head_dim) / (BK, head_dim) — head_dim is lane-aligned (128 for every
assigned arch) and BQ/BK default to MXU-friendly 128/256 tiles.

GQA: all G = H/KV query heads of one kv head are processed together as a
(BQ, G*D)-shaped q block — the kernel reshapes to (BQ, G, D), giving the
MXU a (BQ*G, BK) logits matmul per step, amortizing the K/V loads across
the query group exactly like the GQA-aware TPU kernels in production
serving stacks.

Masking: positions are explicit int32 vectors (supports the ring-buffer
decode cache where k positions are arbitrary): causal (k_pos <= q_pos),
validity (k_pos >= 0), sliding window (q_pos - k_pos < window).

Validated against ref.py in interpret mode (CPU) over shape/dtype sweeps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref,
               o_ref, m_scr, l_scr, acc_scr, *, window: int, soft_cap: float,
               g: int, d: int, nk: int):
    kv_i = pl.program_id(3)

    @pl.when(kv_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    bq = q_ref.shape[-2]
    bk = k_ref.shape[-2]
    q = q_ref[0, 0].astype(jnp.float32).reshape(bq, g, d) / math.sqrt(d)
    k = k_ref[0, 0].astype(jnp.float32)                      # (BK, D)
    s = jnp.einsum("qgd,kd->qgk", q, k)                      # (BQ, G, BK)
    if soft_cap:
        s = soft_cap * jnp.tanh(s / soft_cap)

    qp = qpos_ref[0]                                         # (BQ,)
    kp = kpos_ref[0]                                         # (BK,)
    ok = (kp[None, :] <= qp[:, None]) & (kp[None, :] >= 0)
    if window > 0:
        ok &= (qp[:, None] - kp[None, :]) < window
    s = jnp.where(ok[:, None, :], s, NEG_INF)

    m_prev = m_scr[...]                                      # (BQ, G)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    # fully-masked rows: keep exp(NEG_INF - NEG_INF)=1 from poisoning l
    p = jnp.where(ok[:, None, :], p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1)
    v = v_ref[0, 0].astype(jnp.float32)                      # (BK, D)
    acc_scr[...] = (acc_scr[...] * corr[..., None]
                    + jnp.einsum("qgk,kd->qgd", p, v))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kv_i == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[..., None]).reshape(bq, g * d
                                                            ).astype(o_ref.dtype)


def flash_attention_gqa(q, k, v, q_pos, k_pos, *, window: int = 0,
                        soft_cap: float = 0.0, block_q: int = 128,
                        block_k: int = 256, interpret: bool):
    """q: (B, Sq, H, D); k/v: (B, Sk, KV, D); q_pos: (B, Sq); k_pos: (B, Sk).
    Returns (B, Sq, H, D). Sq % block_q == 0 and Sk % block_k == 0 required
    (ops.py pads)."""
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = sq // block_q
    nk = sk // block_k

    # layout: (B, KV, Sq, G*D) so one block holds a whole query group
    qr = q.reshape(b, sq, kv, g * d).transpose(0, 2, 1, 3)
    kr = k.transpose(0, 2, 1, 3)                             # (B, KV, Sk, D)
    vr = v.transpose(0, 2, 1, 3)

    grid = (b, kv, nq, nk)
    kernel = functools.partial(_fa_kernel, window=window, soft_cap=soft_cap,
                               g=g, d=d, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, g * d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, block_q), lambda bi, hi, qi, ki: (bi, qi)),
            pl.BlockSpec((1, block_k), lambda bi, hi, qi, ki: (bi, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, g * d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, sq, g * d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, g), jnp.float32),      # m (running max)
            pltpu.VMEM((block_q, g), jnp.float32),      # l (running sum)
            pltpu.VMEM((block_q, g, d), jnp.float32),   # acc
        ],
        interpret=interpret,
    )(qr, kr, vr, q_pos, k_pos)
    return out.transpose(0, 2, 1, 3).reshape(b, sq, h, d)
