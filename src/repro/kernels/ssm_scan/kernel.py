"""Mamba-1 selective-scan Pallas kernel (TPU target).

Recurrence per channel c, state n:
    h_t[c,n] = exp(dt_t[c] * A[c,n]) * h_{t-1}[c,n] + dt_t[c] * B_t[n] * u_t[c]
    y_t[c]   = sum_n C_t[n] * h_t[c,n] + D[c] * u_t[c]

TPU adaptation (DESIGN.md §2): the GPU Mamba kernel is a warp-level scan
over time held in registers/shared memory; the TPU analogue keeps the
running state h (BLK_D x N) resident in VMEM SCRATCH across sequential
time-chunk grid steps, processing TS timesteps per grid step with a
fori_loop. Grid = (batch, d_blocks, time_chunks) with time INNERMOST
(sequential, "arbitrary" semantics); channel blocks BLK_D are
lane-aligned (128). State dim N (=16) stays in the sublane dimension.

This trades the associative-scan's O(S log S) elementwise work (the pure
jnp lowering in models/ssm.py) for a single O(S) pass with zero HBM
traffic for h — the structural win on TPU where the scan state would
otherwise round-trip to HBM between layers of the log-tree.

Validated against ref.py in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLK_D = 128          # channel block (lanes)
TS = 64              # timesteps per grid step


def _ssm_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, dskip_ref,
                y_ref, hout_ref, h_scr, *, nt: int, ts: int):
    t_i = pl.program_id(2)

    @pl.when(t_i == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)            # (BLK_D, N)
    dskip = dskip_ref[...].astype(jnp.float32)    # (BLK_D,)

    def step(i, h):
        u_t = u_ref[0, i, :].astype(jnp.float32)          # (BLK_D,)
        dt_t = dt_ref[0, i, :].astype(jnp.float32)        # (BLK_D,)
        b_t = b_ref[0, i, :].astype(jnp.float32)          # (N,)
        c_t = c_ref[0, i, :].astype(jnp.float32)          # (N,)
        decay = jnp.exp(dt_t[:, None] * a)                # (BLK_D, N)
        h = h * decay + (dt_t * u_t)[:, None] * b_t[None, :]
        y = (h * c_t[None, :]).sum(axis=1) + dskip * u_t  # (BLK_D,)
        y_ref[0, i, :] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, ts, step, h_scr[...])
    h_scr[...] = h

    @pl.when(t_i == nt - 1)
    def _finish():
        hout_ref[0] = h_scr[...]


def ssm_scan(u, dt, b, c, a, d_skip, *, ts: int = TS, blk_d: int = BLK_D,
             interpret: bool):
    """u/dt: (B, S, D_in) — u post-conv/silu, dt post-softplus (f32).
    b/c: (B, S, N) f32. a: (D_in, N) f32 (A = a, already negative).
    d_skip: (D_in,) f32. Returns (y (B, S, D_in), h_final (B, D_in, N) f32).

    S % ts == 0 and D_in % blk_d == 0 required (ops.py pads).
    """
    bsz, s, d_in = u.shape
    n = b.shape[-1]
    ts = min(ts, s)
    blk_d = min(blk_d, d_in)
    nt = s // ts
    nd = d_in // blk_d
    grid = (bsz, nd, nt)

    kernel = functools.partial(_ssm_kernel, nt=nt, ts=ts)
    y, h_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ts, blk_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, ts, blk_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, ts, n), lambda bi, di, ti: (bi, ti, 0)),
            pl.BlockSpec((1, ts, n), lambda bi, di, ti: (bi, ti, 0)),
            pl.BlockSpec((blk_d, n), lambda bi, di, ti: (di, 0)),
            pl.BlockSpec((blk_d,), lambda bi, di, ti: (di,)),
        ],
        out_specs=[
            pl.BlockSpec((1, ts, blk_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, blk_d, n), lambda bi, di, ti: (bi, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct((bsz, d_in, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((blk_d, n), jnp.float32)],
        interpret=interpret,
    )(u, dt, b, c, a, d_skip)
    return y, h_fin
