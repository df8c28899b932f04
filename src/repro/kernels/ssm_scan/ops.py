"""jit'd wrapper for the Mamba selective-scan kernel: pads S to the time
chunk and D_in to the channel block, runs the kernel, unpads."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.ssm_scan import kernel as K


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_scan(u, dt, b, c, a, d_skip, interpret: bool = None):
    bsz, s, d_in = u.shape
    ts = min(K.TS, max(8, s))
    blk = min(K.BLK_D, d_in)
    ps = (-s) % ts
    pd = (-d_in) % blk
    if ps or pd:
        u = jnp.pad(u, ((0, 0), (0, ps), (0, pd)))
        dt = jnp.pad(dt, ((0, 0), (0, ps), (0, pd)))
        b = jnp.pad(b, ((0, 0), (0, ps), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, ps), (0, 0)))
    if pd:
        a = jnp.pad(a, ((0, pd), (0, 0)))
        d_skip = jnp.pad(d_skip, (0, pd))
    y, h = K.ssm_scan(u, dt, b, c, a, d_skip, ts=ts, blk_d=blk,
                      interpret=resolve_interpret(interpret))
    return y[:, :s, :d_in], h[:, :d_in, :]
