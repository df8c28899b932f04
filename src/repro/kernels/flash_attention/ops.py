"""jit'd wrapper: pads sequences to block multiples, invokes the Pallas
flash-attention kernel, unpads. This is what models/attention.py routes to
with impl="pallas"."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention import kernel as K


@functools.partial(jax.jit,
                   static_argnames=("window", "soft_cap", "block_q",
                                    "block_k", "interpret"))
def flash_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                    soft_cap: float = 0.0, block_q: int = 128,
                    block_k: int = 256, interpret: bool = None):
    """Same contract as models.attention.sdpa: q (B,Sq,H,D), k/v (B,Sk,KV,D),
    positions (B,Sq)/(B,Sk) int32 (-1 = empty cache slot)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(8, sk))
    pq = (-sq) % bq
    pk = (-sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        # padded queries attend to nothing real; give them pos max so the
        # causal mask passes and l stays > 0 via validity mask handling
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pq)), constant_values=0)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pk)), constant_values=-1)
    out = K.flash_attention_gqa(q, k, v, q_pos, k_pos, window=window,
                                soft_cap=soft_cap, block_q=bq, block_k=bk,
                                interpret=resolve_interpret(interpret))
    return out[:, :sq]
