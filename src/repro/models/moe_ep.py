"""Expert-parallel MoE FFN via shard_map — explicit all-to-all dispatch.

WHY (EXPERIMENTS.md §Perf, kimi-k2 x train_4k): under plain GSPMD the
capacity-slotted dispatch is a dynamic gather from the token-sharded
activations into the expert-sharded slot table. The partitioner cannot
prove locality of the gather indices, so it REPLICATES the dispatched
tensor (384 experts x 27k slots x 7168 = 150 GB per MoE layer) to every
device — 8.7 TB/device of all-gather per training step for kimi-k2.

This module is the classic DeepSpeed-MoE / MaxText pattern written with
shard_map + jax.lax collectives, the TPU-native translation of the GPU
NCCL all-to-all (DESIGN.md hardware-adaptation):

  * tokens are sharded over the `expert_axis` (= data); experts are
    sharded over the SAME axis: shard s owns experts [s*E/S, (s+1)*E/S)
  * each shard routes its local tokens, packs a fixed-capacity send
    buffer bucketed by destination shard, and exchanges it with ONE
    ppermute-free `lax.all_to_all`
  * expert FFN runs locally; the expert ffn dim is column-sharded over
    `model` with a psum for the down-projection (Megatron style)
  * a reverse all_to_all returns expert outputs; a local scatter-add
    combines them.

Collective volume per layer per device: 2 x (S x C_send x D) ~ the ideal
token movement, instead of the full dispatched tensor. Same math as
models/moe.py (validated equal in tests/test_moe_ep.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.layers import linear
from repro.models.moe import MIN_CAPACITY, _route


def _send_capacity(cfg, tokens_per_shard: int, num_shards: int) -> int:
    """Capacity of the (src shard -> dst shard) bucket."""
    c = int(math.ceil(tokens_per_shard * cfg.top_k / num_shards
                      * cfg.capacity_factor))
    c = max(c, MIN_CAPACITY)
    mult = 128 if c >= 128 else 8
    return ((c + mult - 1) // mult) * mult


def moe_forward_ep(cfg, p, x, *, mesh: Mesh, expert_axis=None,
                   model_axis: str = "model"):
    """x: (B, S, D) sharded batch-over-expert_axis. Returns (out, aux).

    Expert weight layout expected (sharding/rules.py `expert_axis` rules):
      gate/up: (E, D, F) with E over expert_axis, F over model_axis
      down:    (E, F, D) with E over expert_axis, F over model_axis
    """
    b, s, d = x.shape
    if expert_axis is None:
        # span every non-model axis (pod AND data on the multi-pod mesh)
        axes = tuple(a for a in mesh.axis_names if a != model_axis)
        expert_axis = axes if len(axes) > 1 else axes[0]
    ax_list = expert_axis if isinstance(expert_axis, tuple) else (expert_axis,)
    n_shards = int(np.prod([mesh.shape[a] for a in ax_list]))
    e_total = cfg.num_experts
    e_local = e_total // n_shards
    assert e_total % n_shards == 0, (e_total, n_shards)
    t_total = b * s
    assert t_total % n_shards == 0
    t_local = t_total // n_shards
    cap = _send_capacity(cfg, t_local, n_shards)

    def local_block(xt, router_w, gate_w, up_w, down_w):
        """Per-(expert_axis x model_axis) shard body.
        xt: (T_l, D) local tokens; gate_w: (E_l, D, F_l) local experts with
        the model-axis column slice of the ffn dim."""
        tl = xt.shape[0]
        logits = (xt.astype(jnp.float32) @ router_w)          # (T_l, E)
        gates, idx, aux = _route(cfg, logits[None])           # add group dim
        gates, idx = gates[0], idx[0]                         # (T_l, k)
        aux = jax.lax.pmean(aux, expert_axis)

        flat_e = idx.reshape(-1)                              # (T_l*k,)
        dest = flat_e // e_local                              # shard of expert
        # rank within destination bucket (sort-based, O(Tk log Tk))
        tk = flat_e.shape[0]
        order = jnp.argsort(dest, stable=True)
        counts = jnp.zeros((n_shards,), jnp.int32).at[dest].add(1)
        starts = jnp.cumsum(counts) - counts
        rank_sorted = jnp.arange(tk, dtype=jnp.int32) - starts[dest[order]]
        rank = jnp.zeros((tk,), jnp.int32).at[order].set(rank_sorted)
        keep = rank < cap
        slot = jnp.where(keep, dest * cap + rank, n_shards * cap)

        token_of = jnp.broadcast_to(
            jnp.arange(tl, dtype=jnp.int32)[:, None],
            (tl, cfg.top_k)).reshape(-1)
        # send buffers: tokens, (local expert id, gate) metadata
        send_tok = jnp.full((n_shards * cap + 1,), tl, jnp.int32
                            ).at[slot].set(token_of)[:-1]
        send_exp = jnp.zeros((n_shards * cap + 1,), jnp.int32
                             ).at[slot].set(flat_e % e_local)[:-1]
        send_gate = jnp.zeros((n_shards * cap + 1,), jnp.float32
                              ).at[slot].set(gates.reshape(-1))[:-1]
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], 0)
        send_x = xt_pad[send_tok]                             # (S*C, D)

        # exchange: (n_shards, C, D) -> recv (n_shards, C, D)
        recv_x = jax.lax.all_to_all(
            send_x.reshape(n_shards, cap, d), expert_axis, 0, 0, tiled=False)
        recv_exp = jax.lax.all_to_all(
            send_exp.reshape(n_shards, cap), expert_axis, 0, 0)
        recv_valid = jax.lax.all_to_all(
            (send_tok < tl).reshape(n_shards, cap), expert_axis, 0, 0)

        # local expert FFN: gather per-slot expert weights via one-hot-free
        # take (E_l is small per shard)
        rx = recv_x.reshape(n_shards * cap, d)
        rexp = recv_exp.reshape(-1)
        rvalid = recv_valid.reshape(-1)
        # (T_r, D) x (E_l, D, F_l): batched by expert id via segment matmul:
        # sort by expert, run dense per-expert matmul with fixed capacity
        per_e_cap = n_shards * cap // e_local if e_local else 0
        per_e_cap = max(per_e_cap, MIN_CAPACITY)
        mult = 128 if per_e_cap >= 128 else 8
        per_e_cap = ((per_e_cap + mult - 1) // mult) * mult
        # sort key sends INVALID slots to a sentinel segment (e_local) so
        # they never pollute a real expert's rank sequence
        key2 = jnp.where(rvalid, rexp, e_local)
        order2 = jnp.argsort(key2, stable=True)
        counts2 = jnp.zeros((e_local + 1,), jnp.int32).at[key2].add(1)
        starts2 = jnp.cumsum(counts2) - counts2
        rank2_sorted = (jnp.arange(rexp.shape[0], dtype=jnp.int32)
                        - starts2[key2[order2]])
        rank2 = jnp.zeros_like(rank2_sorted).at[order2].set(rank2_sorted)
        keep2 = (rank2 < per_e_cap) & rvalid
        slot2 = jnp.where(keep2, rexp * per_e_cap + rank2,
                          e_local * per_e_cap)
        tbl = jnp.full((e_local * per_e_cap + 1,), rexp.shape[0], jnp.int32
                       ).at[slot2].set(jnp.arange(rexp.shape[0],
                                                  dtype=jnp.int32))[:-1]
        rx_pad = jnp.concatenate([rx, jnp.zeros((1, d), rx.dtype)], 0)
        xe = rx_pad[tbl].reshape(e_local, per_e_cap, d)

        h = jnp.einsum("ecd,edf->ecf", xe, gate_w)
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", xe, up_w)
        ye = jnp.einsum("ecf,efd->ecd", h, down_w,
                        preferred_element_type=jnp.float32)
        # NOTE: ye is PARTIAL over the model axis (ffn dim is column-
        # sharded). The psum is DEFERRED to after the token combine —
        # psum'ing here costs E_l x C x D per layer (18.8 GB for kimi);
        # after combine it is T_l x D (1.9 GB): 10x less all-reduce
        # volume, and the reverse all-to-all carries bf16 partials.
        ye = ye.astype(xt.dtype)

        # un-sort back to recv slot order, reverse all_to_all
        ye_flat = ye.reshape(e_local * per_e_cap, d)
        ye_pad = jnp.concatenate([ye_flat, jnp.zeros((1, d), ye_flat.dtype)],
                                 0)
        back = ye_pad[jnp.where(keep2, slot2, e_local * per_e_cap)]
        back = jnp.where(keep2[:, None], back, 0.0)
        ret = jax.lax.all_to_all(
            back.reshape(n_shards, cap, d), expert_axis, 0, 0)

        # combine locally with gates (f32 accumulate), THEN one token-level
        # psum over model completes the row-parallel down projection
        ret_flat = ret.reshape(n_shards * cap, d).astype(jnp.float32)
        weighted = ret_flat * send_gate[:, None]
        out = jnp.zeros((tl + 1, d), jnp.float32
                        ).at[send_tok].add(weighted)[:-1]
        out = jax.lax.psum(out.astype(xt.dtype), model_axis)
        return out, aux

    xt = x.reshape(t_total, d)
    spec_tok = P(expert_axis, None)
    out, aux = jax.shard_map(
        local_block, mesh=mesh,
        in_specs=(spec_tok, P(), P(expert_axis, None, model_axis),
                  P(expert_axis, None, model_axis),
                  P(expert_axis, model_axis, None)),
        out_specs=(spec_tok, P()),
        check_vma=False,
    )(xt, p["router"]["w"], p["gate"], p["up"], p["down"])
    out = out.reshape(b, s, d)

    if cfg.num_shared_experts:
        sh = p["shared"]
        xs = x.reshape(t_total, d)
        hs = jax.nn.silu(linear(sh["gate"], xs)) * linear(sh["up"], xs)
        out = out + linear(sh["down"], hs).reshape(b, s, d)
    return out, cfg.router_aux_coef * aux
