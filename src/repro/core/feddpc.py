"""FedDPC server optimizer (paper Algorithm 1, lines 15-19).

Pure, jit-able server step over a *stacked* batch of client updates
(leading axis = participating clients).  The projection/scaling math per
client lives in core/projection.py; here we vmap it over the client axis
and aggregate.

Server state is exactly one pytree: ``delta_prev`` (the previous global
update Delta_{t-1}) — the method is stateless on clients, which is what
makes it robust to low-rate partial participation.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import projection as proj

PyTree = Any


def init_state(params: PyTree) -> Dict[str, PyTree]:
    """Delta_0 -> 0 (paper input line): projection onto a zero vector is 0,
    so round 1 degenerates to two-sided-LR FedAvg with the lam+1 scaling
    factor exactly as the paper's ablation baseline."""
    return {"delta_prev": proj.tree_zeros_like(params)}


def server_step(state: Dict[str, PyTree], params: PyTree, deltas: PyTree,
                eta_g: float, lam: float = 1.0, use_kernel: bool = False,
                client_mask=None, partitioned: bool = False,
                staleness_weights=None, encoded=None, edges=None
                ) -> Tuple[PyTree, Dict[str, PyTree], Dict[str, jnp.ndarray]]:
    """One FedDPC aggregation.

    deltas: client-stacked pytree — every leaf has leading axis k'
    (participating clients), leaf[j] = Delta_{jt} = (w_{t-1} - w_{jt})/eta_l.

    client_mask (k',) bool marks the REAL rows when the sharded path pads
    the cohort (DESIGN.md §2).  The per-client transform
    scale_j * (d_j - coef_j * prev) is linear in (scale_j, coef_j), so
    masking folds EXACTLY into the reduction-pass scalars: dummy rows get
    scale=coef=0 and the survivors renormalize by k'/n_valid — the
    unchanged mean-over-k' epilogue (jnp or Pallas) then computes the
    mean over real clients only, with no kernel changes.

    partitioned=True declares that the round is PARTITIONED by GSPMD
    over a mesh of more than one device: the client axis, the model axis
    of the two-axis cohort round, or both (DESIGN.md §2). The
    reduction-pass scalars need no change: they go through
    dim-preserving per-leaf sums (projection.tree_vdot), so GSPMD psums
    the ||Δ||² / <Δ, Δ_prev> partials across the mesh before
    ``scale``/``coef`` are formed. A Mosaic kernel, however, cannot be
    partitioned automatically (the TPU compiler refuses it), and the
    epilogue's per-leaf flatten would all-gather model-sharded leaves,
    so ``use_kernel`` falls back to the reference jnp epilogue — which
    is elementwise on the local shards and exact.

    staleness_weights (k',) f32 are the buffered-async discount factors
    (core/async_engine.py, DESIGN.md §11): each buffered delta's weight
    (1+s)^(-alpha) multiplies its adaptive SCALE, so the projection
    geometry (coef, the diagnostics) is computed on the raw delta and
    only the applied magnitude is discounted — the staleness-discounted
    projection coefficient folds into the same reduction-pass scalars
    the masked path uses, leaving the epilogue unchanged. At staleness
    0 every weight is exactly 1.0 and the step is the synchronous one.

    ``edges=E`` runs the aggregation as a two-level hierarchical fold
    (DESIGN.md §15): the k' rows split into E equal contiguous edge
    groups, each edge folds its slice — the SAME fused epilogue
    (including the Pallas and codec-dequant grids) over k'/E rows — to a
    partial reduction-pass sum, and the server combines the E partials.
    The per-client transform scale_j*(d_j - coef_j*prev) derives from
    dim-preserving scalars, so the scalars (and the mask/staleness
    folding above) are UNCHANGED; only the final mean decomposes, into
    mean-of-means over equal groups — exact up to float summation order.
    E must divide k'.

    ``encoded`` is the codec wire payload ({"q", "scale", "zero"} trees,
    repro/codec) whose dequant — ``q * scale + zero`` — reproduces
    ``deltas`` exactly. The reduction-pass scalars are still computed on
    the decoded ``deltas`` (4 dots per client, cheap); with ``use_kernel``
    the epilogue routes to the fused dequant→residual→scale→mean grid
    (kernels/feddpc_project.dequant_*), which reads the int8/bf16 stack
    straight from HBM — the full-precision decoded stack never makes a
    second full-memory pass. Ignored on the partitioned fallback.

    Returns (new_params, new_state, diagnostics).
    """
    if partitioned:
        use_kernel = False
    delta_prev = state["delta_prev"]

    with jax.named_scope("fl.server.reduce"):
        # reduction pass: per-client scalars (4 dots each, vmapped over K)
        coefs, scales, diag = jax.vmap(
            lambda d: proj.projection_scalars(d, delta_prev, lam))(deltas)
        if client_mask is None:
            diag_mean = jnp.mean
        else:
            mf = client_mask.astype(jnp.float32)
            nvalid = jnp.maximum(mf.sum(), 1.0)
            coefs = coefs * mf
            scales = scales * mf * (mf.shape[0] / nvalid)
            diag_mean = lambda x: jnp.sum(x * mf) / nvalid
    wgt = (None if staleness_weights is None
           else jnp.asarray(staleness_weights, jnp.float32))
    E = int(edges) if edges is not None and int(edges) > 1 else None
    if E is not None:
        k_rows = int(coefs.shape[0])
        if k_rows % E:
            raise ValueError(f"edges={E} must divide the cohort rows "
                             f"({k_rows})")
    with jax.named_scope("fl.server.epilogue"):
        if use_kernel:
            # epilogue pass: residual+scale, client-mean (Eq. 4) AND the
            # param update fused into ONE grid over the stacked deltas
            # (kernels/feddpc_project.batched_epilogue) — one HBM pass
            # instead of K per-client kernel calls + two more full
            # passes. The buffered-async fold routes to the scatter-
            # accumulate variant, which applies the staleness discount
            # inside the grid.
            from repro.kernels.feddpc_project import ops as k_ops

            def kernel_fold(d_slice, enc_slice, c_slice, s_slice, w_slice):
                if enc_slice is not None and w_slice is None:
                    return k_ops.dequant_batched_server_epilogue(
                        enc_slice, delta_prev, params, c_slice, s_slice,
                        eta_g)
                if enc_slice is not None:
                    return k_ops.dequant_buffered_server_fold(
                        enc_slice, delta_prev, params, c_slice, s_slice,
                        w_slice, eta_g)
                if w_slice is None:
                    return k_ops.batched_server_epilogue(
                        d_slice, delta_prev, params, c_slice, s_slice,
                        eta_g)
                return k_ops.buffered_server_fold(
                    d_slice, delta_prev, params, c_slice, s_slice, w_slice,
                    eta_g)

            if E is None:
                new_params, delta_t = kernel_fold(deltas, encoded, coefs,
                                                  scales, wgt)
            else:
                # two-level fold: each edge runs the SAME fused grid (Pallas
                # epilogue / codec dequant included) over its k'/E-row slice
                # — its partial mean is the edge→server summary — and the
                # server averages the E partials (equal groups: exact)
                rows = k_rows // E
                def rsl(tree, lo, hi):
                    return jax.tree.map(lambda x: x[lo:hi], tree)
                parts = []
                for e in range(E):
                    lo, hi = e * rows, (e + 1) * rows
                    _, part = kernel_fold(
                        rsl(deltas, lo, hi),
                        None if encoded is None else rsl(encoded, lo, hi),
                        coefs[lo:hi], scales[lo:hi],
                        None if wgt is None else wgt[lo:hi])
                    parts.append(part)
                delta_t = jax.tree.map(
                    lambda *xs: jnp.mean(jnp.stack(xs), axis=0), *parts)
                new_params = jax.tree.map(
                    lambda w, d: (w.astype(jnp.float32)
                                  - eta_g * d).astype(w.dtype),
                    params, delta_t)
        else:
            if wgt is not None:
                scales = scales * wgt
            def bc(s, x):
                return s.reshape((-1,) + (1,) * (x.ndim - 1))

            def mean_rows(x):
                if E is None:
                    return jnp.mean(x, axis=0)
                # two-level: per-edge partial means, then the mean of the E
                # edge summaries — the mask/staleness weights are already
                # folded into the scales, so the decomposition is exact
                return jnp.mean(jnp.mean(
                    x.reshape((E, x.shape[0] // E) + x.shape[1:]), axis=1),
                    axis=0)

            # scaled residual + mean over the client axis (Eq. 4)
            delta_t = jax.tree.map(
                lambda d, p: mean_rows(
                    bc(scales, d) * (d.astype(jnp.float32) - bc(coefs, d)
                                     * p.astype(jnp.float32)[None])),
                deltas, delta_prev)
            new_params = jax.tree.map(
                lambda w, d: (w.astype(jnp.float32)
                              - eta_g * d).astype(w.dtype),
                params, delta_t)
        new_state = {"delta_prev": delta_t}
        diagnostics = {
            "mean_coef": diag_mean(diag["coef"]),
            "mean_cos_angle": diag_mean(diag["cos_angle"]),
            "mean_scale": diag_mean(diag["scale"]),
            "mean_norm_delta": diag_mean(diag["norm_delta"]),
            "norm_global_update": proj.tree_norm(delta_t),
            # orthogonality invariant: <Delta_t, Delta_{t-1}> ~ 0 after
            # round 1
            "global_dot_prev": proj.tree_vdot(delta_t, delta_prev),
        }
    return new_params, new_state, diagnostics


def server_step_projection_only(state, params, deltas, eta_g,
                                client_mask=None, edges=None
                                ) -> Tuple[PyTree, Dict, Dict]:
    """Ablation: orthogonal projection WITHOUT adaptive scaling (paper Fig 6,
    blue line). Equivalent to lam-scaling with scale == 1."""
    delta_prev = state["delta_prev"]

    def one(d):
        coef = proj.project_coefficient(d, delta_prev)
        return jax.tree.map(
            lambda di, pi: (di.astype(jnp.float32)
                            - coef * pi.astype(jnp.float32)).astype(di.dtype),
            d, delta_prev)

    resid = jax.vmap(one)(deltas)
    delta_t = proj.masked_client_mean(resid, client_mask, edges=edges)
    new_params = jax.tree.map(
        lambda w, d: (w.astype(jnp.float32) - eta_g * d).astype(w.dtype),
        params, delta_t)
    return new_params, {"delta_prev": delta_t}, {
        "norm_global_update": proj.tree_norm(delta_t)}
