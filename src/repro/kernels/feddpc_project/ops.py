"""jit'd wrappers: flat-vector and pytree entry points for the fused
FedDPC server epilogue.

``residual_scale_tree`` is what core/projection.py routes to with
use_kernel=True: per-leaf (pad to (M,128)) fused epilogue, given the
already-computed coef/scale scalars.  ``project_and_scale_flat`` is the
complete two-pass kernel path on one flat vector (used by benchmarks to
measure the fused HBM-pass structure end-to-end).

Every entry point takes ``interpret=None``: the compiled kernel on a
TPU, interpret mode elsewhere (``repro.kernels.resolve_interpret``).
Each pads its operands to whole, tile-aligned row blocks (zero rows are
exact no-ops for the dots and the epilogues) and trims the outputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.feddpc_project import kernel as K

EPS = 1e-12


def _rows(n: int, target: int, *xs) -> int:
    """Row block for n elements laid out (M, 128) across operands xs."""
    return K.block_rows(-(-n // K.LANE), target,
                        K.sublane(*(x.dtype for x in xs)))


def _to_2d(x: jnp.ndarray, rows: int = None):
    """Flatten + zero-pad to (M, 128) with M a multiple of ``rows`` (by
    default x's own row block)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = rows or _rows(n, K.DEFAULT_ROWS, x)
    pad = (-n) % (K.LANE * rows)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, K.LANE), n


def _from_2d(y2: jnp.ndarray, n: int, shape, dtype):
    return y2.reshape(-1)[:n].reshape(shape).astype(dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_dots_flat(d: jnp.ndarray, p: jnp.ndarray, interpret: bool = None):
    """-> (3,) = [<d,p>, <d,d>, <p,p>] via one fused HBM pass."""
    rows = _rows(d.size, K.DEFAULT_ROWS, d, p)
    d2, _ = _to_2d(d, rows)
    p2, _ = _to_2d(p, rows)
    partials = K.fused_dots(d2, p2, rows=rows,
                            interpret=resolve_interpret(interpret))
    return partials.sum(axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def guard_dots_flat(d: jnp.ndarray, p: jnp.ndarray, interpret: bool = None):
    """-> (4,) = [<d,p>, <d,d>, <p,p>, nonfinite(d)]: the reduction pass
    with the update-guard's validity column fused into the same HBM
    sweep (DESIGN.md §12). The zero padding added by ``_to_2d`` is
    finite, so it never inflates the non-finite count."""
    rows = _rows(d.size, K.DEFAULT_ROWS, d, p)
    d2, _ = _to_2d(d, rows)
    p2, _ = _to_2d(p, rows)
    return K.guard_dots(d2, p2, rows=rows,
                        interpret=resolve_interpret(interpret)).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("lam", "interpret"))
def project_and_scale_flat(d: jnp.ndarray, p: jnp.ndarray, lam: float = 1.0,
                           interpret: bool = None) -> jnp.ndarray:
    """Complete FedDPC per-client modification on a flat vector:
    pass 1 fused dots -> scalars; pass 2 fused residual+scale."""
    dots = fused_dots_flat(d, p, interpret=interpret)
    dp, dd, pp = dots[0], dots[1], dots[2]
    coef = jnp.where(pp > EPS, dp / jnp.maximum(pp, EPS), 0.0)
    # ||resid||^2 = ||d||^2 - coef^2 ||p||^2 (Pythagoras; saves a 3rd pass)
    sq_resid = jnp.maximum(dd - coef * coef * pp, 0.0)
    scale = lam + jnp.sqrt(dd) / jnp.maximum(jnp.sqrt(sq_resid), EPS)
    return residual_scale_tree(d, p, coef, scale, interpret=interpret)


def _to_3d(x: jnp.ndarray, rows: int):
    """Client-stacked (K, ...) -> (K, M, 128), M a multiple of ``rows``."""
    k = x.shape[0]
    flat = x.reshape(k, -1)
    pad = (-flat.shape[1]) % (K.LANE * rows)
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat.reshape(k, -1, K.LANE)


def _fold_tree(fold, stacked, delta_prev, params, target, leaf_args=()):
    """Runs ``fold(x3, p2, w2, *leaf_scalars, rows=)`` once per leaf of the
    client-stacked tree ``stacked``, each leaf padded to whole row blocks
    of the ``target`` size; ``leaf_args`` are trees of per-leaf (K,)
    scalars (the codec's dequant scale/zero). Returns (new_params,
    delta_t), delta_t f32 (it is server STATE — matching the jnp path's
    f32 accumulation)."""
    flat_x, treedef = jax.tree_util.tree_flatten(stacked)
    extra = [jax.tree.leaves(t) for t in leaf_args]
    new_w, new_dt = [], []
    for i, (x, p, w) in enumerate(zip(flat_x, jax.tree.leaves(delta_prev),
                                      jax.tree.leaves(params))):
        rows = _rows(p.size, target, x, p, w)
        p2, n = _to_2d(p, rows)
        w2, _ = _to_2d(w, rows)
        w_out2, dt2 = fold(_to_3d(x, rows), p2, w2, *[e[i] for e in extra],
                           rows=rows)
        new_w.append(_from_2d(w_out2, n, w.shape, w.dtype))
        new_dt.append(_from_2d(dt2, n, p.shape, jnp.float32))
    return (jax.tree_util.tree_unflatten(treedef, new_w),
            jax.tree_util.tree_unflatten(treedef, new_dt))


def _cohort_size(stacked) -> int:
    return jax.tree.leaves(stacked)[0].shape[0]


def batched_server_epilogue(deltas, delta_prev, params, coefs, scales,
                            eta_g, interpret: bool = None):
    """Whole-cohort FedDPC server epilogue (kernel.batched_epilogue per
    leaf): deltas client-stacked (K, ...), delta_prev/params plain trees,
    coefs/scales (K,) from the reduction pass. Returns
    (new_params, delta_t) in one fused HBM pass over the stacked deltas —
    the use_kernel=True route of core/feddpc.server_step."""
    interpret = resolve_interpret(interpret)

    def fold(d3, p2, w2, *, rows):
        return K.batched_epilogue(d3, p2, w2, coefs, scales, eta_g,
                                  rows=rows, interpret=interpret)

    return _fold_tree(fold, deltas, delta_prev, params,
                      K.cohort_rows(_cohort_size(deltas)))


def buffered_server_fold(deltas, delta_prev, params, coefs, scales,
                         weights, eta_g, interpret: bool = None):
    """Staleness-weighted buffered-async server fold (kernel.buffer_fold
    per leaf, DESIGN.md §11): deltas is the ARRIVAL BUFFER stacked
    (B, ...), coefs/scales (B,) from the reduction pass, weights (B,)
    the (1+s)^(-alpha) staleness discounts. Returns (new_params,
    delta_t) like ``batched_server_epilogue``, but the scatter-
    accumulate grid streams the buffered deltas one at a time, so the
    row block stays at DEFAULT_ROWS regardless of the buffer size
    (batched_epilogue's K-resident block would shrink as B grows)."""
    interpret = resolve_interpret(interpret)

    def fold(d3, p2, w2, *, rows):
        return K.buffer_fold(d3, p2, w2, coefs, scales, weights, eta_g,
                             rows=rows, interpret=interpret)

    return _fold_tree(fold, deltas, delta_prev, params, K.DEFAULT_ROWS)


def dequant_batched_server_epilogue(payload, delta_prev, params, coefs,
                                    scales, eta_g, interpret: bool = None):
    """``batched_server_epilogue`` fed the codec's QUANTIZED cohort
    payload (``{"q", "scale", "zero"}`` per-leaf trees, repro/codec wire
    format) instead of the f32 delta stack: the per-leaf dequant fuses
    into the epilogue grid (kernel.dequant_batched_epilogue), so the
    cohort's HBM sweep reads int8/bf16 blocks. Padded tails dequant to
    the zero-point, which is trimmed away exactly like the f32 path's
    zero padding."""
    interpret = resolve_interpret(interpret)

    def fold(q3, p2, w2, qs, qz, *, rows):
        return K.dequant_batched_epilogue(q3, p2, w2, coefs, scales, eta_g,
                                          qs, qz, rows=rows,
                                          interpret=interpret)

    return _fold_tree(fold, payload["q"], delta_prev, params,
                      K.cohort_rows(_cohort_size(payload["q"])),
                      (payload["scale"], payload["zero"]))


def dequant_buffered_server_fold(payload, delta_prev, params, coefs,
                                 scales, weights, eta_g,
                                 interpret: bool = None):
    """``buffered_server_fold`` fed the codec's quantized arrival-buffer
    payload: the per-arrival dequant fuses into the scatter-accumulate
    stream (kernel.dequant_buffer_fold), staleness discounts composing
    with the dequant scales as per-arrival scalars."""
    interpret = resolve_interpret(interpret)

    def fold(q3, p2, w2, qs, qz, *, rows):
        return K.dequant_buffer_fold(q3, p2, w2, coefs, scales, weights,
                                     eta_g, qs, qz, rows=rows,
                                     interpret=interpret)

    return _fold_tree(fold, payload["q"], delta_prev, params,
                      K.DEFAULT_ROWS, (payload["scale"], payload["zero"]))


def residual_scale_tree(delta, delta_prev, coef, scale,
                        interpret: bool = None):
    """Per-leaf fused epilogue with precomputed scalars (pytree entry used
    by core/projection.project_and_scale(use_kernel=True))."""
    interpret = resolve_interpret(interpret)

    def one(d, p):
        rows = _rows(d.size, K.DEFAULT_ROWS, d, p)
        d2, n = _to_2d(d, rows)
        p2, _ = _to_2d(p, rows)
        out2 = K.fused_epilogue(d2, p2, coef, scale, rows=rows,
                                interpret=interpret)
        return _from_2d(out2, n, d.shape, d.dtype)

    return jax.tree.map(one, delta, delta_prev)
