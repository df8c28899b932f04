"""Pallas TPU kernels for the FedDPC server epilogue (DESIGN.md §2).

The server step per client update is two passes over R^d:

  1. reduction pass:  <d, prev>, ||d||^2, ||prev||^2   (three dots, fused)
  2. epilogue pass:   out = scale * (d - coef * prev)  (fused residual+scale)

Naively (paper Table 1: O(4k'd) server work in 4+ separate passes) each
scalar costs its own HBM sweep. Fusing pass 1 reads d and prev ONCE for
all three dots (6d bytes -> 4d bytes), and pass 2 fuses the projection
subtraction with the adaptive scaling (4d -> 3d bytes). TPU adaptation:
updates are processed as (rows, 128)-tiled blocks resident in VMEM —
lane-aligned, VPU elementwise, no MXU involvement.

``batched_epilogue`` extends pass 2 to the WHOLE cohort (DESIGN.md §2):
one grid over the stacked (K, M, 128) deltas fuses the per-client
residual+scale with the client-mean and the global param update, so the
entire server epilogue is a single HBM pass over (K+2)·d floats instead
of K separate per-client kernel launches under vmap plus two more full
passes for the mean and the parameter update.

Layout rules the TPU lowering enforces (and that interpret mode does
not): every row block is a whole number of the strictest operand's
(sublane, 128) tile — 8 rows for f32, 16 for bf16, 32 for int8 — so each
entry point pads M up to whole blocks itself and trims its outputs; the
per-client / per-arrival scalars (coefs, scales, staleness weights,
dequant scale/zero, eta) sit whole in SMEM and are read per client
index; the per-block dot partials are written to SMEM.

Every kernel here is checked against ref.py in interpret mode on CPU,
AOT-compiled for a described TPU v5e at ResNet-18 widths
(tests/test_tpu_compile.py). On a v5e chip, chip_smoke.py runs
``batched_epilogue`` inside the full-width ResNet-18 FedDPC round and
checks all six against ref.py at that model's largest leaf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DEFAULT_ROWS = 512          # (512, 128) f32 block = 256 KiB VMEM per operand

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)     # whole array, scalar reads


def sublane(*dtypes) -> int:
    """Rows of the strictest (sublane, 128) tile among ``dtypes``: 8 for
    4-byte, 16 for 2-byte, 32 for 1-byte element types."""
    return max(8, *(32 // jnp.dtype(dt).itemsize for dt in dtypes))


def block_rows(m: int, target: int, tile: int) -> int:
    """Row block for an (M, 128) operand: ``target`` rounded down to a
    whole number of ``tile`` rows (at least one tile), and never more
    than M rounded up to one."""
    rows = max(tile, target // tile * tile)
    return min(rows, -(-m // tile) * tile)


def cohort_rows(k: int) -> int:
    """Row target when all K clients' blocks are resident at once: the
    K·rows·128 block shrinks with K so it keeps fitting VMEM."""
    return max(8, DEFAULT_ROWS // max(1, k))


def _pad_rows(x: jnp.ndarray, m_pad: int) -> jnp.ndarray:
    """Zero-pad the row axis (second to last) of x to m_pad."""
    pad = m_pad - x.shape[-2]
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[-2] = (0, pad)
    return jnp.pad(x, widths)


def _plan(m: int, target: int, *arrays):
    """(rows, padded M) for a grid of whole, tile-aligned row blocks."""
    rows = block_rows(m, target, sublane(*(a.dtype for a in arrays)))
    return rows, -(-m // rows) * rows


def _dots_call(kernel, ncol, d2, p2, rows, interpret):
    """Shared grid of the reduction-pass kernels: (G, ncol) per-block
    partials. Each block writes a (1, 1, ncol) SMEM block of a
    (G, 1, ncol) array — its last two dims span the array's, which the
    TPU lowering accepts where a (1, ncol) block of (G, ncol) is not."""
    m = d2.shape[0]
    rows, mp = _plan(m, rows, d2, p2)
    grid = (mp // rows,)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1, ncol), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, ncol), jnp.float32),
        interpret=interpret,
    )(_pad_rows(d2, mp), _pad_rows(p2, mp))[:, 0]


def _reduce_kernel(d_ref, p_ref, out_ref):
    d = d_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    out_ref[0, 0, 0] = jnp.sum(d * p)
    out_ref[0, 0, 1] = jnp.sum(d * d)
    out_ref[0, 0, 2] = jnp.sum(p * p)


def fused_dots(d2: jnp.ndarray, p2: jnp.ndarray, *, rows: int = DEFAULT_ROWS,
               interpret: bool) -> jnp.ndarray:
    """d2/p2: (M, 128). Returns (G, 3) per-block partials of
    [<d,p>, <d,d>, <p,p>] — sum over G outside (one tiny reduction).
    Zero rows padded onto the last block add nothing to the dots."""
    return _dots_call(_reduce_kernel, 3, d2, p2, rows, interpret)


def _guard_reduce_kernel(d_ref, p_ref, out_ref):
    """Reduction pass + guard epilogue in ONE sweep (DESIGN.md §12): the
    three FedDPC dots plus the update-guard's non-finite count, so
    validating a delta costs zero extra HBM traffic over computing its
    reduction scalars. Non-finite entries are zeroed before the dots —
    exact for clean deltas, and the dots stay finite (usable) even when
    the guard column flags the client for quarantine."""
    d = d_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    finite = jnp.isfinite(d)
    df = jnp.where(finite, d, 0.0)
    out_ref[0, 0, 0] = jnp.sum(df * p)
    out_ref[0, 0, 1] = jnp.sum(df * df)
    out_ref[0, 0, 2] = jnp.sum(p * p)
    out_ref[0, 0, 3] = jnp.sum((~finite).astype(jnp.float32))


def guard_dots(d2: jnp.ndarray, p2: jnp.ndarray, *, rows: int = DEFAULT_ROWS,
               interpret: bool) -> jnp.ndarray:
    """d2/p2: (M, 128). Returns (G, 4) per-block partials of
    [<d,p>, <d,d>, <p,p>, nonfinite(d)] — ``fused_dots`` with the guard
    column riding the same pass; sum over G outside."""
    return _dots_call(_guard_reduce_kernel, 4, d2, p2, rows, interpret)


def _cohort_mean(d_ref, p, coef_ref, scale_ref, qs_ref=None, qz_ref=None):
    """mean_j scale_j * (d_j - coef_j * p) over the resident (K, r, 128)
    block, one client at a time; with qs/qz each d_j is dequantized on
    the fly as ``q_j * qs_j + qz_j``."""
    k = d_ref.shape[0]

    def client(j, acc):
        d = d_ref[j].astype(jnp.float32)
        if qs_ref is not None:
            d = d * qs_ref[j] + qz_ref[j]
        return acc + scale_ref[j] * (d - coef_ref[j] * p)

    return jax.lax.fori_loop(0, k, client, jnp.zeros_like(p)) / k


def _batched_epilogue_kernel(coef_ref, scale_ref, eta_ref, d_ref, p_ref,
                             w_ref, w_out_ref, dt_out_ref):
    """Whole-cohort server epilogue on one (rows, 128) tile:

        dt  = mean_j scale_j * (d_j - coef_j * prev)      (residual+scale+mean)
        w'  = w - eta_g * dt                              (global param update)

    d_ref block is (K, rows, 128) — all K clients' tile resident at once,
    so the stacked deltas are read exactly ONCE per round.
    """
    p = p_ref[...].astype(jnp.float32)                    # (r, 128)
    dt = _cohort_mean(d_ref, p, coef_ref, scale_ref)
    dt_out_ref[...] = dt.astype(dt_out_ref.dtype)
    w = w_ref[...].astype(jnp.float32)
    w_out_ref[...] = (w - eta_ref[0] * dt).astype(w_out_ref.dtype)


def _cohort_call(kernel, scalars, d3, p2, w2, rows, interpret):
    """Shared grid of the K-resident epilogues: the (K,) scalars whole in
    SMEM, one (K, rows, 128) cohort block per row block. Returns
    (new_w2, delta_t2) trimmed back to the caller's M."""
    k, m, lane = d3.shape
    assert lane == LANE, d3.shape
    rows, mp = _plan(m, rows or cohort_rows(k), d3, p2, w2)
    w_out, dt = pl.pallas_call(
        kernel,
        grid=(mp // rows,),
        in_specs=[_SMEM] * len(scalars) + [
            pl.BlockSpec((k, rows, LANE), lambda i: (0, i, 0)),
            pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
                   pl.BlockSpec((rows, LANE), lambda i: (i, 0))],
        # delta_t is server STATE: emitted f32 to match the jnp path's
        # f32 accumulation whatever the input dtypes
        out_shape=[jax.ShapeDtypeStruct((mp, lane), w2.dtype),
                   jax.ShapeDtypeStruct((mp, lane), jnp.float32)],
        interpret=interpret,
    )(*scalars, _pad_rows(d3, mp), _pad_rows(p2, mp), _pad_rows(w2, mp))
    return w_out[:m], dt[:m]


def _scalars(n: int, *values):
    return [jnp.asarray(v, jnp.float32).reshape(n) for v in values]


def batched_epilogue(d3: jnp.ndarray, p2: jnp.ndarray, w2: jnp.ndarray,
                     coefs, scales, eta_g, *, rows: int = None,
                     interpret: bool):
    """Fused FedDPC server epilogue over the STACKED cohort in one grid.

    d3: (K, M, 128) client-stacked deltas; p2/w2: (M, 128) delta_prev /
    params; coefs/scales: (K,) per-client scalars from the reduction pass.
    Returns (new_w2, delta_t2), both (M, 128): one HBM pass over the
    (K+2)·M·128 input floats instead of K separate epilogue calls (which
    re-read prev K times and leave the mean + param update as extra
    passes). K·rows·128 elements must fit VMEM, so the row block shrinks
    as K grows (``cohort_rows``), aligned to the operands' sublane tile.
    Any M is accepted: rows past M are zero-padded and trimmed.
    """
    k = d3.shape[0]
    return _cohort_call(_batched_epilogue_kernel,
                        _scalars(k, coefs, scales) + _scalars(1, eta_g),
                        d3, p2, w2, rows, interpret)


def _buffer_fold_kernel(inv_b, coef_ref, scale_ref, wgt_ref, eta_ref,
                        d_ref, p_ref, w_ref, w_out_ref, dt_out_ref,
                        qs_ref=None, qz_ref=None):
    """Buffered-async server fold on one (rows, 128) tile (DESIGN.md §11):

        dt  = (1/B) sum_j wgt_j * scale_j * (d_j - coef_j * prev)
        w'  = w - eta_g * dt

    The grid's innermost axis j walks the B buffered deltas ONE AT A
    TIME and scatter-accumulates into the resident dt output block: the
    output BlockSpec ignores j, so Pallas keeps the (rows, 128) dt tile
    in VMEM across the whole j loop while each d_j block streams
    through. VMEM footprint is O(rows·128) independent of B — a buffer
    of 64 stale updates folds with the same full-size row block the
    K-resident batched epilogue would have to shrink 64x for. With
    qs/qz the streamed block is dequantized on the fly.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dt_out_ref[...] = jnp.zeros_like(dt_out_ref)

    d = d_ref[0].astype(jnp.float32)                      # (r, 128)
    if qs_ref is not None:
        d = d * qs_ref[j] + qz_ref[j]
    p = p_ref[...].astype(jnp.float32)                    # (r, 128)
    # staleness-discounted projection coefficient: the discount wgt_j
    # multiplies the adaptive scale, the geometry (coef_j) stays raw
    dt_out_ref[...] += ((wgt_ref[j] * scale_ref[j])
                        * (d - coef_ref[j] * p)) * inv_b

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        w = w_ref[...].astype(jnp.float32)
        w_out_ref[...] = (w - eta_ref[0] * dt_out_ref[...]
                          ).astype(w_out_ref.dtype)


def _fold_call(kernel, scalars, d3, p2, w2, rows, interpret):
    """Shared (blocks, B) grid of the streaming folds, B innermost so the
    dt block stays resident; scalars whole in SMEM, read at index j."""
    b, m, lane = d3.shape
    assert lane == LANE, d3.shape
    rows, mp = _plan(m, rows or DEFAULT_ROWS, d3, p2, w2)
    w_out, dt = pl.pallas_call(
        functools.partial(kernel, 1.0 / b),
        grid=(mp // rows, b),
        in_specs=[_SMEM] * len(scalars) + [
            pl.BlockSpec((1, rows, LANE), lambda i, j: (j, i, 0)),
            pl.BlockSpec((rows, LANE), lambda i, j: (i, 0)),
            pl.BlockSpec((rows, LANE), lambda i, j: (i, 0)),
        ],
        # output index_maps ignore j -> blocks stay resident across the
        # inner delta loop (the scatter-accumulate idiom)
        out_specs=[pl.BlockSpec((rows, LANE), lambda i, j: (i, 0)),
                   pl.BlockSpec((rows, LANE), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((mp, lane), w2.dtype),
                   jax.ShapeDtypeStruct((mp, lane), jnp.float32)],
        interpret=interpret,
    )(*scalars, _pad_rows(d3, mp), _pad_rows(p2, mp), _pad_rows(w2, mp))
    return w_out[:m], dt[:m]


def buffer_fold(d3: jnp.ndarray, p2: jnp.ndarray, w2: jnp.ndarray,
                coefs, scales, wgts, eta_g, *, rows: int = None,
                interpret: bool):
    """Staleness-weighted buffered fold over B stacked deltas.

    d3: (B, M, 128) buffered deltas; p2/w2: (M, 128) delta_prev/params;
    coefs/scales/wgts: (B,) reduction-pass scalars + staleness discounts.
    Returns (new_w2, delta_t2) like ``batched_epilogue``, but streams
    the deltas through a (blocks, B) grid with B innermost instead of
    holding all B resident — so ``rows`` stays at DEFAULT_ROWS no
    matter how large the arrival buffer grows. At wgts == 1 the math is
    ``batched_epilogue`` with mean replaced by an ordered partial-sum
    accumulation (same values up to f32 summation order).
    """
    b = d3.shape[0]
    return _fold_call(_buffer_fold_kernel,
                      _scalars(b, coefs, scales, wgts) + _scalars(1, eta_g),
                      d3, p2, w2, rows, interpret)


def _dequant_batched_epilogue_kernel(coef_ref, scale_ref, eta_ref, qs_ref,
                                     qz_ref, q_ref, p_ref, w_ref,
                                     w_out_ref, dt_out_ref):
    """``_batched_epilogue_kernel`` with the codec dequant fused in front
    (DESIGN.md §13): the resident (K, rows, 128) block holds the
    QUANTIZED cohort (int8 or bf16 — 4x / 2x less HBM traffic and VMEM
    residency than the f32 stack), and the per-client dequant
    ``d_j = q_j * qs_j + qz_j`` runs on the VPU right before the
    residual+scale+mean, so the f32 deltas never materialize:

        dt  = mean_j scale_j * ((q_j * qs_j + qz_j) - coef_j * prev)
        w'  = w - eta_g * dt
    """
    p = p_ref[...].astype(jnp.float32)                    # (r, 128)
    dt = _cohort_mean(q_ref, p, coef_ref, scale_ref, qs_ref, qz_ref)
    dt_out_ref[...] = dt.astype(dt_out_ref.dtype)
    w = w_ref[...].astype(jnp.float32)
    w_out_ref[...] = (w - eta_ref[0] * dt).astype(w_out_ref.dtype)


def dequant_batched_epilogue(q3: jnp.ndarray, p2: jnp.ndarray,
                             w2: jnp.ndarray, coefs, scales, eta_g,
                             qscales, qzeros, *, rows: int = None,
                             interpret: bool):
    """``batched_epilogue`` over a QUANTIZED cohort stack.

    q3: (K, M, 128) int8/bf16 quantized deltas; qscales/qzeros: (K,)
    per-client dequant scalars for this leaf (repro/codec wire format:
    ``dequant(q) = q * qscale + qzero``); everything else as
    ``batched_epilogue``. The grid is the same; the row block aligns to
    the quantized dtype's taller sublane tile (32 rows for int8, 16 for
    bf16). Zero-padded rows dequant to qzero (not 0) and are trimmed,
    exactly as the f32 path trims its zero padding.
    """
    k = q3.shape[0]
    return _cohort_call(_dequant_batched_epilogue_kernel,
                        _scalars(k, coefs, scales) + _scalars(1, eta_g)
                        + _scalars(k, qscales, qzeros),
                        q3, p2, w2, rows, interpret)


def _dequant_buffer_fold_kernel(inv_b, coef_ref, scale_ref, wgt_ref,
                                eta_ref, qs_ref, qz_ref, q_ref, p_ref,
                                w_ref, w_out_ref, dt_out_ref):
    """``_buffer_fold_kernel`` with the per-arrival dequant fused in: the
    staleness discount wgt_j and the dequant scale qs_j COMPOSE as plain
    per-j scalar multipliers on the streamed block (DESIGN.md §11/§13):

        dt = (1/B) sum_j wgt_j * scale_j * ((q_j * qs_j + qz_j) - coef_j * prev)
    """
    _buffer_fold_kernel(inv_b, coef_ref, scale_ref, wgt_ref, eta_ref,
                        q_ref, p_ref, w_ref, w_out_ref, dt_out_ref,
                        qs_ref, qz_ref)


def dequant_buffer_fold(q3: jnp.ndarray, p2: jnp.ndarray, w2: jnp.ndarray,
                        coefs, scales, wgts, eta_g, qscales, qzeros, *,
                        rows: int = None, interpret: bool):
    """``buffer_fold`` over a QUANTIZED arrival buffer: q3 (B, M, 128)
    int8/bf16, qscales/qzeros (B,) per-arrival dequant scalars; the
    scatter-accumulate grid is unchanged (B innermost, dt resident) and
    each streamed block is dequantized on the fly."""
    b = q3.shape[0]
    return _fold_call(_dequant_buffer_fold_kernel,
                      _scalars(b, coefs, scales, wgts) + _scalars(1, eta_g)
                      + _scalars(b, qscales, qzeros),
                      q3, p2, w2, rows, interpret)


def _epilogue_kernel(coef_ref, scale_ref, d_ref, p_ref, out_ref):
    coef = coef_ref[0]
    scale = scale_ref[0]
    d = d_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    out_ref[...] = (scale * (d - coef * p)).astype(out_ref.dtype)


def fused_epilogue(d2: jnp.ndarray, p2: jnp.ndarray, coef, scale, *,
                   rows: int = DEFAULT_ROWS,
                   interpret: bool) -> jnp.ndarray:
    """out = scale * (d2 - coef * p2), one HBM pass. d2/p2: (M, 128)."""
    m = d2.shape[0]
    rows, mp = _plan(m, rows, d2, p2)
    out = pl.pallas_call(
        _epilogue_kernel,
        grid=(mp // rows,),
        in_specs=[_SMEM, _SMEM,
                  pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, LANE), d2.dtype),
        interpret=interpret,
    )(*_scalars(1, coef, scale), _pad_rows(d2, mp), _pad_rows(p2, mp))
    return out[:m]
