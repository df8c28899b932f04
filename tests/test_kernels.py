"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp
oracle (ref.py), as required for every kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.feddpc_project import ops as fp_ops, ref as fp_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.ssm_scan import ops as ss_ops, ref as ss_ref


# ---------------- feddpc_project ----------------

@pytest.mark.parametrize("n", [32, 128, 1000, 65536, 70001])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_feddpc_project_sweep(n, dtype, rng):
    k1, k2 = jax.random.split(rng)
    d = jax.random.normal(k1, (n,), dtype)
    p = jax.random.normal(k2, (n,), dtype)
    got = fp_ops.project_and_scale_flat(d, p, lam=1.0)
    want = fp_ref.project_and_scale_flat_ref(d, p, 1.0)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("k,n", [(1, 128), (3, 1000), (8, 70001)])
def test_feddpc_batched_epilogue_sweep(k, n, rng):
    """kernel.batched_epilogue (one grid over the stacked cohort) vs the
    pure-jnp oracle, across block-boundary/pad shapes."""
    from repro.kernels.feddpc_project import kernel as fp_kernel
    ks = jax.random.split(rng, 5)
    m = -(-n // 128)
    rows = max(8, fp_kernel.DEFAULT_ROWS // k)
    m += (-m) % rows                              # full blocks, like ops.py
    d3 = jax.random.normal(ks[0], (k, m, 128))
    p2 = jax.random.normal(ks[1], (m, 128))
    w2 = jax.random.normal(ks[2], (m, 128))
    coefs = jax.random.normal(ks[3], (k,))
    scales = 1.0 + jnp.abs(jax.random.normal(ks[4], (k,)))
    got_w, got_dt = fp_kernel.batched_epilogue(d3, p2, w2, coefs, scales, 0.3,
                                               interpret=True)
    want_w, want_dt = fp_ref.batched_epilogue_ref(d3, p2, w2, coefs, scales,
                                                  0.3)
    np.testing.assert_allclose(got_w, want_w, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_dt, want_dt, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("round1", [True, False])
def test_feddpc_batched_server_step_matches_jnp(round1, rng):
    """feddpc.server_step(use_kernel=True) — the single-HBM-pass batched
    epilogue — matches the jnp path, including the K>1 zero-delta_prev
    round-1 case (projection degenerates to 0, scale to lam+1)."""
    from repro.core import feddpc
    ks = jax.random.split(rng, 3)
    params = {"w": jax.random.normal(ks[0], (40, 37)),
              "b": jax.random.normal(ks[1], (37,))}
    deltas = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(ks[2], x.ndim),
                                    (5,) + x.shape), params)
    prev = (jax.tree.map(jnp.zeros_like, params) if round1
            else jax.tree.map(lambda x: x * 0.3, params))
    outs = {}
    for uk in (False, True):
        outs[uk] = feddpc.server_step({"delta_prev": prev}, params, deltas,
                                      0.1, 1.0, use_kernel=uk)
    for a, b in zip(jax.tree.leaves(outs[False][:2]),
                    jax.tree.leaves(outs[True][:2])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    for key, va in outs[False][2].items():
        np.testing.assert_allclose(np.asarray(va),
                                   np.asarray(outs[True][2][key]),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("b,n", [(1, 512 * 128), (3, 1000), (7, 70001)])
def test_feddpc_buffer_fold_sweep(b, n, rng):
    """kernel.buffer_fold (scatter-accumulate over the arrival buffer,
    DESIGN.md §11) vs the pure-jnp oracle: the row block stays at
    DEFAULT_ROWS for every buffer size B (unlike batched_epilogue's
    K-resident block), and the staleness weights fold into the scales."""
    from repro.kernels.feddpc_project import kernel as fp_kernel
    ks = jax.random.split(rng, 6)
    m = -(-n // 128)
    m += (-m) % fp_kernel.DEFAULT_ROWS            # full blocks, like ops.py
    d3 = jax.random.normal(ks[0], (b, m, 128))
    p2 = jax.random.normal(ks[1], (m, 128))
    w2 = jax.random.normal(ks[2], (m, 128))
    coefs = jax.random.normal(ks[3], (b,))
    scales = 1.0 + jnp.abs(jax.random.normal(ks[4], (b,)))
    wgts = jax.random.uniform(ks[5], (b,), minval=0.1, maxval=1.0)
    got_w, got_dt = fp_kernel.buffer_fold(d3, p2, w2, coefs, scales, wgts,
                                          0.3, interpret=True)
    want_w, want_dt = fp_ref.buffer_fold_ref(d3, p2, w2, coefs, scales,
                                             wgts, 0.3)
    np.testing.assert_allclose(got_w, want_w, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_dt, want_dt, rtol=2e-5, atol=2e-5)


def test_feddpc_buffer_fold_unit_weights_match_batched_epilogue(rng):
    """At weights==1 (the zero-staleness anchor) the buffer fold is the
    plain batched epilogue on the same inputs — the property that makes
    the async anchor cell reproduce the sync round through the kernel
    path too."""
    from repro.kernels.feddpc_project import kernel as fp_kernel
    ks = jax.random.split(rng, 5)
    b, m = 4, 512
    d3 = jax.random.normal(ks[0], (b, m, 128))
    p2 = jax.random.normal(ks[1], (m, 128))
    w2 = jax.random.normal(ks[2], (m, 128))
    coefs = jax.random.normal(ks[3], (b,))
    scales = 1.0 + jnp.abs(jax.random.normal(ks[4], (b,)))
    ones = jnp.ones((b,))
    got_w, got_dt = fp_kernel.buffer_fold(d3, p2, w2, coefs, scales, ones,
                                          0.3, interpret=True)
    rows = max(8, fp_kernel.DEFAULT_ROWS // b)
    want_w, want_dt = fp_kernel.batched_epilogue(d3, p2, w2, coefs, scales,
                                                 0.3, rows=rows,
                                                 interpret=True)
    np.testing.assert_allclose(got_w, want_w, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(got_dt, want_dt, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("round1", [True, False])
def test_feddpc_buffered_server_step_matches_jnp(round1, rng):
    """feddpc.server_step(use_kernel=True, staleness_weights=...) routes
    to ops.buffered_server_fold and matches the jnp path (which folds
    the weights into the reduction-pass scales)."""
    from repro.core import feddpc
    ks = jax.random.split(rng, 4)
    params = {"w": jax.random.normal(ks[0], (40, 37)),
              "b": jax.random.normal(ks[1], (37,))}
    deltas = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(ks[2], x.ndim),
                                    (5,) + x.shape), params)
    prev = (jax.tree.map(jnp.zeros_like, params) if round1
            else jax.tree.map(lambda x: x * 0.3, params))
    wgts = jax.random.uniform(ks[3], (5,), minval=0.2, maxval=1.0)
    outs = {}
    for uk in (False, True):
        outs[uk] = feddpc.server_step({"delta_prev": prev}, params, deltas,
                                      0.1, 1.0, use_kernel=uk,
                                      staleness_weights=wgts)
    for a, b in zip(jax.tree.leaves(outs[False][:2]),
                    jax.tree.leaves(outs[True][:2])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    for key, va in outs[False][2].items():
        np.testing.assert_allclose(np.asarray(va),
                                   np.asarray(outs[True][2][key]),
                                   rtol=2e-4, atol=2e-5)


def test_feddpc_batched_server_step_bf16_state_stays_f32(rng):
    """delta_prev is server STATE: both epilogue paths must keep it f32
    even for bf16 params/deltas (regression: the kernel path used to
    inherit the input dtype)."""
    from repro.core import feddpc
    ks = jax.random.split(rng, 2)
    params = {"w": jax.random.normal(ks[0], (33, 40), jnp.bfloat16)}
    deltas = {"w": jax.random.normal(ks[1], (4, 33, 40), jnp.bfloat16)}
    prev = feddpc.init_state(params)["delta_prev"]
    for uk in (False, True):
        _, state, _ = feddpc.server_step({"delta_prev": prev}, params,
                                         deltas, 0.1, 1.0, use_kernel=uk)
        assert state["delta_prev"]["w"].dtype == jnp.float32, uk


def test_feddpc_fused_dots(rng):
    k1, k2 = jax.random.split(rng)
    d = jax.random.normal(k1, (5000,))
    p = jax.random.normal(k2, (5000,))
    got = fp_ops.fused_dots_flat(d, p)
    want = jnp.stack([jnp.vdot(d, p), jnp.vdot(d, d), jnp.vdot(p, p)])
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("m", [8, 64, 512, 1024])
def test_feddpc_guard_dots_bitwise_vs_ref(m, rng):
    """kernel.guard_dots (the reduction pass with the update guard's
    non-finite column fused in — DESIGN.md §12) vs ref.guard_dots_ref,
    BITWISE per grid block in interpret mode: same block, same jnp
    reduction, same result — including NaN/Inf entries scattered through
    d (they must zero out of the dots and land in the count). Full
    blocks only, like ops._to_2d always produces (partial-block padding
    semantics are exactly what the padding avoids)."""
    from repro.kernels.feddpc_project import kernel as fp_kernel
    k1, k2, k3 = jax.random.split(rng, 3)
    d2 = jax.random.normal(k1, (m, fp_kernel.LANE))
    p2 = jax.random.normal(k2, (m, fp_kernel.LANE))
    bad = jax.random.uniform(k3, d2.shape) < 0.05
    d2 = jnp.where(bad, jnp.where(d2 > 0, jnp.nan, jnp.inf), d2)
    rows = min(fp_kernel.DEFAULT_ROWS, m)
    got = fp_kernel.guard_dots(d2, p2, rows=rows, interpret=True)
    want = jnp.stack([fp_ref.guard_dots_ref(d2[i:i + rows], p2[i:i + rows])
                      for i in range(0, m, rows)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the count column is exact by construction
    assert int(got[:, 3].sum()) == int(bad.sum())


def test_feddpc_guard_dots_flat_nonfinite(rng):
    """ops.guard_dots_flat (padded flat entry point) against the whole-
    array oracle: the zero padding must not inflate the non-finite
    count, and the dots must stay finite despite NaN/Inf in d."""
    k1, k2 = jax.random.split(rng)
    d = jax.random.normal(k1, (5001,))
    p = jax.random.normal(k2, (5001,))
    d = d.at[7].set(jnp.nan).at[4999].set(jnp.inf)
    got = fp_ops.guard_dots_flat(d, p)
    d2, _ = fp_ops._to_2d(d)
    p2, _ = fp_ops._to_2d(p)
    want = fp_ref.guard_dots_ref(d2, p2)
    assert np.all(np.isfinite(np.asarray(got)))
    assert int(got[3]) == 2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------- flash_attention ----------------

@pytest.mark.parametrize("b,sq,sk,h,kv,d", [
    (2, 256, 256, 8, 2, 64),
    (1, 128, 128, 4, 4, 128),
    (1, 100, 100, 4, 2, 64),          # pad path
    (2, 64, 64, 16, 8, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, sq, sk, h, kv, d, dtype, rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, kv, d), dtype)
    pos = jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32)[None], (b, sq))
    kpos = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32)[None], (b, sk))
    got = fa_ops.flash_attention(q, k, v, pos, kpos)
    want = fa_ref.attention_ref(q, k, v, pos, kpos)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_sliding_window(rng):
    ks = jax.random.split(rng, 3)
    b, s, h, kv, d = 2, 384, 8, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    got = fa_ops.flash_attention(q, k, v, pos, pos, window=100)
    want = fa_ref.attention_ref(q, k, v, pos, pos, window=100)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_attention_ring_cache_decode(rng):
    """Decode against a partially-filled ring cache (-1 = empty slots)."""
    ks = jax.random.split(rng, 3)
    b, sk, h, kv, d = 2, 300, 16, 2, 128
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, sk, kv, d))
    v = jax.random.normal(ks[2], (b, sk, kv, d))
    q_pos = jnp.full((b, 1), sk, jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32)[None], (b, sk))
    k_pos = k_pos.at[:, -7:].set(-1)
    got = fa_ops.flash_attention(q, k, v, q_pos, k_pos, window=128)
    want = fa_ref.attention_ref(q, k, v, q_pos, k_pos, window=128)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_sdpa(rng):
    """Kernel == the model's blocked jnp path (impl='pallas' contract)."""
    from repro.models.attention import sdpa
    ks = jax.random.split(rng, 3)
    b, s, h, kv, d = 1, 256, 8, 4, 64
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    got = sdpa(q, k, v, pos, pos, impl="pallas")
    want = sdpa(q, k, v, pos, pos, impl="blocked")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------- ssm_scan ----------------

@pytest.mark.parametrize("b,s,d_in,n", [
    (2, 64, 128, 16),
    (1, 128, 256, 16),
    (2, 100, 96, 8),                  # pad path
    (1, 17, 64, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_sweep(b, s, d_in, n, dtype, rng):
    ks = jax.random.split(rng, 5)
    u = jax.random.normal(ks[0], (b, s, d_in), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d_in))) * 0.1
    bm = jax.random.normal(ks[2], (b, s, n))
    cm = jax.random.normal(ks[3], (b, s, n))
    a = -jnp.exp(jax.random.normal(ks[4], (d_in, n)) * 0.3)
    dsk = jnp.ones((d_in,))
    y, h = ss_ops.ssm_scan(u, dt, bm, cm, a, dsk)
    y2, h2 = ss_ref.ssm_scan_ref(u, dt, bm, cm, a, dsk)
    tol = 2e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y2, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(h, h2, rtol=2e-4, atol=2e-4)


def test_ssm_scan_matches_model_associative_scan(rng):
    from repro.configs.base import get_config
    from repro.models import ssm as ssm_mod
    from repro.models.layers import linear
    cfg = get_config("falcon-mamba-7b", smoke=True)
    p = ssm_mod.init_mamba(rng, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(rng, 1),
                          (2, 32, cfg.d_model), jnp.float32)
    y_model, _ = ssm_mod.mamba_forward(cfg, p, x)
    xz = linear(p["in_proj"], x)
    d_in = cfg.ssm_d_inner
    u, z = xz[..., :d_in], xz[..., d_in:]
    u_ext = jnp.concatenate(
        [jnp.zeros((2, cfg.ssm_conv - 1, d_in)), u], axis=1)
    u_c = jax.nn.silu(ssm_mod._conv_causal_from(p, u_ext, 32, cfg.ssm_conv))
    dt, bm, cm = ssm_mod._ssm_params(cfg, p, u_c)
    a = -jnp.exp(p["a_log"])
    yk, _ = ss_ops.ssm_scan(u_c, dt, bm, cm, a, p["d_skip"])
    y_kernel = linear(p["out_proj"], yk * jax.nn.silu(z))
    np.testing.assert_allclose(y_kernel, y_model, rtol=2e-4, atol=2e-4)
