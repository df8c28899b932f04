"""Hypothesis-driven fuzz coverage for the FedDPC batched Pallas
epilogue (kernels/feddpc_project), interpret mode vs the pure-jnp oracle
(ref.py): ragged cohort sizes K, zero ``delta_prev`` (the round-1
degenerate case), leaf/row shapes that are NOT multiples of the block
size (the kernel's largest-divisor row search and ops.py's pad-to-lane
path), and the two-axis model-sharded route — where the kernel must
fall back to the reference epilogue (DESIGN.md §2).

Runs under hypothesis when installed, else the deterministic fallback
(tests/_hypothesis_compat.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core import feddpc
from repro.kernels.feddpc_project import kernel as fp_kernel
from repro.kernels.feddpc_project import ops as fp_ops
from repro.kernels.feddpc_project import ref as fp_ref

settings.register_profile("ci", max_examples=12, deadline=None)
settings.load_profile("ci")

# rows that are NOT multiples of the block target: primes force the
# kernel's largest-divisor search down to small (even unit) blocks
M_CASES = (1, 7, 8, 96, 127, 128, 129, 200)


@given(st.integers(1, 9), st.sampled_from(M_CASES),
       st.sampled_from([False, True]))
def test_batched_epilogue_raw_fuzz(k, m, zero_prev):
    """kernel.batched_epilogue on raw (K, M, 128) blocks == ref.py, for
    ragged K x non-multiple-of-block M x zero delta_prev."""
    r = np.random.RandomState(k * 1000 + m + int(zero_prev))
    d3 = jnp.asarray(r.randn(k, m, 128), jnp.float32)
    p2 = (jnp.zeros((m, 128), jnp.float32) if zero_prev
          else jnp.asarray(r.randn(m, 128), jnp.float32))
    w2 = jnp.asarray(r.randn(m, 128), jnp.float32)
    coefs = jnp.asarray(r.randn(k), jnp.float32)
    scales = jnp.asarray(1.0 + np.abs(r.randn(k)), jnp.float32)
    got_w, got_dt = fp_kernel.batched_epilogue(d3, p2, w2, coefs, scales,
                                               0.3, interpret=True)
    want_w, want_dt = fp_ref.batched_epilogue_ref(d3, p2, w2, coefs,
                                                  scales, 0.3)
    np.testing.assert_allclose(got_w, want_w, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_dt, want_dt, rtol=2e-5, atol=2e-5)


@given(st.integers(1, 8), st.sampled_from([3, 37, 127, 130, 1000]),
       st.sampled_from([False, True]))
def test_batched_server_epilogue_tree_fuzz(k, n, zero_prev):
    """ops.batched_server_epilogue over a pytree whose leaf sizes are
    not lane/block multiples == the flat-vector oracle math, leaf by
    leaf (exercises the per-leaf pad + un-pad path end to end)."""
    r = np.random.RandomState(k * 77 + n)
    shapes = [(n,), (5, 7), (2, 3, 4)]
    params = {f"l{i}": jnp.asarray(r.randn(*s), jnp.float32)
              for i, s in enumerate(shapes)}
    deltas = jax.tree.map(
        lambda x: jnp.asarray(r.randn(k, *np.shape(x)), jnp.float32), params)
    prev = (jax.tree.map(jnp.zeros_like, params) if zero_prev
            else jax.tree.map(lambda x: x * 0.3, params))
    coefs = jnp.asarray(r.randn(k), jnp.float32)
    scales = jnp.asarray(1.0 + np.abs(r.randn(k)), jnp.float32)
    new_w, dt = fp_ops.batched_server_epilogue(deltas, prev, params,
                                               coefs, scales, 0.2,
                                               interpret=True)
    bc = lambda s, x: s.reshape((-1,) + (1,) * (x.ndim - 1))
    want_dt = jax.tree.map(
        lambda d, p: jnp.mean(bc(scales, d) * (d - bc(coefs, d) * p[None]),
                              axis=0), deltas, prev)
    want_w = jax.tree.map(lambda w, d: w - 0.2 * d, params, want_dt)
    for a, b in zip(jax.tree.leaves(new_w), jax.tree.leaves(want_w)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    for a, b in zip(jax.tree.leaves(dt), jax.tree.leaves(want_dt)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


@given(st.integers(1, 6), st.sampled_from([False, True]))
def test_server_step_model_sharded_falls_back_to_reference(k, round1):
    """feddpc.server_step(use_kernel=True, partitioned=True) must take
    the reference epilogue (the Pallas path would flatten partitioned
    leaves); on one device that makes it BITWISE equal to the jnp path,
    and the reduction-pass scalars are untouched by the flag."""
    r = np.random.RandomState(k)
    params = {"w": jnp.asarray(r.randn(12, 16), jnp.float32),
              "b": jnp.asarray(r.randn(16), jnp.float32)}
    deltas = jax.tree.map(
        lambda x: jnp.asarray(r.randn(k, *np.shape(x)), jnp.float32), params)
    prev = (jax.tree.map(jnp.zeros_like, params) if round1
            else jax.tree.map(lambda x: x * 0.5, params))
    ref_out = feddpc.server_step({"delta_prev": prev}, params, deltas,
                                 0.1, 1.0, use_kernel=False)
    got_out = feddpc.server_step({"delta_prev": prev}, params, deltas,
                                 0.1, 1.0, use_kernel=True,
                                 partitioned=True)
    for a, b in zip(jax.tree.leaves(ref_out), jax.tree.leaves(got_out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unit_model_axis_degenerates_to_prefix_layout():
    """A ("clients", "model") mesh whose model axis has size 1 must
    behave exactly like the 1-D client mesh: cohort_round_shardings
    takes the replicated-prefix branch (templates ignored), and the
    jit'd round equals the meshless one. The REAL per-leaf spec
    threading (model axis > 1) is only reachable with multiple devices
    and is covered by the regime matrix's forced-8-device subprocess
    plus the FakeMesh unit tests in test_sharding.py."""
    from repro.core.baselines import make_algorithm
    from repro.core.round import make_cohort_round

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    r = np.random.RandomState(0)
    params = {"w": jnp.asarray(r.randn(4, 8), jnp.float32),
              "b": jnp.zeros((8,), jnp.float32)}
    algo = make_algorithm("feddpc")
    state = algo.init(params, 4)
    batches = {"x": jnp.asarray(r.randn(3, 2, 5, 4), jnp.float32),
               "y": jnp.asarray(r.randn(3, 2, 5, 8), jnp.float32)}
    masks = jnp.ones((3, 2), bool)
    ids = jnp.arange(3, dtype=jnp.int32)
    mesh = jax.make_mesh((1, 1), ("clients", "model"))
    plain = make_cohort_round(loss_fn, algo, 0.05, 0.1, donate=False)
    two = make_cohort_round(loss_fn, algo, 0.05, 0.1, donate=False,
                            mesh=mesh, shard_templates=(params, state))
    p0, s0, l0, d0 = plain(state, params, batches, masks, ids)
    p1, s1, l1, d1 = two(state, params, batches, masks, ids)
    for a, b in zip(jax.tree.leaves((p0, s0, l0)),
                    jax.tree.leaves((p1, s1, l1))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    assert d0.keys() == d1.keys()


@given(st.integers(1, 9), st.sampled_from(M_CASES),
       st.sampled_from([False, True]),
       st.sampled_from(["int8", "bf16"]))
def test_dequant_batched_epilogue_raw_fuzz(k, m, zero_prev, qdtype):
    """kernel.dequant_batched_epilogue (the fused dequant->residual->
    scale->mean grid, DESIGN.md §13) == dequant-then-epilogue oracle,
    for ragged K x non-block-multiple M x zero delta_prev x both wire
    dtypes."""
    r = np.random.RandomState(k * 1000 + m + int(zero_prev))
    if qdtype == "int8":
        q3 = jnp.asarray(r.randint(-127, 128, (k, m, 128)), jnp.int8)
        qscales = jnp.asarray(0.01 + np.abs(r.randn(k)) * 0.1, jnp.float32)
        qzeros = jnp.asarray(r.randn(k) * 0.01, jnp.float32)
    else:
        q3 = jnp.asarray(r.randn(k, m, 128), jnp.bfloat16)
        qscales = jnp.ones((k,), jnp.float32)
        qzeros = jnp.zeros((k,), jnp.float32)
    p2 = (jnp.zeros((m, 128), jnp.float32) if zero_prev
          else jnp.asarray(r.randn(m, 128), jnp.float32))
    w2 = jnp.asarray(r.randn(m, 128), jnp.float32)
    coefs = jnp.asarray(r.randn(k), jnp.float32)
    scales = jnp.asarray(1.0 + np.abs(r.randn(k)), jnp.float32)
    got_w, got_dt = fp_kernel.dequant_batched_epilogue(
        q3, p2, w2, coefs, scales, 0.3, qscales, qzeros, interpret=True)
    want_w, want_dt = fp_ref.dequant_batched_epilogue_ref(
        q3, p2, w2, coefs, scales, 0.3, qscales, qzeros)
    np.testing.assert_allclose(got_w, want_w, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_dt, want_dt, rtol=2e-5, atol=2e-5)


@given(st.integers(1, 6), st.sampled_from(M_CASES),
       st.sampled_from([False, True]))
def test_dequant_buffer_fold_raw_fuzz(b, m, zero_prev):
    """kernel.dequant_buffer_fold (scatter-accumulate fold with fused
    dequant; staleness weights compose with the dequant scales as plain
    per-arrival multipliers) == the dequant-then-fold oracle."""
    r = np.random.RandomState(b * 31 + m)
    q3 = jnp.asarray(r.randint(-127, 128, (b, m, 128)), jnp.int8)
    qscales = jnp.asarray(0.01 + np.abs(r.randn(b)) * 0.1, jnp.float32)
    qzeros = jnp.asarray(r.randn(b) * 0.01, jnp.float32)
    p2 = (jnp.zeros((m, 128), jnp.float32) if zero_prev
          else jnp.asarray(r.randn(m, 128), jnp.float32))
    w2 = jnp.asarray(r.randn(m, 128), jnp.float32)
    coefs = jnp.asarray(r.randn(b), jnp.float32)
    scales = jnp.asarray(1.0 + np.abs(r.randn(b)), jnp.float32)
    wgts = jnp.asarray(r.uniform(0.2, 1.0, b), jnp.float32)
    got_w, got_dt = fp_kernel.dequant_buffer_fold(
        q3, p2, w2, coefs, scales, wgts, 0.25, qscales, qzeros,
        interpret=True)
    want_w, want_dt = fp_ref.dequant_buffer_fold_ref(
        q3, p2, w2, coefs, scales, wgts, 0.25, qscales, qzeros)
    np.testing.assert_allclose(got_w, want_w, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_dt, want_dt, rtol=2e-5, atol=2e-5)


@given(st.integers(1, 5), st.sampled_from([3, 37, 127, 130]),
       st.sampled_from([False, True]))
def test_dequant_server_epilogue_matches_decode_then_epilogue(k, n, wgt):
    """ops.dequant_*_server_* over a real codec payload (non-lane-
    multiple leaf shapes) == decode the payload with the codec, then the
    plain f32 epilogue/fold — the fused route must be a pure layout
    optimization."""
    from repro.codec import make_codec
    codec = make_codec("int8")
    r = np.random.RandomState(k * 13 + n + int(wgt))
    params = {"a": jnp.asarray(r.randn(n), jnp.float32),
              "b": jnp.asarray(r.randn(4, 11), jnp.float32)}
    deltas = jax.tree.map(
        lambda x: jnp.asarray(r.randn(k, *np.shape(x)), jnp.float32), params)
    payload = codec.encode_cohort(deltas)
    decoded = codec.decode_cohort(payload)
    prev = jax.tree.map(lambda x: x * 0.3, params)
    coefs = jnp.asarray(r.randn(k), jnp.float32)
    scales = jnp.asarray(1.0 + np.abs(r.randn(k)), jnp.float32)
    wgts = (jnp.asarray(r.uniform(0.2, 1.0, k), jnp.float32)
            if wgt else None)
    if wgts is None:
        got = fp_ops.dequant_batched_server_epilogue(
            payload, prev, params, coefs, scales, 0.2, interpret=True)
        want = fp_ops.batched_server_epilogue(
            decoded, prev, params, coefs, scales, 0.2, interpret=True)
    else:
        got = fp_ops.dequant_buffered_server_fold(
            payload, prev, params, coefs, scales, wgts, 0.2,
            interpret=True)
        want = fp_ops.buffered_server_fold(
            decoded, prev, params, coefs, scales, wgts, 0.2,
            interpret=True)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
