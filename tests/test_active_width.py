"""The active-width local scan (core/client.py, DESIGN.md §2) against
the full-width masked scan it replaces on an unsharded client axis:
the same inputs give the same deltas and losses, for every client
variant and local optimizer, in the fused sync round and in a buffered-
async wave; and the host's count of the client-steps it computes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import client as client_mod
from repro.core.api import AlgoConfig, ExecConfig, FederatedTrainer
from repro.core.client import (chunk_width, make_cohort_local_update,
                               step_width, steps_run)
from repro.ingest import stack_cohort

NUM_CLIENTS = 8


def loss_fn(p, batch):
    pred = batch["x"] @ p["w"] + p["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def make_params(seed=0):
    r = np.random.RandomState(seed)
    return {"w": jnp.asarray(r.randn(4, 3), jnp.float32),
            "b": jnp.asarray(r.randn(3), jnp.float32)}


def batches_of(c, t, n):
    r = np.random.RandomState(1000 * c + t)
    return [{"x": r.randn(8, 4).astype(np.float32),
             "y": r.randn(8, 3).astype(np.float32)} for _ in range(n)]


def assert_close(a, b, rtol=1e-5, atol=1e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# client step counts of each cohort: ragged with a zero-data client; a
# bucket of 5 whose trailing steps are empty; one client alone at the
# last step; every client at every step
COHORTS = {
    "ragged_zero_data": ([1, 0, 3, 2, 1, 1, 2], 3),
    "empty_trailing_steps": ([2, 1, 2, 1], 5),
    "one_client_at_the_end": ([1, 1, 1, 1, 1, 4], 4),
    "nothing_to_skip": ([3, 3, 3, 3], 3),
}


def cohort_inputs(counts, m, t=0):
    lists = [batches_of(c, t, n) for c, n in enumerate(counts)]
    return stack_cohort(lists, m)


@pytest.mark.parametrize("cohort", sorted(COHORTS))
@pytest.mark.parametrize("variant", ["plain", "prox", "cm", "ga"])
@pytest.mark.parametrize("optimizer", ["sgd", "momentum"])
def test_active_width_equals_full_width(cohort, variant, optimizer):
    counts, m = COHORTS[cohort]
    batches, masks = cohort_inputs(counts, m)
    params = make_params()
    extra = (None if variant in ("plain", "prox")
             else jax.tree.map(lambda x: 0.3 * x, make_params(1)))
    kw = dict(variant=variant, optimizer=optimizer, jit=True)
    d_act, l_act = make_cohort_local_update(loss_fn, 0.05, **kw)(
        params, batches, masks, extra)
    d_full, l_full = make_cohort_local_update(
        loss_fn, 0.05, clients_sharded=True, **kw)(
            params, batches, masks, extra)
    assert_close(d_act, d_full)
    np.testing.assert_allclose(np.asarray(l_act), np.asarray(l_full),
                               rtol=1e-5, atol=1e-7)
    # a zero-data client keeps its start (the global model, or FedGA's
    # displaced one: a zero delta but there) and a zero loss
    for j, n in enumerate(counts):
        if n == 0:
            assert float(l_act[j]) == 0.0
            if variant != "ga":
                assert all(float(jnp.abs(x[j]).max()) == 0.0
                           for x in jax.tree.leaves(d_act))


def test_step_widths_and_count():
    assert [chunk_width(k) for k in (10, 7, 1)] == [5, 4, 1]
    assert [step_width(n, 10) for n in (0, 1, 5, 6, 10)] == [0, 5, 5, 10, 10]
    # an odd K's two chunks overlap by one row, masked in the second
    assert [step_width(n, 7) for n in (0, 4, 5, 7)] == [0, 4, 8, 8]
    # per step of "ragged_zero_data": 6, 3, 1 active of 7 -> 8 + 4 + 4
    _, masks = cohort_inputs(*COHORTS["ragged_zero_data"])
    assert steps_run(masks) == 16
    _, masks = cohort_inputs(*COHORTS["empty_trailing_steps"])
    assert steps_run(masks) == 4 + 2          # steps 3-5 are skipped
    _, masks = cohort_inputs(*COHORTS["nothing_to_skip"])
    assert steps_run(masks) == masks.size


def _full_width(monkeypatch):
    """The trainer built with the full-width masked scan, as on a client
    axis sharded over chips."""
    real = client_mod.make_cohort_local_update
    monkeypatch.setattr(client_mod, "make_cohort_local_update",
                        lambda *a, **k: real(*a, **{**k,
                                                    "clients_sharded": True}))


def _trainer(batch_fn, **exec_kw):
    cfg = ExecConfig(rounds=3, clients_per_round=4, seed=5,
                     eval_every=10 ** 9, **exec_kw)
    return FederatedTrainer(loss_fn, make_params(), NUM_CLIENTS, batch_fn,
                            cfg, algo=AlgoConfig(name="feddpc", eta_l=0.05,
                                                 eta_g=0.1))


def ragged_fn(c, t):
    return batches_of(c, t, (c % 4))        # 0-3 steps, zero-data too


@pytest.mark.parametrize("exec_kw", [{}, {"async_buffer": True}],
                         ids=["sync", "async_wave"])
def test_trainer_rounds_equal_the_full_width_scan(monkeypatch, exec_kw):
    with _trainer(ragged_fn, **exec_kw) as tr:
        recs = tr.run()
        active = jax.device_get(tr.params), [r.train_loss for r in recs]
    _full_width(monkeypatch)
    with _trainer(ragged_fn, **exec_kw) as tr:
        recs_full = tr.run()
        full = jax.device_get(tr.params), [r.train_loss for r in recs_full]
    assert_close(active[0], full[0])
    np.testing.assert_allclose(active[1], full[1], rtol=1e-5)
    if not exec_kw:
        # every ragged round skips some of the bucket's client-steps
        assert all(r.steps_useful <= r.steps_run < r.steps_computed
                   for r in recs)


def test_nothing_to_skip_runs_at_full_width():
    """Every client has M steps: every step is computed at width K and
    the record's run count is the staged mask's size."""
    with _trainer(lambda c, t: batches_of(c, t, 3)) as tr:
        recs = tr.run()
    assert all(r.steps_run == r.steps_computed == 4 * 3 for r in recs)
    assert all(r.steps_useful == r.steps_run for r in recs)
