"""Staged ingest pipeline (DESIGN.md §10): depth-N determinism, device
staging, the staging ring's semantics, checkpointing with in-flight
staging, and the one-release deprecation shims over the moved modules.

The heavyweight cross-regime equivalence (staged regimes on the forced
8-device mesh) lives in tests/test_regime_matrix.py; the fresh-process
bitwise resume with depth-8 staging lives in tests/test_resume.py. These
are the fast single-process contracts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import AlgoConfig, ExecConfig, FederatedTrainer
from repro.ingest import (CohortIngestPipeline, CohortPlacer,
                          CohortPrefetcher, ListDataSource, stack_cohort)

NUM_CLIENTS = 6
K = 3


def loss_fn(p, batch):
    pred = batch["x"] @ p["w"] + p["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def make_params(seed=0):
    r = np.random.RandomState(seed)
    return {"w": jnp.asarray(r.randn(4, 3), jnp.float32),
            "b": jnp.asarray(r.randn(3), jnp.float32)}


def ragged_batch_fn(c, t):
    r = np.random.RandomState(1000 * c + t)
    return [{"x": r.randn(8, 4).astype(np.float32),
             "y": r.randn(8, 3).astype(np.float32)}
            for _ in range((c % 3) + 1)]


def run_trainer(rounds=5, **exec_kw):
    kw = dict(clients_per_round=K, seed=7, eval_every=10 ** 9)
    kw.update(exec_kw)
    with FederatedTrainer(loss_fn, make_params(), NUM_CLIENTS,
                          ragged_batch_fn, ExecConfig(rounds=rounds, **kw),
                          algo=AlgoConfig(eta_l=0.05, eta_g=0.1)) as tr:
        tr.run()
    return tr


def assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------- depth-N determinism ----------------

def test_depth_n_prefetch_is_bitwise_deterministic():
    """Depths 1..8 x {device-staged, host-staged} all replay the
    blocking path's RNG draws bit for bit: identical schedules, params,
    server state, and per-round losses (the round-order contract)."""
    ref = run_trainer(prefetch=False)
    for depth in (1, 2, 4, 8):
        for device_stage in (True, False):
            tr = run_trainer(prefetch=True, prefetch_depth=depth,
                             device_stage=device_stage)
            label = (depth, device_stage)
            for a, b in zip(ref.schedule, tr.schedule[:len(ref.schedule)]):
                assert (np.asarray(a) == np.asarray(b)).all(), label
            assert_trees_equal(ref.params, tr.params)
            assert_trees_equal(ref.server_state, tr.server_state)
            assert [r.train_loss for r in ref.history] == \
                [r.train_loss for r in tr.history], label


def test_depth_must_be_positive():
    with pytest.raises(ValueError, match="depth"):
        run_trainer(prefetch_depth=0)


# ---------------- ingest wait split ----------------

def test_ingest_seconds_split_sums_and_places():
    """Device-staged rounds pay ~no consumer-side transfer wait (it
    moved to the staging thread), host-staged rounds report a real
    placement component."""
    for device_stage in (True, False):
        tr = run_trainer(prefetch=True, device_stage=device_stage)
        if device_stage:
            assert all(r.ingest_device_seconds == 0.0 for r in tr.history)
    # the blocking path measures placement explicitly too
    tr = run_trainer(prefetch=False)
    assert any(r.ingest_device_seconds > 0.0 for r in tr.history)


def test_round_inputs_arrive_device_placed():
    """With device staging the round's inputs are committed jax arrays
    before dispatch — StagedCohort carries no host numpy leaves."""
    cfg = ExecConfig(rounds=2, clients_per_round=K, seed=0,
                     eval_every=10 ** 9, prefetch=True)
    with FederatedTrainer(loss_fn, make_params(), NUM_CLIENTS,
                          ragged_batch_fn, cfg,
                          algo=AlgoConfig(eta_l=0.05, eta_g=0.1)) as tr:
        staged = tr._pipeline.get(0)
        try:
            for leaf in jax.tree.leaves((staged.batches, staged.masks,
                                         staged.ids)):
                assert isinstance(leaf, jax.Array), type(leaf)
        finally:
            staged.release()


# ---------------- staging-ring semantics ----------------

def test_ring_depth_one_single_buffers():
    produced = []

    def produce(t, slot):
        produced.append(t)
        return t

    ring = CohortPrefetcher(produce, 0, 4, slots=1)
    try:
        for t in range(4):
            item, slot = ring.get(t)
            assert item == t
            ring.release(slot)
    finally:
        ring.stop()
    assert produced == [0, 1, 2, 3]


def test_ring_horizon_and_out_of_order_errors():
    ring = CohortPrefetcher(lambda t, slot: t, 0, 3, slots=2)
    try:
        with pytest.raises(RuntimeError, match="horizon"):
            ring.get(3)
        _, slot = ring.get(0)
        ring.release(slot)
        with pytest.raises(RuntimeError, match="sequential"):
            ring.get(2)
    finally:
        ring.stop()


def test_ring_stall_deadline_raises_instead_of_spinning():
    """A producer hung inside produce_fn (alive thread, nothing staged)
    used to spin get() forever — the 1s dead-thread poll only escaped
    on a DEAD producer. With a stall deadline the consumer raises,
    naming the stuck round (regression)."""
    import threading
    import time
    hang = threading.Event()

    def produce(t, slot):
        if t == 1:
            hang.wait(timeout=30)       # simulate a deadlocked source read
        return t

    ring = CohortPrefetcher(produce, 0, 4, slots=1, stall_timeout=0.3)
    try:
        item, slot = ring.get(0)
        assert item == 0
        ring.release(slot)
        tic = time.monotonic()
        with pytest.raises(RuntimeError, match="round 1"):
            ring.get(1)
        assert time.monotonic() - tic < 5.0     # bounded, not the 30s hang
        assert ring._thread.is_alive()          # the ALIVE-but-stuck case
    finally:
        hang.set()                              # unstick before join
        ring.stop()


def test_ring_stop_joins_producer_and_drains_staged_slots():
    """stop() must JOIN the producer (not just drop a sentinel) and
    drain staged-but-unconsumed items so their buffers aren't pinned by
    the dead ring (regression: stop() returned with the producer still
    mid-produce and _ready still holding staged rounds)."""
    staged = []

    def produce(t, slot):
        staged.append(t)
        return ("payload", t)

    ring = CohortPrefetcher(produce, 0, 100, slots=4)
    item, slot = ring.get(0)                    # let the producer spin up
    ring.release(slot)
    ring.stop()
    assert not ring._thread.is_alive()          # joined, not abandoned
    assert ring._ready.empty()                  # staged work was drained
    assert len(staged) <= 100
    ring.stop()                                 # idempotent


def test_trainer_threads_stall_deadline_to_the_ring():
    """ExecConfig.ingest_stall_s reaches the staging ring: a source that
    blocks forever on a mid-run round surfaces as the stall error
    instead of hanging the round loop."""
    import threading
    hang = threading.Event()

    def hanging_batch_fn(c, t):
        if t == 2:
            hang.wait(timeout=30)
        return ragged_batch_fn(c, t)

    cfg = ExecConfig(rounds=4, clients_per_round=K, seed=3,
                     eval_every=10 ** 9, prefetch=True,
                     ingest_stall_s=0.3)
    try:
        with FederatedTrainer(loss_fn, make_params(), NUM_CLIENTS,
                              hanging_batch_fn, cfg,
                              algo=AlgoConfig(eta_l=0.05, eta_g=0.1)) as tr:
            tr.run_round(0)
            tr.run_round(1)
            with pytest.raises(RuntimeError, match="stall"):
                tr.run_round(2)
    finally:
        hang.set()


def test_staged_cohort_release_is_idempotent():
    src = ListDataSource(ragged_batch_fn)
    pipe = CohortIngestPipeline(
        src, lambda t: np.asarray([0, 1, 2]), num_clients=NUM_CLIENTS,
        rounds=3, depth=2, device_stage=True, placer=CohortPlacer())
    try:
        staged = pipe.get(0)
        staged.release()
        staged.release()                    # second release is a no-op
        staged2 = pipe.get(1)
        staged2.release()
    finally:
        pipe.close()


def test_blocking_stage_matches_ring_values():
    """stage_blocking and the ring stage the same bytes for a round."""
    sample = lambda t: np.asarray([2, 0, 4])
    src = ListDataSource(ragged_batch_fn)
    a = CohortIngestPipeline(src, sample, num_clients=NUM_CLIENTS,
                             rounds=2, depth=2, device_stage=True,
                             placer=CohortPlacer())
    b = CohortIngestPipeline(src, sample, num_clients=NUM_CLIENTS,
                             rounds=2, depth=2, device_stage=True,
                             placer=CohortPlacer())
    try:
        s1 = a.get(0)
        s2 = b.stage_blocking(0)
        assert_trees_equal(s1.batches, s2.batches)
        np.testing.assert_array_equal(np.asarray(s1.masks),
                                      np.asarray(s2.masks))
        np.testing.assert_array_equal(np.asarray(s1.ids),
                                      np.asarray(s2.ids))
        s1.release()
    finally:
        a.close()
        b.close()


# ---------------- checkpointing with in-flight staging ----------------

def test_save_restore_with_deep_inflight_staging(tmp_path):
    """At depth 8 with 6 rounds the producer stages (and SAMPLES) every
    remaining round the moment the run starts; save() after round 2 must
    roll RNG/sampler/schedule back to round 3's pre-draw capture so the
    resumed run re-draws the staged-but-unconsumed rounds identically.
    (The fresh-process bitwise version is tests/test_resume.py.)"""
    ec = ExecConfig(rounds=6, clients_per_round=K, seed=11,
                    eval_every=10 ** 9, prefetch=True, prefetch_depth=8)
    ac = AlgoConfig(name="feddpc", eta_l=0.05, eta_g=0.1)
    with FederatedTrainer(loss_fn, make_params(), NUM_CLIENTS,
                          ragged_batch_fn, ec, algo=ac) as full:
        full.run()
    with FederatedTrainer(loss_fn, make_params(), NUM_CLIENTS,
                          ragged_batch_fn, ec, algo=ac) as part:
        part.run_round(0)
        part.run_round(1)
        part.run_round(2)
        part.save(str(tmp_path))
    res = FederatedTrainer.resume(str(tmp_path), loss_fn, make_params(),
                                  NUM_CLIENTS, ragged_batch_fn, ec, algo=ac)
    with res:
        assert res.start_round == 3
        res.run()
    assert_trees_equal(full.params, res.params)
    assert_trees_equal(full.server_state, res.server_state)
    assert [r.train_loss for r in full.history] == \
        [r.train_loss for r in res.history]
    for a, b in zip(full.schedule, res.schedule):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_resume_reads_a_history_with_ingest_seconds(tmp_path):
    """A checkpoint written before RoundRecord lost ``ingest_seconds``
    (and before it gained the span durations and counters) still
    resumes: the old key is dropped, the new fields take defaults."""
    import glob
    import hashlib
    import json
    import os
    ec = ExecConfig(rounds=4, clients_per_round=K, seed=11,
                    eval_every=10 ** 9)
    ac = AlgoConfig(name="feddpc", eta_l=0.05, eta_g=0.1)
    with FederatedTrainer(loss_fn, make_params(), NUM_CLIENTS,
                          ragged_batch_fn, ec, algo=ac) as part:
        part.run_round(0)
        part.run_round(1)
        part.save(str(tmp_path))
    step = sorted(glob.glob(os.path.join(str(tmp_path), "step_*")))[-1]
    aux = os.path.join(step, "aux.json")
    with open(aux) as f:
        meta = json.load(f)
    new = ("ingest_stage_seconds", "ingest_place_seconds", "sync_seconds",
           "ingest_ready", "steps_useful", "steps_computed")
    for r in meta["history"]:
        r["ingest_seconds"] = (r["ingest_host_seconds"]
                               + r["ingest_device_seconds"])
        for k in new:
            del r[k]
    with open(aux, "w") as f:
        json.dump(meta, f)
    manifest = os.path.join(step, "manifest.json")
    with open(manifest) as f:
        man = json.load(f)
    with open(aux, "rb") as f:
        man["digests"]["aux.json"] = hashlib.sha256(f.read()).hexdigest()
    with open(manifest, "w") as f:
        json.dump(man, f)
    res = FederatedTrainer.resume(str(tmp_path), loss_fn, make_params(),
                                  NUM_CLIENTS, ragged_batch_fn, ec, algo=ac)
    with res:
        assert [r.train_loss for r in res.history] == \
            [r.train_loss for r in part.history]
        assert all(r.steps_useful == 0 and r.sync_seconds == 0.0
                   for r in res.history)
        res.run()
    assert [r.round for r in res.history] == [0, 1, 2, 3]
    assert res.history[-1].steps_useful > 0


def test_stack_cohort_reexport_identical():
    """stack_cohort via repro.ingest is the one the trainer uses (no
    forked copies): same padding semantics as before the move."""
    lists = [ragged_batch_fn(c, 0) for c in range(K)]
    mx = max(len(b) for b in lists)
    b, m = stack_cohort(lists, mx, pad_to=5)
    assert m.shape == (5, mx) and not m[K:].any()
