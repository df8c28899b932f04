"""Index ingest (DESIGN.md §10): a source that offers its sample table
has the table placed on the device once and each round staged as sample
indices, which the round program gathers. Three rounds give the pixel
path's params; sources without a table stay on pixels; the record says
how many bytes each round placed."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import AlgoConfig, ExecConfig, FederatedTrainer
from repro.ingest import (CIFAR10Source, IteratorDataSource, ListDataSource,
                          StreamingImageSource, build_federated_image_data)
from repro.ingest.stack import IndexedBatches

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "data")
NUM_CLIENTS, K, B = 8, 4, 8


def loss_fn(p, batch):
    x = batch["images"].reshape(batch["images"].shape[0], -1)
    y = jax.nn.one_hot(batch["labels"], 10)
    return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)


def make_params(dim=32 * 32 * 3):
    r = np.random.RandomState(0)
    return {"w": jnp.asarray(0.01 * r.randn(dim, 10), jnp.float32),
            "b": jnp.zeros((10,), jnp.float32)}


@pytest.fixture(scope="module")
def source():
    data = build_federated_image_data(num_classes=10,
                                      num_clients=NUM_CLIENTS, alpha=0.3,
                                      samples_per_class=12, seed=1)
    return StreamingImageSource(data, B)


def run(source, params=None, **exec_kw):
    cfg = ExecConfig(rounds=3, clients_per_round=K, seed=3,
                     eval_every=10 ** 9, **exec_kw)
    with FederatedTrainer(loss_fn, make_params() if params is None
                          else params, NUM_CLIENTS, source, cfg,
                          algo=AlgoConfig(eta_l=0.05, eta_g=0.1)) as tr:
        seen = []
        inner = tr._cohort_round

        def spy(*args):
            seen.append(args[2])
            return inner(*args)

        tr._cohort_round = spy
        recs = tr.run()
        return jax.device_get(tr.params), recs, tr._pipeline.indexed, seen


def test_selections_are_the_batches(source):
    table = source.sample_table()
    for c in range(NUM_CLIENTS):
        sels = source.client_selections(c, 2)
        batches = list(source.client_batches(c, 2))
        assert len(sels) == len(batches)
        for sel, b in zip(sels, batches):
            assert sel.dtype == np.int32 and sel.shape == (B,)
            for key in ("images", "labels"):
                np.testing.assert_array_equal(table[key][sel], b[key])


@pytest.mark.parametrize("prefetch", [True, False])
def test_index_path_equals_the_pixel_path(source, prefetch):
    p_idx, r_idx, indexed, seen = run(source, prefetch=prefetch)
    assert indexed
    assert all(isinstance(b, IndexedBatches) for b in seen)
    pixels = ListDataSource(lambda c, t: list(source.client_batches(c, t)))
    p_pix, r_pix, indexed_pix, seen_pix = run(pixels, prefetch=prefetch)
    assert not indexed_pix
    assert not any(isinstance(b, IndexedBatches) for b in seen_pix)
    # the same batch values, gathered on the device: the same rounds
    for a, b in zip(jax.tree.leaves(p_idx), jax.tree.leaves(p_pix)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose([r.train_loss for r in r_idx],
                               [r.train_loss for r in r_pix], rtol=1e-6)
    for ri, rp in zip(r_idx, r_pix):
        m = ri.steps_computed // K
        assert ri.steps_computed == rp.steps_computed
        # indices, mask and ids on the index path; the pixel slot else
        assert ri.ingest_placed_bytes == K * m * B * 4 + K * m + K * 4
        assert rp.ingest_placed_bytes == (K * m * B * (32 * 32 * 3 * 4 + 4)
                                          + K * m + K * 4)


def test_sources_without_a_table_stay_on_pixels(source):
    iterating = IteratorDataSource(lambda c, t: source.client_batches(c, t))
    _, recs, indexed, seen = run(iterating)
    assert not indexed
    assert all(isinstance(b, dict) for b in seen)
    m = recs[0].steps_computed // K
    assert recs[0].ingest_placed_bytes == (K * m * B * (32 * 32 * 3 * 4 + 4)
                                           + K * m + K * 4)


@pytest.mark.parametrize("augment", [False, True])
def test_disk_backed_sources_stay_on_pixels(augment):
    disk = CIFAR10Source(FIXTURES, num_clients=4, alpha=1.0, batch_size=B,
                         augment=augment, seed=0, min_size=2)
    assert disk.sample_table() is None
    cfg = ExecConfig(rounds=2, clients_per_round=2, seed=0,
                     eval_every=10 ** 9)
    with FederatedTrainer(loss_fn, make_params(), 4, disk, cfg,
                          algo=AlgoConfig(eta_l=0.05, eta_g=0.1)) as tr:
        recs = tr.run()
        assert not tr._pipeline.indexed
    assert recs[0].ingest_placed_bytes > recs[0].steps_computed * B * 3072


def test_serial_and_sharded_trainers_stay_on_pixels(source):
    """The per-client reference path reads pixels and never places the
    table; a round on a mesh (one device here is enough to take it)
    stages pixels."""
    cfg = ExecConfig(rounds=2, clients_per_round=K, seed=3,
                     eval_every=10 ** 9, vectorize=False)
    with FederatedTrainer(loss_fn, make_params(), NUM_CLIENTS, source, cfg,
                          algo=AlgoConfig(eta_l=0.05, eta_g=0.1)) as tr:
        tr.run()
        assert tr._pipeline._table is None
    _, _, indexed, seen = run(source, shard_clients=True)
    assert not indexed
    assert all(isinstance(b, dict) for b in seen)
