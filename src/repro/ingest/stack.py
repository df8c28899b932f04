"""Cohort-stack stage of the ingest pipeline (DESIGN.md §10; moved here
from core/client.py, which keeps deprecated shims for one release).

``stack_batches``/``stack_cohort`` build the padded (K, M, ...) cohort
stack the fused round consumes; ``stack_cohort_into`` does the same into
preallocated buffers owned by a staging-ring slot, so the per-round
np.stack allocations disappear from the ingest path.

``IndexedBatches`` is the index form of a cohort stack: the source's
sample table, placed on the device once, and the (K, M, B) int32 sample
indices the same stacking stage builds from each client's batch
selections; the round program gathers the batches (DESIGN.md §10).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import numpy as np


class IndexedBatches(NamedTuple):
    """A cohort's batches as sample indices into a device table:
    batch (k, s) is ``{key: table[key][idx[k, s]]}``."""
    table: Any      # {key: (N, ...)} device arrays, placed once
    idx: Any        # (K, M, B) int32


def stack_batches(batch_list, max_batches: int, template=None):
    """Pad a list of same-shape batch pytrees to (max_batches, ...) + mask.

    ``template`` (one batch pytree, shapes/dtypes only) makes an EMPTY
    list stackable: a zero-data client contributes an all-zero stack
    with an all-False mask (the local scan no-ops, delta == 0) while
    still counting as a sampled client for the server rules."""
    n = len(batch_list)
    assert n <= max_batches, (n, max_batches)
    if n == 0:
        if template is None:
            raise ValueError("stack_batches of an empty batch list needs a "
                             "template batch for the leaf shapes")
        stacked = jax.tree.map(
            lambda x: np.zeros((max_batches,) + np.shape(x),
                               np.asarray(x).dtype), template)
        return stacked, np.zeros(max_batches, bool)
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batch_list)
    if n < max_batches:
        pad = max_batches - n
        stacked = jax.tree.map(
            lambda x: np.concatenate(
                [x, np.repeat(x[-1:], pad, axis=0)], axis=0), stacked)
    mask = np.arange(max_batches) < n
    return stacked, mask


def stack_cohort(per_client_batches, max_batches: int, pad_to: int = None):
    """Stack K clients' batch lists into one (K, M, ...) pytree + (K, M)
    mask — the input of ``make_cohort_local_update``. M = max_batches is
    the shape bucket; ragged clients pad with masked repeats.

    ``pad_to`` > K appends DUMMY clients (copies of the last real row
    with an all-False mask row) so uneven cohorts shard over a client
    axis whose size does not divide K (DESIGN.md §2): a fully-masked
    client runs a no-op local scan (delta == 0) and the server rules
    exclude it from every mean via the derived client validity mask.
    """
    template = next((b[0] for b in per_client_batches if b), None)
    if template is None:
        raise ValueError("every client in the cohort has zero batches")
    pairs = [stack_batches(b, max_batches, template=template)
             for b in per_client_batches]
    batches = jax.tree.map(lambda *xs: np.stack(xs), *[p[0] for p in pairs])
    masks = np.stack([p[1] for p in pairs])
    k = len(per_client_batches)
    if pad_to is not None and pad_to > k:
        pad = pad_to - k
        batches = jax.tree.map(
            lambda x: np.concatenate(
                [x, np.repeat(x[-1:], pad, axis=0)], axis=0), batches)
        masks = np.concatenate(
            [masks, np.zeros((pad,) + masks.shape[1:], bool)], axis=0)
    return batches, masks


def stack_cohort_into(per_client_batches, max_batches: int, slot: dict,
                      pad_to: int = None):
    """``stack_cohort`` into PREALLOCATED host buffers (DESIGN.md §10).

    ``slot`` is a mutable dict owned by the caller (one per staging-ring
    buffer): its (K, M, ...) arrays + (K, M) mask are allocated on first
    use and reused every round — reallocation happens only when the
    cohort shape grows/changes (grow-once M bucketing keeps that rare),
    so the per-round np.stack allocations disappear from the ingest path.
    Returns (batches_pytree, mask) views backed by the slot's buffers;
    they stay valid until the slot is refilled.

    ``pad_to`` appends dummy clients exactly as ``stack_cohort`` does
    (copies of the last real row, all-False mask rows).
    """
    k, m = len(per_client_batches), max_batches
    kp = k if pad_to is None else max(pad_to, k)
    first = next((b for b in per_client_batches if b), None)
    if first is None:
        raise ValueError("every client in the cohort has zero batches")
    leaves0, treedef = jax.tree_util.tree_flatten(first[0])
    shapes = tuple((np.shape(x), np.asarray(x).dtype) for x in leaves0)
    key = (kp, m, treedef, shapes)
    if slot.get("key") != key:
        slot["key"] = key
        slot["bufs"] = [np.empty((kp, m) + s, dt) for s, dt in shapes]
        slot["mask"] = np.empty((kp, m), bool)
    bufs, mask = slot["bufs"], slot["mask"]
    for j, blist in enumerate(per_client_batches):
        n = len(blist)
        assert n <= m, (n, m)
        if n == 0:                      # zero-data client: all-zero rows,
            for buf in bufs:            # fully masked — still a SAMPLED
                buf[j] = 0              # client (see core/round.py)
            mask[j] = False
            continue
        for i, b in enumerate(blist):
            for buf, x in zip(bufs, jax.tree_util.tree_flatten(b)[0]):
                buf[j, i] = x
        if n < m:                       # ragged: pad with masked repeats
            for buf in bufs:
                buf[j, n:] = buf[j, n - 1]
        mask[j] = np.arange(m) < n
    for j in range(k, kp):              # dummy clients: masked copies
        for buf in bufs:
            buf[j] = buf[k - 1]
        mask[j] = False
    return jax.tree_util.tree_unflatten(treedef, bufs), mask
