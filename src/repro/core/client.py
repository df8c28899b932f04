"""Client-side local training (paper Algorithm 1, lines 4-13).

``make_local_update`` builds ONE jit-compiled function reused for every
client and round: it scans over a fixed-shape stack of minibatches with a
validity mask (clients with fewer samples than the stack pad with masked
batches), runs the local optimizer, and returns the pseudo-gradient

    Delta_j = (w_{t-1} - w_j) / eta_l                    (line 12)

``make_cohort_local_update`` is the same program over a leading client
axis: batches (K, M, ...), masks (K, M) -> deltas stacked (K, ...) plus
per-client mean losses (K,). The global params and the server-side
``extra`` state (Delta_prev for the cm/ga variants) are broadcast to every
client; the mask axis is per-client, so ragged cohorts (clients with
different minibatch counts) are handled by padding to the cohort max.

With a mask and an unsharded client axis it runs the ACTIVE-WIDTH scan
(DESIGN.md §2): step s computes only as many client rows as it needs, in
chunks of ceil(K/2) rows, active clients first — no chunk when no client
has a batch at s (the step is skipped), one when at most ceil(K/2) do,
else two (all K rows for an even K). A chunk's rows are gathered from
the (K, ...) carry, stepped vmapped, and written back with the same keep
rule as the masked scan, so every unmasked step of every client is
computed exactly once and the deltas come out in the cohort's order.
One chunk body serves every width, so the step is compiled once.
``step_width`` / ``steps_run`` give the same widths on the host. A
client axis sharded over chips keeps the full-width vmapped masked scan
(a row gather there would move weights between chips), and
``masks=None`` streams keep the select-free scan.

Batches are pixels, or an ``IndexedBatches`` (ingest/stack.py): a
device-resident sample table plus (K, M, B) int32 sample indices, which
the scan gathers step by step into the same batch values (DESIGN.md §10).

Client variants (selected by the server algorithm):
  plain  SGD/momentum/AdamW on the local loss
  prox   FedProx: + mu/2 ||w - w_global||^2 added to every local gradient
  cm     FedCM:   g <- alpha*g + (1-alpha)*Delta_prev  (client momentum)
  ga     FedGA:   local model initialized at w - beta*eta_l*Delta_prev

The host-ingest helpers that used to live here (``stack_batches`` /
``stack_cohort`` / ``stack_cohort_into`` / ``CohortPrefetcher``) live in
the staged ingest subsystem — ``repro.ingest`` (DESIGN.md §10); the
one-release deprecation aliases are gone.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.ingest.stack import IndexedBatches
from repro.optim.optimizers import Optimizer, get_optimizer

PyTree = Any


def chunk_width(k: int) -> int:
    """Client rows the active-width scan steps at a time for a K-row
    cohort: ceil(K/2)."""
    return -(-k // 2)


def step_width(active: int, k: int) -> int:
    """Rows the active-width scan computes for a step with ``active``
    unmasked clients of K: 0 (the step is skipped), one chunk of
    ceil(K/2), or two (K rows for an even K)."""
    w = chunk_width(k)
    return -(-active // w) * w


def steps_run(masks) -> int:
    """Client-steps the active-width scan computes for a (K, M) mask."""
    masks = np.asarray(masks)
    k = masks.shape[0]
    return sum(step_width(int(n), k) for n in masks.sum(axis=0))


def _build_local_step(loss_fn: Callable[[PyTree, PyTree], jnp.ndarray],
                      eta_l: float, variant: str, optimizer: str,
                      mu: float, cm_alpha: float, ga_beta: float):
    """(init, train_step, opt) for one client: ``init(global_params,
    extra)`` is the local model's start, ``train_step(params, opt_state,
    i, batch, global_params, extra) -> (loss, params', opt_state')`` one
    unmasked local step."""
    opt: Optimizer = get_optimizer(optimizer, eta_l)

    def init(global_params, extra):
        if variant == "ga" and extra is not None:
            # displacement init along the previous global direction
            return jax.tree.map(
                lambda w, d: (w.astype(jnp.float32)
                              - ga_beta * eta_l * d.astype(jnp.float32)
                              ).astype(w.dtype), global_params, extra)
        return global_params

    def train_step(params, opt_state, i, batch, global_params, extra):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if variant == "prox":
            grads = jax.tree.map(
                lambda g, p, p0: g + mu * (p.astype(jnp.float32)
                                           - p0.astype(jnp.float32)),
                grads, params, global_params)
        if variant == "cm" and extra is not None:
            grads = jax.tree.map(
                lambda g, d: cm_alpha * g
                + (1.0 - cm_alpha) * d.astype(g.dtype), grads, extra)
        updates, new_opt_state = opt.update(grads, opt_state, params, i)
        new_params = jax.tree.map(lambda p, u: (p - u).astype(p.dtype),
                                  params, updates)
        return loss, new_params, new_opt_state

    return init, train_step, opt


def _delta(global_params, w, eta_l):
    return jax.tree.map(
        lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32))
        / eta_l, global_params, w)


def _build_local_update(loss_fn: Callable[[PyTree, PyTree], jnp.ndarray],
                        eta_l: float, variant: str, optimizer: str,
                        mu: float, cm_alpha: float, ga_beta: float):
    """Raw (un-jitted) per-client local update; shared by the serial and
    the full-width cohort paths."""
    init, train_step, opt = _build_local_step(
        loss_fn, eta_l, variant, optimizer, mu, cm_alpha, ga_beta)

    def local_update(global_params, batches, mask, extra):
        w0 = init(global_params, extra)

        def step(carry, xs):
            params, opt_state, i, loss_sum, nvalid = carry
            if mask is None:            # fixed-shape stream: no select pass
                batch, valid = xs, None
            else:
                batch, valid = xs
            loss, new_params, new_opt_state = train_step(
                params, opt_state, i, batch, global_params, extra)
            if valid is None:
                params, opt_state = new_params, new_opt_state
                loss_sum = loss_sum + loss
                nvalid = nvalid + 1.0
            else:
                # masked batches are no-ops
                keep = lambda new, old: jax.tree.map(
                    lambda a, b: jnp.where(valid, a, b), new, old)
                params = keep(new_params, params)
                opt_state = keep(new_opt_state, opt_state)
                loss_sum = loss_sum + jnp.where(valid, loss, 0.0)
                nvalid = nvalid + valid.astype(jnp.float32)
            return (params, opt_state, i + 1, loss_sum, nvalid), None

        m = jax.tree.leaves(batches)[0].shape[0]
        carry0 = (w0, opt.init(w0), jnp.zeros((), jnp.int32),
                  jnp.zeros(()), jnp.zeros(()))
        (w, _, _, loss_sum, nvalid), _ = jax.lax.scan(
            step, carry0, batches if mask is None else (batches, mask),
            length=m)
        return _delta(global_params, w, eta_l), \
            loss_sum / jnp.maximum(nvalid, 1.0)

    return local_update


def _build_active_width_update(loss_fn: Callable[[PyTree, PyTree],
                                                 jnp.ndarray],
                               eta_l: float, variant: str, optimizer: str,
                               mu: float, cm_alpha: float, ga_beta: float):
    """Raw cohort local update over (K, M) masks that computes each step
    at the width its active clients need (module docstring)."""
    init, train_step, opt = _build_local_step(
        loss_fn, eta_l, variant, optimizer, mu, cm_alpha, ga_beta)
    vstep = jax.vmap(train_step, in_axes=(0, 0, None, 0, None, None))

    def cohort(global_params, batches, masks, extra):
        table = None
        if isinstance(batches, IndexedBatches):
            table, batches = batches.table, batches.idx
        # host arrays index by a traced step below: make them jax values
        batches, masks = jax.tree.map(jnp.asarray, (batches, masks))
        k, m = masks.shape
        w = chunk_width(k)
        w0 = init(global_params, extra)
        rows = lambda x: jnp.broadcast_to(x, (k,) + jnp.shape(x))

        def step(carry, s):
            valid = masks[:, s]
            # active clients first (stable), then the inactive rows
            order = jnp.argsort((~valid).astype(jnp.int32), stable=True)

            def chunk(j, carry):
                params, opt_state, loss_sum, nvalid = carry
                # an odd K's second chunk overlaps the first: the rows
                # the first already stepped are masked in the second
                first = jnp.minimum(j * w, k - w)
                pos = first + jnp.arange(w)
                sel = order[pos]
                v = valid[sel] & (pos >= j * w)
                take = lambda t: jax.tree.map(lambda x: x[sel], t)
                batch = jax.tree.map(lambda x: x[sel, s], batches)
                if table is not None:
                    batch = jax.tree.map(lambda x: x[batch], table)
                p, o = take(params), take(opt_state)
                loss, new_p, new_o = vstep(p, o, s, batch, global_params,
                                           extra)
                # the masked scan's keep rule, row by row
                keep = lambda new, old: jax.tree.map(
                    lambda a, b: jnp.where(
                        v.reshape((-1,) + (1,) * (a.ndim - 1)), a, b),
                    new, old)
                put = lambda full, part: jax.tree.map(
                    lambda x, r: x.at[sel].set(r, unique_indices=True),
                    full, part)
                return (put(params, keep(new_p, p)),
                        put(opt_state, keep(new_o, o)),
                        loss_sum.at[sel].add(jnp.where(v, loss, 0.0),
                                             unique_indices=True),
                        nvalid.at[sel].add(v.astype(jnp.float32),
                                           unique_indices=True))

            # no chunk for a step no client has, one for up to w active
            # clients, two for more
            chunks = (valid.sum() + w - 1) // w
            return jax.lax.fori_loop(0, chunks, chunk, carry), None

        carry0 = (jax.tree.map(rows, w0), jax.tree.map(rows, opt.init(w0)),
                  jnp.zeros((k,)), jnp.zeros((k,)))
        (w_end, _, loss_sum, nvalid), _ = jax.lax.scan(
            step, carry0, jnp.arange(m, dtype=jnp.int32))
        return jax.vmap(_delta, in_axes=(None, 0, None))(
            global_params, w_end, eta_l), loss_sum / jnp.maximum(nvalid, 1.0)

    return cohort


def make_local_update(loss_fn: Callable[[PyTree, PyTree], jnp.ndarray],
                      eta_l: float,
                      variant: str = "plain",
                      optimizer: str = "sgd",
                      mu: float = 0.01,
                      cm_alpha: float = 0.1,
                      ga_beta: float = 0.1,
                      jit: bool = True):
    """Returns fn(global_params, batches, mask, extra) ->
    (delta, mean_loss)  where batches is a pytree with leading axis M
    (minibatch stack), mask (M,) bool, extra = Delta_prev or None.
    mask=None means every batch is valid AND skips the masked-select pass
    over the parameters entirely (the mesh path's fixed-shape streams).
    """
    fn = _build_local_update(loss_fn, eta_l, variant, optimizer,
                             mu, cm_alpha, ga_beta)
    return jax.jit(fn) if jit else fn


def make_cohort_local_update(loss_fn: Callable[[PyTree, PyTree], jnp.ndarray],
                             eta_l: float,
                             variant: str = "plain",
                             optimizer: str = "sgd",
                             mu: float = 0.01,
                             cm_alpha: float = 0.1,
                             ga_beta: float = 0.1,
                             jit: bool = False,
                             clients_sharded: bool = False):
    """Cohort local training: fn(global_params, batches, masks, extra) ->
    (deltas, losses) with batches (K, M, ...), masks (K, M) or None (all
    valid, select-free), deltas client-stacked (K, ...), losses (K,).
    params/extra broadcast.

    With masks the active-width scan runs, and takes an
    ``IndexedBatches`` too, unless ``clients_sharded`` (the K axis is
    partitioned over chips): then, as without masks, the per-client scan
    is vmapped over all K rows."""
    fn = _build_local_update(loss_fn, eta_l, variant, optimizer,
                             mu, cm_alpha, ga_beta)
    full = jax.vmap(fn, in_axes=(None, 0, 0, None))
    active = _build_active_width_update(loss_fn, eta_l, variant, optimizer,
                                        mu, cm_alpha, ga_beta)

    def cohort(global_params, batches, masks, extra):
        if masks is None or clients_sharded:
            return full(global_params, batches, masks, extra)
        return active(global_params, batches, masks, extra)

    return jax.jit(cohort) if jit else cohort
