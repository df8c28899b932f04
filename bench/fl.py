"""The plain reference of a FedDPC round, and the comparison that decides
``correct``.

The reference computes at the precision the configurations state
(float32, matrix products at the TPU's default precision) and follows
FedDPC (Algorithm 1) one client and one local step at a time: each sampled client runs SGD from the global weights over its
own batches, its pseudo-gradient is (w_global - w_client) / eta_l; the
server projects each pseudo-gradient off the previous global update,
scales the residual by lam + ||d|| / ||residual||, averages the scaled
residuals into the new global update and steps the weights by eta_g
times it. Nothing here comes from the program.

What is compared, for the first three rounds the timed path ran:

* ``loss_gap``: the worst round's |program - reference| / |reference|
  of the round's training loss (the mean over clients of the mean over
  each client's steps of the loss before the step);
* ``grad_gap``: the first round's global update, as the server state
  holds it after one round, by the worst leaf: the gap between the two
  leaf norms over the larger of the reference's leaf norm and its
  median leaf norm;
* ``change_gap``: the weights' change over the three rounds, by the
  same measure, leaving out leaves whose first update in the reference
  is under a thousandth of the median leaf's (they move by round-off);
* ``cohort_mismatch``: sampled clients that differ from the schedule,
  an exact count.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12
DEAD_LEAF = 1e-3      # of the median leaf's first-update norm

# The precisions a reference can run at: the reference itself, at what
# the configurations state (float32 at the TPU's default precision); the
# control, one step below (bfloat16); and float32 at ``highest``, a
# witness that calibrate.py reads beside them.
PRECISIONS = {
    "reference": (jnp.float32, jax.lax.Precision.DEFAULT),
    "control": (jnp.bfloat16, jax.lax.Precision.DEFAULT),
    "highest": (jnp.float32, jax.lax.Precision.HIGHEST),
}

Batch = Tuple[np.ndarray, np.ndarray]


def make_local_step(loss: Callable, eta_l: float, dtype,
                    half_batch: bool = False):
    """One jitted SGD step of one client: (w, x, y) -> (loss, w').
    ``half_batch`` plants a fault: the step sees only the first half of
    its batch."""

    def step(w, x, y):
        if half_batch:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        value, g = jax.value_and_grad(loss)(w, x, y)
        return value, jax.tree.map(
            lambda a, b: (a - jnp.asarray(eta_l, a.dtype) * b.astype(a.dtype)
                          ).astype(a.dtype), w, g)

    return jax.jit(step)


def _vdot(a, b) -> jnp.ndarray:
    return sum(jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@jax.jit
def _accumulate(acc, w, wc, prev, eta_l, lam):
    """Adds one client's scaled residual to the server's sum: its
    pseudo-gradient d = (w - wc) / eta_l, projected off ``prev`` and
    scaled by lam + ||d|| / ||residual||."""
    d = jax.tree.map(lambda a, b: (a.astype(jnp.float32)
                                   - b.astype(jnp.float32)) / eta_l, w, wc)
    sq_prev = _vdot(prev, prev)
    coef = jnp.where(sq_prev > EPS,
                     _vdot(d, prev) / jnp.maximum(sq_prev, EPS), 0.0)
    resid = jax.tree.map(lambda a, p: a - coef * p, d, prev)
    scale = lam + jnp.sqrt(_vdot(d, d)) / jnp.maximum(
        jnp.sqrt(_vdot(resid, resid)), EPS)
    return jax.tree.map(lambda s, r: s + scale * r, acc, resid)


@jax.jit
def _server_step(w, acc, n, eta_g):
    update = jax.tree.map(lambda s: s / n, acc)
    return jax.tree.map(lambda a, u: a - eta_g * u, w, update), update


def reference_rounds(step, params0, cohorts: Sequence[np.ndarray],
                     batches: Callable[[int, int], List[Batch]],
                     eta_l: float, eta_g: float, lam: float, dtype
                     ) -> Dict[str, Any]:
    """Runs len(cohorts) FedDPC rounds from ``params0``. ``batches(c, t)``
    gives client c's batches in round t.

    Returns the per-round losses, the first round's global update and
    the weights after the last round, all on the host."""
    w = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params0)
    prev = jax.tree.map(jnp.zeros_like, w)
    losses, first_update = [], None
    for t, cohort in enumerate(cohorts):
        acc = jax.tree.map(jnp.zeros_like, w)
        client_losses = []
        for c in cohort:
            wc = jax.tree.map(lambda a: a.astype(dtype), w)
            step_losses = []
            for x, y in batches(int(c), t):
                value, wc = step(wc, jnp.asarray(x), jnp.asarray(y))
                step_losses.append(value)
            client_losses.append(float(np.mean(jax.device_get(step_losses))))
            acc = _accumulate(acc, w, wc, prev, eta_l, lam)
        w, prev = _server_step(w, acc, float(len(cohort)), eta_g)
        losses.append(float(np.mean(client_losses)))
        if t == 0:
            first_update = jax.device_get(prev)
    return {"losses": losses, "first_update": first_update,
            "params": jax.device_get(w)}


# ---- the comparison ----

def _leaf_norms(tree) -> np.ndarray:
    return np.asarray([np.linalg.norm(np.asarray(x, np.float64))
                       for x in jax.tree.leaves(tree)])


def _worst_leaf_gap(got: np.ndarray, want: np.ndarray,
                    keep: np.ndarray) -> float:
    floor = max(float(np.median(want)), EPS)
    gaps = np.abs(got - want) / np.maximum(want, floor)
    return float(gaps[keep].max())


def compare(program: Dict[str, Any], reference: Dict[str, Any],
            params0) -> Dict[str, float]:
    """The numbers ``correct`` is decided on (see the module's doc)."""
    lp = np.asarray(program["losses"], np.float64)
    lr = np.asarray(reference["losses"], np.float64)
    g_ref = _leaf_norms(reference["first_update"])
    g_prog = _leaf_norms(program["first_update"])
    live = g_ref >= DEAD_LEAF * float(np.median(g_ref))
    w0 = [np.asarray(x, np.float64) for x in jax.tree.leaves(params0)]

    def change(tree):
        return np.asarray([np.linalg.norm(np.asarray(x, np.float64) - a)
                           for x, a in zip(jax.tree.leaves(tree), w0)])

    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": _worst_leaf_gap(g_prog, g_ref, np.ones_like(live)),
        "change_gap": _worst_leaf_gap(change(program["params"]),
                                      change(reference["params"]), live),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[str]]:
    """correct when every number is within its limit (and is a number);
    returns the verdict and one line per number with its limit."""
    ok, lines = True, []
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok &= bool(good)
        lines.append(f"{name} {value} limit {limit}"
                     f"{'' if good else ' FAILED'}")
    return ok, lines
