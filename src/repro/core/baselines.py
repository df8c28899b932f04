"""FL server rules the paper compares against (paper §3, §5.2.3):

  fedavg   plain mean of local updates (two-sided LRs)
  fedprox  fedavg server; clients add a proximal term (client variant)
  fedexp   extrapolated server LR from the POCS view (Jhunjhunwala et al.)
  fedga    fedavg server; clients initialize with a displacement along the
           previous global update (stateless reading of Dandi et al.)
  fedcm    fedavg server; clients mix a momentum-like term from the
           previous global update into every local gradient step
  fedvarp  server keeps the latest update of EVERY client and uses
           surrogate updates for absent ones (stateful, O(k d))
  feddpc   the paper's method (core/feddpc.py)
  feddpc_noscale  ablation: projection only (Fig 6)

Unified interface so the trainer can swap algorithms:

  algo.init(params, num_clients)                         -> server_state
  algo.step(state, params, deltas, client_ids, eta_g, t,
            client_mask=None) -> (params', state', diag)
  algo.client_variant in {"plain","prox","cm","ga"}      local-training flavour
  algo.client_extra(state)    pytree broadcast to clients (e.g. Delta_{t-1})
  algo.client_hparams         kwargs the local-update builder needs
                              (mu / cm_alpha / ga_beta for its variant)

deltas are client-stacked pytrees (leading axis k'), client_ids (k',) int32.
``client_mask`` (k',) bool marks REAL cohort rows when the sharded path
pads the cohort to a multiple of the client axis (DESIGN.md §2): masked
rows carry dummy clients whose deltas must not perturb the client mean,
FedExP's extrapolation count, or FedVARP's table — dummy client_ids are
out of range and are dropped by the scatter.  ``partitioned`` (bool
kwarg, default False) declares that the round is partitioned by GSPMD
over a mesh of more than one device (client axis, model axis or both,
§2); rules that only use dim-preserving tree reductions can ignore it
(GSPMD reduces the partials for free), rules with fast paths GSPMD
cannot partition (FedDPC's Pallas epilogue) must fall back.  Steps
accept unknown kwargs (**_) so new execution hints never break an
algorithm that does not care.

Algorithms register through ``register_algorithm(name, HyperCls)``: each
carries a frozen hyperparameter dataclass (``FedDPCHyper(lam=...)``,
``FedProxHyper(mu=...)``, ...) and ``make_algorithm(name, hyper)`` builds
the ``ServerAlgo``.  ``get_algorithm(name, lam=..., use_kernel=...)`` is
the deprecated flat-kwargs shim kept for old callers.

Every ``step`` runs inside the fused cohort round (core/round.py): it is
traced together with the vmapped local training into one jit'd program
whose state/params buffers are DONATED. Steps must therefore be pure
functions of traced inputs — client_ids arrives as a traced int32 array
(FedVARP's per-client table update is a gather + scatter on it), and all
branching on k'/shape must be static. ``client_extra`` is likewise traced
from server_state inside the program.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from repro.core import feddpc as feddpc_mod
from repro.core import projection as proj

PyTree = Any


@dataclass(frozen=True)
class ServerAlgo:
    name: str
    init: Callable[[PyTree, int], PyTree]
    step: Callable[..., Tuple[PyTree, PyTree, Dict]]
    client_variant: str = "plain"
    # extracts what local training needs from server state (None if nothing)
    client_extra: Callable[[PyTree], Optional[PyTree]] = lambda s: None
    stateful_per_client: bool = False
    # per-variant local-training knobs (mu / cm_alpha / ga_beta) sourced
    # from the algorithm's hyper dataclass; the round builder reads them
    client_hparams: Dict[str, float] = field(default_factory=dict)
    hyper: Any = None
    # the rule folds buffered-async staleness weights into its OWN
    # reduction scalars (step accepts staleness_weights=); False makes
    # the async engine pre-scale the buffered deltas instead (FedBuff
    # mean semantics) — see core/async_engine.py, DESIGN.md §11
    staleness_aware: bool = False


# masked client mean (padded dummy rows excluded): one implementation,
# shared with core/feddpc.py through core/projection.py
_mean_over_clients = proj.masked_client_mean


def _apply(params: PyTree, delta: PyTree, eta_g) -> PyTree:
    return jax.tree.map(
        lambda w, d: (w.astype(jnp.float32)
                      - eta_g * d.astype(jnp.float32)).astype(w.dtype),
        params, delta)


# ---------------- registry ----------------

@dataclass(frozen=True)
class AlgorithmSpec:
    name: str
    hyper_cls: Type
    build: Callable[[Any], ServerAlgo]


_REGISTRY: Dict[str, AlgorithmSpec] = {}


def register_algorithm(name: str, hyper_cls: Type = None):
    """Decorator: ``@register_algorithm("myalgo", MyHyper)`` over a
    ``build(hyper) -> ServerAlgo`` factory adds it to the registry (and
    to ``FLConfig``/CLI name resolution for free)."""
    def deco(build):
        if name in _REGISTRY:
            raise ValueError(f"algorithm {name!r} already registered")
        _REGISTRY[name] = AlgorithmSpec(name, hyper_cls or NoHyper, build)
        return build
    return deco


def make_algorithm(name: str, hyper=None) -> ServerAlgo:
    """Build a ``ServerAlgo`` from the registry. ``hyper`` is the
    algorithm's hyper dataclass instance, a kwargs dict for it, or None
    for registry defaults."""
    try:
        spec = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown algorithm {name!r}; registered: "
                         f"{', '.join(sorted(_REGISTRY))}") from None
    if hyper is None:
        hyper = spec.hyper_cls()
    elif isinstance(hyper, dict):
        hyper = spec.hyper_cls(**hyper)
    elif not isinstance(hyper, spec.hyper_cls):
        raise TypeError(f"{name} expects {spec.hyper_cls.__name__} hyper-"
                        f"parameters, got {type(hyper).__name__}")
    # stamp the resolved hyper onto the (frozen) ServerAlgo so callers —
    # and the checkpoint echo — can recover the full parameterization
    return dataclasses.replace(spec.build(hyper), hyper=hyper)


def algorithm_hyper_cls(name: str) -> Type:
    return _REGISTRY[name].hyper_cls


def default_hyper(name: str, *, lam: float = 1.0, use_kernel: bool = False,
                  mu: float = 0.01, cm_alpha: float = 0.1,
                  ga_beta: float = 0.1):
    """Hyper instance built from the FLAT legacy knobs; None for
    algorithms none of them reach (registry defaults apply). The ONE
    mapping behind the FLConfig shim, the legacy ``get_algorithm``
    kwargs, and the lam-bearing CLI/benchmark entry points."""
    return {
        "feddpc": lambda: FedDPCHyper(lam=lam, use_kernel=use_kernel),
        "feddpc_m": lambda: FedDPCMHyper(lam=lam),
        "fedprox": lambda: FedProxHyper(mu=mu),
        "fedcm": lambda: FedCMHyper(alpha=cm_alpha),
        "fedga": lambda: FedGAHyper(beta=ga_beta),
    }.get(name, lambda: None)()


def client_kwargs(algo: ServerAlgo) -> Dict[str, float]:
    """Local-update kwargs the algorithm pins (its variant-specific
    hypers); knobs it leaves unset keep the builder defaults in
    core/client.py — the single source of those defaults."""
    return dict(algo.client_hparams)


# ---------------- hyperparameter dataclasses ----------------

@dataclass(frozen=True)
class NoHyper:
    pass


@dataclass(frozen=True)
class FedProxHyper:
    mu: float = 0.01                 # proximal strength


@dataclass(frozen=True)
class FedCMHyper:
    alpha: float = 0.1               # client-momentum mixing


@dataclass(frozen=True)
class FedGAHyper:
    beta: float = 0.1                # displacement along Delta_{t-1}


@dataclass(frozen=True)
class FedExPHyper:
    eps: float = 1e-3                # extrapolation denominator guard


@dataclass(frozen=True)
class FedDPCHyper:
    lam: float = 1.0                 # adaptive-scaling hyper-param
    use_kernel: bool = False         # route the epilogue through Pallas


@dataclass(frozen=True)
class FedDPCMHyper:
    lam: float = 1.0
    beta: float = 0.9                # server momentum


@dataclass(frozen=True)
class AdaptiveHyper:                 # FedAdam / FedYogi (Reddi et al. [9])
    b1: float = 0.9
    b2: float = 0.99
    eps: float = 1e-3


# ---------------- FedAvg family ----------------

def _fedavg_init(params, num_clients):
    return {"delta_prev": proj.tree_zeros_like(params)}


def _fedavg_step(state, params, deltas, client_ids, eta_g, t,
                 client_mask=None, edges=None, **_):
    delta_t = _mean_over_clients(deltas, client_mask, edges=edges)
    return _apply(params, delta_t, eta_g), {"delta_prev": delta_t}, {
        "norm_global_update": proj.tree_norm(delta_t)}


@register_algorithm("fedavg")
def _build_fedavg(h):
    return ServerAlgo("fedavg", _fedavg_init, _fedavg_step)


@register_algorithm("fedprox", FedProxHyper)
def _build_fedprox(h):
    # same server as FedAvg; prox term applied in the client loop
    return ServerAlgo("fedprox", _fedavg_init, _fedavg_step,
                      client_variant="prox", client_hparams={"mu": h.mu})


# ---------------- FedExP ----------------

def _fedexp_step(state, params, deltas, client_ids, eta_g, t, eps=1e-3,
                 client_mask=None, edges=None, **_):
    """eta_g_t = max(1, sum_j||Δ_j||² / (2 k' (||Δ̄||² + eps))) — the POCS
    extrapolation rule; then w ← w − eta_g · eta_g_t · Δ̄."""
    delta_t = _mean_over_clients(deltas, client_mask, edges=edges)
    sq_each = jax.vmap(proj.tree_sqnorm)(deltas)               # (k',)
    if client_mask is None:
        kprime = sq_each.shape[0]
    else:
        mf = client_mask.astype(jnp.float32)
        sq_each = sq_each * mf
        kprime = jnp.maximum(mf.sum(), 1.0)
    sq_mean = proj.tree_sqnorm(delta_t)
    if edges is not None and int(edges) > 1:
        # two-level: per-edge partial sums of ||Δ_j||², then the sum of
        # the E edge summaries — the scalar is dim-preserving, so it
        # composes across aggregation levels (DESIGN.md §15)
        sq_total = jnp.sum(jnp.sum(
            sq_each.reshape(int(edges), -1), axis=1))
    else:
        sq_total = sq_each.sum()
    extrap = jnp.maximum(1.0, sq_total / (2 * kprime * (sq_mean + eps)))
    return _apply(params, delta_t, eta_g * extrap), {
        "delta_prev": delta_t}, {
        "norm_global_update": proj.tree_norm(delta_t), "extrap": extrap}


@register_algorithm("fedexp", FedExPHyper)
def _build_fedexp(h):
    return ServerAlgo("fedexp", _fedavg_init,
                      functools.partial(_fedexp_step, eps=h.eps))


# ---------------- FedGA / FedCM ----------------

@register_algorithm("fedga", FedGAHyper)
def _build_fedga(h):
    # clients start from a displaced model along Delta_{t-1}
    return ServerAlgo("fedga", _fedavg_init, _fedavg_step,
                      client_variant="ga",
                      client_extra=lambda s: s["delta_prev"],
                      client_hparams={"ga_beta": h.beta})


@register_algorithm("fedcm", FedCMHyper)
def _build_fedcm(h):
    # clients mix Delta_{t-1} into each local gradient
    return ServerAlgo("fedcm", _fedavg_init, _fedavg_step,
                      client_variant="cm",
                      client_extra=lambda s: s["delta_prev"],
                      client_hparams={"cm_alpha": h.alpha})


# ---------------- FedVARP ----------------

def _fedvarp_init(params, num_clients):
    zeros = proj.tree_zeros_like(params)
    table = jax.tree.map(
        lambda z: jnp.zeros((num_clients,) + z.shape, jnp.float32), params)
    return {"y": table, "delta_prev": zeros}


def _fedvarp_step(state, params, deltas, client_ids, eta_g, t,
                  client_mask=None, edges=None, **_):
    """Δ_t = (1/k)Σ_i y_i + (1/k')Σ_{j∈S}(Δ_j − y_j);  y_j ← Δ_j for j∈S.

    Padded dummy rows (client_mask False) carry out-of-range ids: the
    gather fills zeros, the correction mean skips them, and the scatter
    DROPS them so no real client's table row is clobbered."""
    y = state["y"]
    if client_mask is None:
        y_sel = jax.tree.map(lambda tb: tb[client_ids], y)      # (k', ...)
        new_y = jax.tree.map(
            lambda tb, d: tb.at[client_ids].set(d.astype(jnp.float32)),
            y, deltas)
    else:
        y_sel = jax.tree.map(
            lambda tb: tb.at[client_ids].get(mode="fill", fill_value=0.0), y)
        new_y = jax.tree.map(
            lambda tb, d: tb.at[client_ids].set(d.astype(jnp.float32),
                                                mode="drop"), y, deltas)
    # the correction mean goes two-level under edges=; the y-table
    # scatter is edge-resident state that rides along unchanged, and
    # base = y.mean(axis=0) is server-local state
    corr = _mean_over_clients(
        jax.tree.map(lambda d, ys: d.astype(jnp.float32) - ys,
                     deltas, y_sel), client_mask, edges=edges)
    base = jax.tree.map(lambda tb: tb.mean(axis=0), y)
    delta_t = jax.tree.map(lambda b, c: b + c, base, corr)
    return _apply(params, delta_t, eta_g), {
        "y": new_y, "delta_prev": delta_t}, {
        "norm_global_update": proj.tree_norm(delta_t)}


@register_algorithm("fedvarp")
def _build_fedvarp(h):
    return ServerAlgo("fedvarp", _fedvarp_init, _fedvarp_step,
                      stateful_per_client=True)


# ---------------- FedDPC (the paper) ----------------

@register_algorithm("feddpc", FedDPCHyper)
def _build_feddpc(h):
    def step(state, params, deltas, client_ids, eta_g, t,
             client_mask=None, partitioned=False,
             staleness_weights=None, encoded=None, edges=None, **_):
        return feddpc_mod.server_step(state, params, deltas, eta_g, h.lam,
                                      use_kernel=h.use_kernel,
                                      client_mask=client_mask,
                                      partitioned=partitioned,
                                      staleness_weights=staleness_weights,
                                      encoded=encoded, edges=edges)
    return ServerAlgo("feddpc", lambda p, n: feddpc_mod.init_state(p), step,
                      staleness_aware=True)


def _feddpc_noscale_step(state, params, deltas, client_ids, eta_g, t,
                         client_mask=None, edges=None, **_):
    return feddpc_mod.server_step_projection_only(
        state, params, deltas, eta_g, client_mask=client_mask, edges=edges)


@register_algorithm("feddpc_noscale")
def _build_feddpc_noscale(h):
    return ServerAlgo("feddpc_noscale", lambda p, n: feddpc_mod.init_state(p),
                      _feddpc_noscale_step)


# ---------------- adaptive server optimizers (Reddi et al. [9]) ----------

def _adaptive_init(params, num_clients):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"delta_prev": proj.tree_zeros_like(params),
            "m": jax.tree.map(z, params), "v": jax.tree.map(z, params),
            "t": jnp.zeros((), jnp.float32)}


def _make_adaptive(kind: str, h: AdaptiveHyper) -> ServerAlgo:
    """FedAdam / FedYogi: the client-mean pseudo-gradient feeds a server-
    side adaptive optimizer (beyond-paper: the paper's two-sided-LR view
    generalized to adaptive server steps)."""
    b1, b2, eps = h.b1, h.b2, h.eps

    def step(state, params, deltas, client_ids, eta_g, t_unused,
             client_mask=None, edges=None, **_):
        delta_t = _mean_over_clients(deltas, client_mask, edges=edges)
        t = state["t"] + 1.0
        m = jax.tree.map(lambda mm, d: b1 * mm + (1 - b1) * d,
                         state["m"], delta_t)
        if kind == "adam":
            v = jax.tree.map(lambda vv, d: b2 * vv + (1 - b2) * d * d,
                             state["v"], delta_t)
        else:   # yogi
            v = jax.tree.map(
                lambda vv, d: vv - (1 - b2) * d * d * jnp.sign(vv - d * d),
                state["v"], delta_t)
        upd = jax.tree.map(lambda mm, vv: mm / (jnp.sqrt(vv) + eps), m, v)
        new_params = _apply(params, upd, eta_g)
        return new_params, {"delta_prev": delta_t, "m": m, "v": v, "t": t}, {
            "norm_global_update": proj.tree_norm(upd)}

    return ServerAlgo(f"fed{kind}", _adaptive_init, step)


@register_algorithm("fedadam", AdaptiveHyper)
def _build_fedadam(h):
    return _make_adaptive("adam", h)


@register_algorithm("fedyogi", AdaptiveHyper)
def _build_fedyogi(h):
    return _make_adaptive("yogi", h)


# ---------------- FedDPC-M (beyond-paper composition) ----------------

@register_algorithm("feddpc_m", FedDPCMHyper)
def _build_feddpc_m(h):
    """FedDPC + server momentum on the aggregated (projected+scaled)
    update: m_t = beta m_{t-1} + Delta_t; w -= eta_g m_t. The projection
    is still against the raw previous Delta (paper semantics), momentum
    only smooths the applied step."""
    lam, beta = h.lam, h.beta

    def init(params, num_clients):
        s = feddpc_mod.init_state(params)
        s["m"] = proj.tree_zeros_like(params)
        return s

    def step(state, params, deltas, client_ids, eta_g, t,
             client_mask=None, partitioned=False,
             staleness_weights=None, edges=None, **_):
        _, new_state, diag = feddpc_mod.server_step(
            {"delta_prev": state["delta_prev"]}, params, deltas, 0.0, lam,
            client_mask=client_mask, partitioned=partitioned,
            staleness_weights=staleness_weights, edges=edges)
        delta_t = new_state["delta_prev"]
        m = jax.tree.map(
            lambda mm, d: beta * mm.astype(jnp.float32)
            + d.astype(jnp.float32), state["m"], delta_t)
        new_params = _apply(params, m, eta_g)
        return new_params, {"delta_prev": delta_t, "m": m}, diag

    return ServerAlgo("feddpc_m", init, step, staleness_aware=True)


# ---------------- legacy flat-kwargs shim ----------------

# module-level prebuilt instances, kept for old importers
FEDAVG = make_algorithm("fedavg")
FEDPROX = make_algorithm("fedprox")
FEDEXP = make_algorithm("fedexp")
FEDGA = make_algorithm("fedga")
FEDCM = make_algorithm("fedcm")
FEDVARP = make_algorithm("fedvarp")
FEDDPC = make_algorithm("feddpc")
FEDDPC_NOSCALE = make_algorithm("feddpc_noscale")
FEDADAM = make_algorithm("fedadam")
FEDYOGI = make_algorithm("fedyogi")
FEDDPC_M = make_algorithm("feddpc_m")


def get_algorithm(name: str, *, lam: float = 1.0,
                  use_kernel: bool = False) -> ServerAlgo:
    """DEPRECATED closure factory: the flat (lam, use_kernel) kwargs only
    parameterize the feddpc family.  Use ``make_algorithm(name, hyper)``
    with the algorithm's hyper dataclass instead."""
    warnings.warn(
        "get_algorithm(name, lam=..., use_kernel=...) is deprecated; use "
        "make_algorithm(name, hyper) with the per-algorithm hyper "
        "dataclass (e.g. FedDPCHyper)", DeprecationWarning, stacklevel=2)
    return make_algorithm(name, default_hyper(name, lam=lam,
                                              use_kernel=use_kernel))


ALGORITHM_NAMES = tuple(_REGISTRY)
