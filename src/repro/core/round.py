"""Fused FL round step — local training vmapped over the client axis +
server aggregation in ONE jit'd program (DESIGN.md §2).

``make_cohort_round`` is the single implementation behind both execution
modes:

  simulation (core/api.py)   the FederatedTrainer's default path: the
      cohort's padded minibatch stacks arrive as one (K, M, ...) batch
      pytree with a (K, M) validity mask, and the whole round — K local
      trainings + the server rule — is one dispatch, donating the
      params/server-state buffers.
  cross-silo mesh (``make_fl_round_step``)   the (pod x data) axes form
      the CLIENT axis — each (pod, data) slice is one participating silo
      training a model-parallel replica (weights replicated over client
      axes, Megatron-sharded over ``model``). Partial participation =
      which silos show up this round; a pod boundary is a datacenter
      boundary.

FedDPC's epilogue stays collective-native under GSPMD: per-client scalars
<Δ_j,Δ_prev>, ||Δ_j||², ||Δ_prev||² reduce over every model-sharding axis
automatically (4 scalar all-reduces), the projection/scaling is
elementwise on the sharded update shards, and the client-mean is one
all-reduce over the client axes — asymptotically the same collective
volume as FedAvg (the paper's server loop is O(4k'd) *serial*; here it is
fused into the data-parallel reduction).
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from repro.codec import base as codec_base
from repro.core import client as client_mod
from repro.core import faults as faults_mod
from repro.core import projection as proj
from repro.core.baselines import ServerAlgo, client_kwargs, make_algorithm

PyTree = Any

# out-of-range id for quarantined / deadline-dropped rows: FedVARP's
# masked scatter (mode="drop") only ignores OUT-OF-RANGE ids — an
# in-range id would still clobber that client's table row even with its
# client_mask bit cleared (DESIGN.md §12)
ID_SENTINEL = 2_147_483_647      # jnp.iinfo(jnp.int32).max


def _bc(v: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a (K,) vector over a client-stacked leaf."""
    return v.reshape((-1,) + (1,) * (x.ndim - 1))


def apply_fault_codes(deltas: PyTree, fault_codes: jnp.ndarray,
                      magnitude: float) -> PyTree:
    """Chaos harness (core/faults.py): corrupt the coded clients' rows of
    a client-stacked delta tree — CODE_NAN fills with NaN, CODE_EXPLODE
    multiplies by ``magnitude`` — AFTER local training, BEFORE
    validation/aggregation: exactly where a byzantine or numerically-
    diverged client would surface in a real deployment. Shared by the
    fused sync round and the buffered-async fold."""
    mult = jnp.where(fault_codes == faults_mod.CODE_EXPLODE,
                     jnp.float32(magnitude), jnp.float32(1.0))
    return jax.tree.map(
        lambda d: jnp.where(
            _bc(fault_codes == faults_mod.CODE_NAN, d), jnp.nan,
            d.astype(jnp.float32) * _bc(mult, d)).astype(d.dtype),
        deltas)


def apply_guard(deltas: PyTree, client_ids: jnp.ndarray, client_mask,
                guard_thresh, guard_cfg):
    """Update-guard validation (core/guards.py, DESIGN.md §12) on a
    client-stacked delta tree: per-row ||Δ||² + non-finite count (the
    same dim-preserving reduction pass FedDPC's projection scalars ride;
    fused Pallas form in kernels/feddpc_project.guard_dots), quarantine
    on any non-finite entry or norm > quarantine_mult x thresh, clip to
    clip_mult x thresh otherwise.

    Quarantined rows are ZEROED in-program — client_mask folding alone is
    NOT enough, because a masked row still multiplies into the masked
    means (0 x NaN = NaN) — their ids are replaced by the out-of-range
    sentinel, and they fold into ``client_mask``, so every server rule
    stays exact with zero rule changes. With thresh = +inf and finite
    rows every multiplier is exactly 1.0: the guarded computation IS the
    unguarded one. Returns (deltas, client_ids, client_mask, stats) with
    stats = {"quarantined": (K,) bool, "clipped": (K,) bool,
    "norm": (K,) f32 post-clip norms}."""
    sq = jax.vmap(proj.tree_sqnorm)(deltas)                   # (K,)
    nfin = jax.vmap(proj.tree_nonfinite_count)(deltas)        # (K,)
    norm = jnp.sqrt(sq)
    thresh = jnp.asarray(guard_thresh, jnp.float32)
    q_lim = jnp.float32(guard_cfg.quarantine_mult) * thresh
    c_lim = jnp.float32(guard_cfg.clip_mult) * thresh
    bad = (nfin > 0) | (norm > q_lim)
    # clip multiplier is EXACTLY 1.0 below the limit (and always 1.0
    # while thresh is +inf); a NaN norm compares False, so a non-finite
    # row takes cs=1.0 — harmless, it is where-selected to zero below
    cs = jnp.where(norm > c_lim, c_lim / jnp.maximum(norm, proj.EPS),
                   jnp.float32(1.0))
    clipped = jnp.logical_and(~bad, norm > c_lim)
    deltas = jax.tree.map(
        lambda d: jnp.where(_bc(bad, d), jnp.float32(0.0),
                            d.astype(jnp.float32) * _bc(cs, d)
                            ).astype(d.dtype), deltas)
    client_ids = jnp.where(bad, ID_SENTINEL, client_ids.astype(jnp.int32))
    client_mask = ~bad if client_mask is None else client_mask & ~bad
    stats = {"quarantined": bad, "clipped": clipped,
             "norm": jnp.minimum(norm, c_lim)}
    return deltas, client_ids, client_mask, stats


def make_cohort_round(loss_fn: Callable[[PyTree, PyTree], jnp.ndarray],
                      algo: ServerAlgo, eta_l: float, eta_g: float, *,
                      optimizer: str = "sgd",
                      jit: bool = True, donate: bool = True,
                      mesh=None, client_axis: str = "clients",
                      model_axis: str = "model",
                      pad_clients: bool = False,
                      real_clients: int = None,
                      shard_templates: Tuple[PyTree, PyTree] = None,
                      shardings=None,
                      guard: bool = False, guard_cfg=None,
                      inject_faults: bool = False,
                      deadline_mask: bool = False,
                      fault_magnitude: float = 1e12,
                      codec=None, codec_ef: bool = False,
                      server_opt=None, edges=None):
    """Returns cohort_round(server_state, params, batches, masks,
    client_ids, *extras) -> (new_params, new_server_state, losses, diag
    [, guard_stats]).

    The chaos-hardening extras (DESIGN.md §12) extend the signature in a
    FIXED order — ``inject_faults`` appends a (K,) int32 ``fault_codes``
    input (0 = pass through, faults.CODE_NAN fills the client's delta
    with NaN, faults.CODE_EXPLODE multiplies it by ``fault_magnitude``),
    ``deadline_mask`` appends a (K,) bool ``live_mask`` (False = the
    client timed out: its row folds out of ``client_mask`` and its id is
    replaced by an out-of-range sentinel so FedVARP's scatter drops it),
    and ``guard`` appends a scalar f32 ``guard_thresh`` and a trailing
    ``guard_stats`` output.

    The codec extras (repro/codec, DESIGN.md §13) extend that order: with
    a LOSSY ``codec`` (identity never enters the program) the round
    encodes each client's shipped delta, decodes it, and aggregates the
    DECODED values — so the simulated uplink carries exactly the codec's
    wire format. A stochastic codec appends a PRNG-key input after
    ``guard_thresh``; ``codec_ef=True`` appends a params-shaped f32
    error-feedback accumulator input after that, and a matching
    ``new_ef`` output after ``guard_stats``. EF uses broadcast
    compensation: every client ships ``Delta_j + ef`` and the new
    accumulator is the guarded-client mean of the sanitized
    (nonfinite-zeroed) quantization residuals. The guard, when enabled,
    reads the decoded (quantized-domain) norms; the fused Pallas dequant
    epilogue is only engaged when the guard is off, because quarantine/
    clip rewrite decoded rows that the payload scalars no longer
    describe.

    A STATEFUL ``server_opt`` (repro.optim.server, DESIGN.md §14)
    extends the order one last time: the optimizer's moment state is the
    LAST extra input and the updated state the LAST output. It consumes
    the POST-projection aggregate — ``algo.step``'s proposed params —
    and re-steps from the round's incoming params, so it composes with
    every registered rule without touching the rule's own math.
    ``server_opt=None`` (the sgd pass-through) leaves the program
    byte-identical to the pre-layer round.

    The guard validates every delta BEFORE the server rule sees it:
    per-client ||Δ||² + non-finite count (the reduction-pass sweep the
    FedDPC scalars come from; fused Pallas form in
    kernels/feddpc_project.guard_dots), quarantine on any non-finite
    entry or ||Δ|| > quarantine_mult x thresh, clip to
    clip_mult x thresh otherwise. Quarantined deltas are ZEROED in-
    program — client_mask folding alone is NOT enough, because FedDPC's
    scale=0 still multiplies the delta (0 x NaN = NaN) — their ids are
    sentineled, and their rows fold into ``client_mask``, so every
    server rule stays exact with zero rule changes. With thresh = +inf
    and no non-finite entries every multiplier is exactly 1.0 and the
    guarded round computes the unguarded round's values.
    ``guard_stats`` = {"quarantined": (K,) bool, "clipped": (K,) bool,
    "norm": (K,) f32 post-clip norms} — the trainer filters real rows
    and feeds accepted norms back into the rolling threshold window.

    batches: pytree with leading axes (K, M, ...) — K participating
    clients, M padded minibatches each — or an ``IndexedBatches`` (a
    device sample table and (K, M, B) sample indices, gathered step by
    step; DESIGN.md §10); masks (K, M) bool marks the valid ones (None =
    all valid, skipping the masked-select pass entirely). With masks and
    no partitioning, local training runs the active-width scan
    (core/client.py): each step computes only the rows it needs.
    client_ids (K,) int32 feeds stateful server rules (FedVARP's
    per-client table). The server-side ``extra`` (Delta_prev for the
    cm/ga client variants) is derived from server_state INSIDE the
    program, so the round is one closed jit'd function of
    (state, params, data).

    With jit=True the state/params buffers are donated: the round updates
    them in place, which keeps FedVARP's O(num_clients * d) table from
    being double-buffered every round.

    With ``mesh`` set (a mesh carrying ``client_axis``, e.g.
    launch/mesh.make_cohort_mesh), the K axis of batches/masks/client_ids
    gets a client-axis NamedSharding and the round runs data-parallel
    across the mesh devices with params/server-state replicated
    (sharding/rules.cohort_round_shardings — DESIGN.md §2). K should be a
    multiple of the axis size; with ``pad_clients=True`` the caller pads
    the cohort stack itself (dummy rows with all-False mask rows and
    out-of-range client_ids) and the round masks dummy clients out of
    every server mean and out of FedVARP's table.

    ``real_clients`` is how the caller communicates its pad count: the
    first ``real_clients`` rows are sampled clients, the rest padding.
    Prefer it over bare ``pad_clients=True``, which falls back to
    deriving the validity mask from ``masks.any(axis=1)`` — that
    reclassifies a genuinely sampled client whose every minibatch is
    invalid (a zero-data client) as padding, silently dropping its id
    from FedVARP's table while its (zero) delta still dilutes nothing
    but its loss row still enters ``losses``.

    A TWO-AXIS mesh (``model_axis`` present with size > 1, built by
    make_cohort_mesh(model=N)) additionally shards params / server state
    per leaf over ``model`` (§8 rules + trailing-dim fallback) so each
    client slice carries only 1/|model| of the weights. That layout
    needs shape templates: ``shard_templates=(params, server_state)``
    (only shapes are read). Deltas inherit the (clients, model) layout
    from the vmapped local training, the FedDPC reduction-pass scalars
    reduce over BOTH axes automatically (dim-preserving per-leaf sums ->
    GSPMD inserts the model-axis psum before the scale is formed), and
    the server rule sees ``partitioned=True`` so the Pallas epilogue
    falls back to the reference path (its flatten would all-gather the
    shards).

    ``edges=E`` (E > 1) runs the server rule as a two-level hierarchical
    fold (DESIGN.md §15): the K cohort rows split into E equal contiguous
    edge groups, each edge folds its slice with the SAME fused epilogue
    (Pallas / codec-dequant grids included) to a partial summary, and the
    server combines the E summaries. FedDPC's reduction-pass scalars are
    dim-preserving sums, so only the final client-mean decomposes — the
    result is allclose to the flat fold (float summation order only). On
    a process-spanning clients mesh each contiguous group is one host's
    local shard, so the cross-host traffic is E summaries, not K rows.
    E must divide K (the padded cohort size).

    The per-variant local-training knobs (mu / cm_alpha / ga_beta) come
    from the algorithm's own hyperparameters (``algo.client_hparams``);
    anything the algorithm leaves unset keeps the local-update builder's
    defaults.
    """
    # any mesh of more than one device partitions the round under GSPMD,
    # which cannot partition a Mosaic kernel: the server rule then takes
    # its jnp epilogue, and local training keeps the full-width masked
    # scan, since the active-width scan's row gather would move weights
    # between chips (DESIGN.md §2)
    partitioned = bool(mesh is not None and mesh.devices.size > 1)
    local = client_mod.make_cohort_local_update(
        loss_fn, eta_l, variant=algo.client_variant, optimizer=optimizer,
        clients_sharded=partitioned, **client_kwargs(algo))
    # codec stage (repro/codec, DESIGN.md §13): only LOSSY codecs enter
    # the program — identity is a literal pass-through and compiles to
    # the no-codec round. Error feedback adds one params-shaped f32
    # accumulator input + output; stochastic rounding adds a key input.
    codec_lossy = bool(codec is not None and codec.lossy)
    ef_active = bool(codec_lossy and codec_ef)
    codec_stochastic = bool(codec_lossy
                            and getattr(codec, "stochastic", False))

    def cohort_round(server_state, params, batches, masks, client_ids,
                     *extras):
        it = iter(extras)
        fault_codes = next(it) if inject_faults else None
        live_mask = next(it) if deadline_mask else None
        guard_thresh = next(it) if guard else None
        codec_key = next(it) if codec_stochastic else None
        ef = next(it) if ef_active else None
        opt_state = next(it) if server_opt is not None else None
        extra = algo.client_extra(server_state)
        # stage scopes (repro.trace): HLO op_name metadata only
        with jax.named_scope("fl.local"):
            deltas, losses = local(params, batches, masks, extra)
        if inject_faults:
            deltas = apply_fault_codes(deltas, fault_codes, fault_magnitude)
        if real_clients is not None:
            # pad mask from the caller's pad count: rows >= real_clients
            # are padding, everything below is a sampled client — even
            # one whose every minibatch is masked (zero-data client)
            cm = jnp.arange(client_ids.shape[0]) < real_clients
        elif pad_clients and masks is not None:
            # legacy fallback for callers that only know "padded":
            # misclassifies zero-data clients as padding (see docstring)
            cm = masks.any(axis=1)
        else:
            cm = None
        if deadline_mask:
            client_ids = jnp.where(live_mask, client_ids.astype(jnp.int32),
                                   ID_SENTINEL)
            cm = live_mask if cm is None else cm & live_mask
        payload = shipped = decoded = None
        if codec_lossy:
            # what each client SHIPS: its delta plus the server-held
            # error-feedback residual (broadcast compensation — the same
            # accumulator is folded into every row, so a mean-style rule
            # recovers the compensation exactly in aggregate)
            with jax.named_scope("fl.codec"):
                shipped = deltas
                if ef_active:
                    shipped = jax.tree.map(
                        lambda d, e: (d.astype(jnp.float32)
                                      + e.astype(jnp.float32)[None]
                                      ).astype(d.dtype), deltas, ef)
                payload = codec.encode_cohort(shipped, key=codec_key)
                decoded = codec.decode_cohort(payload)
                deltas = decoded
        gstats = None
        if guard:
            # quarantine/clip decisions read the DECODED (quantized-
            # domain) deltas — the values the server would aggregate
            with jax.named_scope("fl.guard"):
                deltas, client_ids, cm, gstats = apply_guard(
                    deltas, client_ids, cm, guard_thresh, guard_cfg)
        new_ef = None
        if ef_active:
            # residual of the PRE-guard decode (pure quantization error;
            # a clipped row's shaved mass is a guard decision, not lost
            # signal), nonfinite-sanitized so faulty rows cannot poison
            # the accumulator, mean over the surviving clients only
            with jax.named_scope("fl.codec"):
                resid = codec_base.sanitized_residual(shipped, decoded)
                new_ef = proj.masked_client_mean(resid, cm)
        # the fused dequant epilogue re-derives the decoded rows from the
        # payload scalars, so it is only handed over when the guard has
        # NOT rewritten rows between decode and aggregation
        with jax.named_scope("fl.server"):
            new_params, new_state, diag = algo.step(
                server_state, params, deltas, client_ids, eta_g, 0,
                client_mask=cm, partitioned=partitioned,
                encoded=(payload if codec_lossy and not guard else None),
                edges=edges)
            new_opt = None
            if server_opt is not None:
                # adaptive server step (DESIGN.md §14): precondition the
                # POST-projection aggregate — re-step from the round's
                # incoming params with moment-scaled magnitudes
                new_params, new_opt = server_opt.apply(params, new_params,
                                                       opt_state)
        outs = [new_params, new_state, losses, diag]
        if guard:
            outs.append(gstats)
        if ef_active:
            outs.append(new_ef)
        if server_opt is not None:
            outs.append(new_opt)
        return tuple(outs)

    if not jit:
        return cohort_round
    kw = {"donate_argnums": (0, 1) if donate else ()}
    if mesh is not None:
        if shardings is None:
            # `shardings` lets a caller that already built the
            # (in, out) pair (the trainer, which also pre-places the
            # state with it) avoid recomputing the per-leaf specs
            from repro.sharding.rules import cohort_round_shardings
            tmpl_p, tmpl_s = shard_templates or (None, None)
            shardings = cohort_round_shardings(
                mesh, client_axis, model_axis=model_axis, params=tmpl_p,
                server_state=tmpl_s)
        kw["in_shardings"], kw["out_shardings"] = shardings
    return jax.jit(cohort_round, **kw)


def make_fl_round_step(loss_fn: Callable[[PyTree, PyTree], jnp.ndarray],
                       eta_l: float, eta_g: float, lam: float = 1.0,
                       algorithm: str = "feddpc", *,
                       mesh=None, client_axis: str = "clients",
                       model_axis: str = "model",
                       params_template: PyTree = None):
    """Mesh-path wrapper: round_step(params, delta_prev, batches) ->
    (new_params, new_delta_prev, metrics).

    batches: pytree whose leaves have leading axes (K, M, ...) — K
    participating clients (sharded over the mesh client axes), M local
    steps each, all valid (no mask: the mesh path feeds fixed-shape
    silo streams). loss_fn(params, batch) -> scalar. Supports any
    algorithm whose server state is exactly {"delta_prev"} (feddpc,
    fedavg, fedexp, ...); per-client-stateful rules (fedvarp) need the
    full ``make_cohort_round`` interface.

    Two sharding modes share this one implementation:
      * mesh=None (default): returns the raw python fn — the cross-silo
        dry-run jits it with full Megatron param shardings externally
        (launch/dryrun.fl_round_dryrun).
      * mesh=<1-D client mesh>: returns a jit'd round with the same
        client-axis NamedSharding layout as the simulation trainer
        (batches sharded on K over ``client_axis``, params/delta_prev
        replicated) — the unified sharded round of DESIGN.md §2.
      * mesh=<two-axis (clients, model) mesh>: params AND delta_prev
        shard per leaf over ``model_axis`` (sharding/rules.
        cohort_param_specs); needs ``params_template`` for the leaf
        shapes, and fails loudly without it.
    """
    hyper = {"lam": lam} if algorithm in ("feddpc", "feddpc_m") else None
    algo = make_algorithm(algorithm, hyper)
    probe = algo.init({"w": jnp.zeros(())}, 1)
    if set(probe) != {"delta_prev"}:
        raise ValueError(
            f"make_fl_round_step supports algorithms whose server state is "
            f"exactly {{'delta_prev'}}; {algorithm!r} keeps {sorted(probe)} "
            f"— use make_cohort_round for stateful server rules")
    # mesh/model_axis are forwarded so the raw round still sees
    # partitioned=True on a multi-device mesh (the Pallas-fallback contract
    # holds on this entry point too); jit=False leaves the sharding
    # layout to the external jit below
    cohort = make_cohort_round(loss_fn, algo, eta_l, eta_g, jit=False,
                               mesh=mesh, model_axis=model_axis)

    def round_step(params, delta_prev, batches):
        k = jax.tree.leaves(batches)[0].shape[0]
        ids = jnp.arange(k, dtype=jnp.int32)
        # masks=None: fixed-shape silo streams are all-valid, and the
        # select-free scan avoids an extra full-parameter pass per step
        new_params, new_state, losses, diag = cohort(
            {"delta_prev": delta_prev}, params, batches, None, ids)
        # fedavg is the collective-volume comparison baseline: drop its
        # diagnostics so the unused norm reduction is DCE'd and the
        # compiled round carries no extra all-reduce vs plain FedAvg
        if algorithm == "fedavg":
            diag = {}
        metrics = {"train_loss": losses.mean(), **diag}
        return new_params, new_state["delta_prev"], metrics

    if mesh is None:
        return round_step
    # same layout as the simulation trainer: (state, params, batches, ...)
    # -> here state==delta_prev (a tree mirroring params, so on a
    # two-axis mesh it takes the params' per-leaf specs) and metrics are
    # scalars (replicated)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis_sizes.get(model_axis, 1) > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.sharding.rules import cohort_param_specs, to_named
        if params_template is None:
            raise ValueError(
                f"mesh carries a {model_axis!r} axis of size "
                f"{axis_sizes[model_axis]}: make_fl_round_step needs "
                "params_template= for the per-leaf model specs")
        p_s = to_named(cohort_param_specs(params_template, mesh,
                                          client_axis, model_axis), mesh)
        b_s = NamedSharding(mesh, P(client_axis))
        rep = NamedSharding(mesh, P())
        return jax.jit(round_step, in_shardings=(p_s, p_s, b_s),
                       out_shardings=(p_s, p_s, rep))
    from repro.sharding.rules import cohort_round_shardings
    (st_s, p_s, b_s, _, _), (po_s, so_s, _, m_s) = cohort_round_shardings(
        mesh, client_axis)
    return jax.jit(round_step, in_shardings=(p_s, st_s, b_s),
                   out_shardings=(po_s, so_s, m_s))


def fl_round_input_specs(cfg, *, clients: int, local_steps: int,
                         local_batch: int, seq_len: int):
    """ShapeDtypeStructs for the round's batch stack (LM training)."""
    shape = (clients, local_steps, local_batch, seq_len)
    return {"tokens": jax.ShapeDtypeStruct(shape, jnp.int32),
            "labels": jax.ShapeDtypeStruct(shape, jnp.int32)}
