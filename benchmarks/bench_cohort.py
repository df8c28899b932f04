"""Cohort round latency sweep — the perf receipt for the fused round and
its scaling levers (core/round.py, core/api.py, DESIGN.md §2):

  {serial, vectorized, sharded[, sharded2d]} x {prefetch} x {kernel}

serial        historical per-client dispatch (ExecConfig.vectorize=False)
vectorized    one fused jit program per round on a single device
sharded       client axis NamedSharding over the local devices
              (ExecConfig.shard_clients=True; force 8 host devices on CPU)
prefetch      double-buffered host ingest (ExecConfig.prefetch)
kernel        FedDPC epilogue through the batched Pallas kernel
              (FedDPCHyper.use_kernel; interpret mode on CPU)
sharded2d     --model-shards M > 1: the two-axis (clients x model) mesh —
              params/server state shard per leaf over a model axis of M
              inside each client slice (ExecConfig.shard_model); the
              receipt lands in BENCH_cohort_2axis.json

Per-mode stats include ``ingest_mean_s`` — the host time run_round spends
blocked on cohort stacking — so the prefetch win is measured directly.

  PYTHONPATH=src python -m benchmarks.bench_cohort --devices 8   # full sweep
  PYTHONPATH=src python -m benchmarks.bench_cohort --rounds 3 --devices 8

``--ingest-sweep`` runs the STAGED-INGEST receipt instead (DESIGN.md
§10): prefetch_depth in {1, 2, 4, 8} x {host-staged, device-staged},
reporting the split ingest waits (``ingest_host_mean_s`` = blocked on
staging, ``ingest_device_mean_s`` = blocked on H2D placement at
dispatch) -> BENCH_ingest.json. The headline number is
``transfer_wait_reduction_device_vs_host_d2``: how much of the depth-2
host-staged baseline's dispatch-side transfer wait device staging
removes (it moves the placement onto the staging thread, so the
consumer-side wait collapses to ~0 by construction).

``--codec-sweep`` runs the DELTA-CODEC receipt (DESIGN.md §13): the same
round under codec {identity, bf16, int8, int8+ef}, reporting the uplink
bytes the wire actually carries (``RoundRecord.comm_bytes_up``) and the
device-stage bytes of the encoded cohort payload
(``codec.EncodedCohort.nbytes`` — what ingest H2D placement moves) ->
BENCH_codec.json. The headline keys are the byte-reduction factors int8
vs identity; the acceptance bools (``int8_halves_uplink``,
``int8_shrinks_stage_bytes``) assert the §13 criteria on the receipt
itself so the bench gate holds them exactly.

``--multihost`` runs the HIERARCHICAL-AGGREGATION receipt (DESIGN.md
§15): the same sharded round flat (server folds K raw deltas) vs
hierarchical (E edge aggregators fold their cohort slices into partial
summaries; the server consumes E), including the Pallas-epilogue and
int8-dequant edge folds -> BENCH_multihost.json. Per mode it records
wall-clock AND the split uplink (``comm_bytes_edge_up`` /
``comm_bytes_server_up``) plus ``server_fanin`` — the headline is the
K -> E server fan-in reduction. With ``--processes N`` the whole bench
re-executes as an N-process jax.distributed job through
launch/distributed.spawn_local (single machine, 127.0.0.1 coordinator —
the offline-CI stand-in for a real multi-host launch); each process
stages only its local client shard and acts as one edge, and process 0
writes the receipt.

``--block-sweep`` runs the batched-epilogue BLOCK receipt instead: the
(rows, 128)-tile row-block size x cohort size K grid of
kernel.batched_epilogue (K·rows·128 f32 must stay VMEM-resident, so
the viable row block shrinks as K grows — the crossover this receipt
documents) -> BENCH_blocks.json. ``vmem_block_bytes`` per cell is the
exact-gated shape arithmetic; ``*_ms`` keys are wall-clock (interpret
mode off-TPU: a correctness/shape artifact, not kernel perf).

``--devices N`` must be handled BEFORE jax initializes (the device count
locks at first init), hence the argv scan at the top of this module
(and the ``--processes`` spawn, which must fork before this process
touches a backend).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict


def _maybe_force_devices(argv):
    n = None
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            n = argv[i + 1]
        elif a.startswith("--devices="):
            n = a.split("=", 1)[1]
    if n and int(n) > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={int(n)}").strip()
    return int(n) if n else None


def _maybe_spawn_processes(argv):
    """--processes N: re-exec this bench as an N-process jax.distributed
    job (launch/distributed.spawn_local — 127.0.0.1 coordinator, no
    external network). Returns the child results in the PARENT (which
    must exit without touching jax); returns None in the children
    (REPRO_DIST_PID set — they fall through, join the job in main())
    and in single-process runs. ``--devices`` means the TOTAL device
    count: it is stripped from the child argv and split evenly into
    per-process XLA host-device forces by spawn_local."""
    n = None
    for i, a in enumerate(argv):
        if a == "--processes" and i + 1 < len(argv):
            n = argv[i + 1]
        elif a.startswith("--processes="):
            n = a.split("=", 1)[1]
    n = int(n) if n else 0
    if n <= 1 or os.environ.get("REPRO_DIST_PID"):
        return None
    total = None
    child, skip = [], False
    for a in argv[1:]:
        if skip:
            total = int(a)
            skip = False
            continue
        if a == "--devices":
            skip = True
            continue
        if a.startswith("--devices="):
            total = int(a.split("=", 1)[1])
            continue
        child.append(a)
    from repro.launch.distributed import spawn_local
    return spawn_local(
        [sys.executable, os.path.abspath(argv[0]), *child], n,
        devices_per_process=max(1, (total or n) // n),
        timeout_s=1800.0)


_maybe_force_devices(sys.argv)
_spawned = _maybe_spawn_processes(sys.argv)
if _spawned is not None:
    # parent of a --processes job: the children did the work (process 0
    # wrote the receipt); surface their stdout and stop here
    for _i, (_rc, _out, _err) in enumerate(_spawned):
        sys.stdout.write(f"--- process {_i} ---\n{_out or ''}")
    sys.exit(0)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from repro.core.api import (AlgoConfig, ExecConfig,     # noqa: E402
                            FederatedTrainer)
from repro.core.baselines import default_hyper          # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(_ROOT, "BENCH_cohort_sharded.json")
# --model-shards sweeps land in their own receipt
DEFAULT_OUT_2AXIS = os.path.join(_ROOT, "BENCH_cohort_2axis.json")
# --ingest-sweep (depth x staging) receipt
DEFAULT_OUT_INGEST = os.path.join(_ROOT, "BENCH_ingest.json")
# --async-sweep (runtime model x buffer/concurrency) receipt
DEFAULT_OUT_ASYNC = os.path.join(_ROOT, "BENCH_async.json")
# --codec-sweep (delta codec x error feedback) receipt
DEFAULT_OUT_CODEC = os.path.join(_ROOT, "BENCH_codec.json")
# --multihost (flat vs hierarchical edge aggregation) receipt
DEFAULT_OUT_MULTIHOST = os.path.join(_ROOT, "BENCH_multihost.json")
# --block-sweep (batched-epilogue row block x K) receipt
DEFAULT_OUT_BLOCKS = os.path.join(_ROOT, "BENCH_blocks.json")

# mode name -> config overrides (use_kernel routes into the feddpc hyper,
# the rest are ExecConfig fields); the sweep skips nothing silently — a
# combo that fails records its error string in the payload.
MODES = [
    ("serial", dict(vectorize=False, prefetch=False)),
    ("vectorized", dict(prefetch=False)),
    ("vectorized+prefetch", dict(prefetch=True)),
    ("vectorized+kernel", dict(prefetch=False, use_kernel=True)),
    ("vectorized+prefetch+kernel", dict(prefetch=True, use_kernel=True)),
    ("sharded", dict(shard_clients=True, prefetch=False)),
    ("sharded+prefetch", dict(shard_clients=True, prefetch=True)),
    ("sharded+kernel", dict(shard_clients=True, prefetch=False,
                            use_kernel=True)),
    ("sharded+prefetch+kernel", dict(shard_clients=True, prefetch=True,
                                     use_kernel=True)),
]


def modes_for(model_shards: int):
    """The sweep's mode list; --model-shards M > 1 appends the two-axis
    (clients x model) regimes (DESIGN.md §2) — params/server state shard
    per leaf over a model axis of M within each client slice. The kernel
    mode is included to measure the documented fall-back to the
    reference epilogue under model-sharded leaves."""
    if model_shards <= 1:
        return MODES
    two = dict(shard_clients=True, shard_model=model_shards)
    return MODES + [
        ("sharded2d", dict(two, prefetch=False)),
        ("sharded2d+prefetch", dict(two, prefetch=True)),
        ("sharded2d+prefetch+kernel", dict(two, prefetch=True,
                                           use_kernel=True)),
    ]


def build_task(num_clients: int, batches_per_client: int, batch: int,
               dim: int, hidden: int, classes: int, seed: int = 0):
    """MLP classification sized by --dim/--hidden; the sharded receipt
    uses a >=1M-param model where the round is compute-bound."""
    r = np.random.RandomState(seed)
    scale = 1.0 / np.sqrt(dim)
    params = {
        "w1": jnp.asarray(r.randn(dim, hidden) * scale, jnp.float32),
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jnp.asarray(r.randn(hidden, classes) * scale, jnp.float32),
        "b2": jnp.zeros((classes,), jnp.float32),
    }

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        logits = h @ p["w2"] + p["b2"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, b["y"][:, None], axis=1))

    data = []
    for c in range(num_clients):
        rc = np.random.RandomState(seed + 1 + c)
        data.append([{"x": rc.randn(batch, dim).astype(np.float32),
                      "y": rc.randint(0, classes, size=batch).astype(np.int32)}
                     for _ in range(batches_per_client)])
    batch_fn = lambda c, t: data[c]
    return params, loss_fn, batch_fn


def bench(overrides: dict, *, params, loss_fn, batch_fn, k: int,
          rounds: int, warmup: int, algorithm: str) -> Dict:
    exec_kw = dict(overrides)
    hyper = default_hyper(algorithm,
                          use_kernel=exec_kw.pop("use_kernel", False))
    cfg = ExecConfig(rounds=warmup + rounds, clients_per_round=k, seed=0,
                     eval_every=10 ** 9, **exec_kw)
    algo = AlgoConfig(name=algorithm, eta_l=0.05, eta_g=0.1, hyper=hyper)
    with FederatedTrainer(loss_fn, params, k, batch_fn, cfg, None,
                          algo=algo) as tr:
        for t in range(warmup):                   # compile + cache warm
            tr.run_round(t)
        recs = [tr.run_round(t) for t in range(warmup, warmup + rounds)]
    times = np.asarray([r.seconds for r in recs])
    ingest = np.asarray([r.ingest_host_seconds + r.ingest_device_seconds
                         for r in recs])
    return {"mean_s": float(times.mean()), "p50_s": float(np.median(times)),
            "p90_s": float(np.percentile(times, 90)),
            "min_s": float(times.min()),
            "ingest_mean_s": float(ingest.mean()),
            "ingest_host_mean_s": float(np.mean(
                [r.ingest_host_seconds for r in recs])),
            "ingest_device_mean_s": float(np.mean(
                [r.ingest_device_seconds for r in recs])),
            "rounds": int(rounds),
            # per-round training curve: a CURVE-class gate key
            # (bench_gate.py) — a regressing loss trajectory fails the
            # lane pointwise, not just at the final round
            "train_loss_curve": [float(r.train_loss) for r in recs]}


def run_ingest_sweep(clients: int = 16, rounds: int = 10, warmup: int = 2,
                     batches_per_client: int = 4, batch: int = None,
                     dim: int = None, hidden: int = None, classes: int = 10,
                     algorithm: str = "feddpc", out: str = None) -> Dict:
    """Staged-ingest receipt (DESIGN.md §10): prefetch_depth x staging.

    Every mode runs the same vectorized (sharded when >1 device) round;
    only the ingest pipeline changes. Host-staged modes pay the H2D
    placement on the consumer thread at dispatch (ingest_device_mean_s
    measures it); device-staged modes run it on the staging thread,
    overlapped with compute. The default batch payload is sized LARGER
    (batch 32 x dim 1024) and the model SMALLER than the compute-bound
    cohort sweep's, so the per-round transfer is actually visible on
    CPU hosts; --batch/--dim/--hidden override it."""
    batch = 32 if batch is None else batch
    dim = 1024 if dim is None else dim
    hidden = 512 if hidden is None else hidden
    out = out or DEFAULT_OUT_INGEST
    sharded = len(jax.devices()) > 1
    params, loss_fn, batch_fn = build_task(
        clients, batches_per_client, batch, dim, hidden, classes)
    n_params = sum(int(np.prod(np.shape(x))) for x in jax.tree.leaves(params))
    results = {}
    for depth in (1, 2, 4, 8):
        for staged in ("host", "device"):
            mode = f"d{depth}+{staged}"
            overrides = dict(prefetch=True, prefetch_depth=depth,
                             device_stage=(staged == "device"),
                             shard_clients=sharded)
            try:
                results[mode] = bench(
                    overrides, params=params, loss_fn=loss_fn,
                    batch_fn=batch_fn, k=clients, rounds=rounds,
                    warmup=warmup, algorithm=algorithm)
                r = results[mode]
                print(f"{mode:12s} mean {r['mean_s']*1e3:9.3f} ms"
                      f"  ingest host {r['ingest_host_mean_s']*1e3:8.3f} ms"
                      f"  device {r['ingest_device_mean_s']*1e3:8.3f} ms")
            except Exception as e:            # record, never skip silently
                results[mode] = {"error": f"{type(e).__name__}: {e}"}
                print(f"{mode:12s} FAILED: {results[mode]['error']}")

    def dev_wait(m):
        return results.get(m, {}).get("ingest_device_mean_s")

    payload = {
        "bench": "cohort_ingest_staged",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "sharded": sharded,
        "algorithm": algorithm,
        "clients_per_round": clients,
        "batches_per_client": batches_per_client,
        "batch": batch, "dim": dim, "hidden": hidden,
        "model_params": n_params,
        "modes": results,
        "note": ("ingest_host_mean_s = consumer blocked on staging "
                 "(sample+read+stack); ingest_device_mean_s = consumer "
                 "blocked on H2D placement at dispatch — ~0 when "
                 "device_stage moved it onto the staging thread"),
    }
    base, dev = dev_wait("d2+host"), dev_wait("d4+device")
    if base and dev is not None:
        # the acceptance comparison: device staging vs the depth-2
        # host-staged baseline's dispatch-side transfer wait
        payload["transfer_wait_reduction_device_vs_host_d2"] = \
            1.0 - dev / base
    d2 = dev_wait("d2+device")
    if base and d2 is not None:
        payload["transfer_wait_reduction_device_d2_vs_host_d2"] = \
            1.0 - d2 / base
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    for key in ("transfer_wait_reduction_device_vs_host_d2",
                "transfer_wait_reduction_device_d2_vs_host_d2"):
        if key in payload:
            print(f"{key}: {payload[key]:.3f}")
    print(f"-> {out}")
    return payload


def run_async_sweep(clients: int = 16, rounds: int = 10, warmup: int = 2,
                    batches_per_client: int = 4, batch: int = None,
                    dim: int = None, hidden: int = None, classes: int = 10,
                    algorithm: str = "feddpc", out: str = None) -> Dict:
    """Buffered-async receipt (DESIGN.md §11): every runtime model
    (core/runtime.runtime_matrix) under two (buffer, concurrency)
    points — the sync-shaped anchor (B=K, concurrency 1) and the
    streaming point (B=K/2, concurrency 4, where staleness appears).

    Two metric classes land in the payload: wall-clock stats (machine-
    dependent, gated loosely) and SIMULATION metrics — the virtual-time
    staleness series, wave counts, and the final train loss — which are
    deterministic functions of the seed and gate tightly. The
    deterministic-runtime anchor must report identically-zero staleness
    (the regime-matrix anchor cell, checked here as
    ``anchor_zero_staleness``)."""
    from repro.core.runtime import runtime_matrix

    batch = 8 if batch is None else batch
    dim = 256 if dim is None else dim
    hidden = 512 if hidden is None else hidden
    out = out or DEFAULT_OUT_ASYNC
    params, loss_fn, batch_fn = build_task(
        clients, batches_per_client, batch, dim, hidden, classes)
    n_params = sum(int(np.prod(np.shape(x))) for x in jax.tree.leaves(params))
    points = [("anchor", clients, 1),
              ("stream", max(1, clients // 2), 4)]
    results = {}
    for rt_name in runtime_matrix(clients):
        for label, bsize, conc in points:
            mode = f"{rt_name}+{label}"
            cfg = ExecConfig(rounds=warmup + rounds, clients_per_round=clients,
                             seed=0, eval_every=10 ** 9, async_buffer=True,
                             buffer_size=bsize, async_concurrency=conc)
            algo = AlgoConfig(name=algorithm, eta_l=0.05, eta_g=0.1)
            try:
                runtime = runtime_matrix(clients)[rt_name]   # fresh state
                with FederatedTrainer(loss_fn, params, clients, batch_fn,
                                      cfg, None, algo=algo,
                                      runtime=runtime) as tr:
                    for t in range(warmup):
                        tr.run_round(t)
                    recs = [tr.run_round(t)
                            for t in range(warmup, warmup + rounds)]
                    waves = len(tr.schedule)
                times = np.asarray([r.seconds for r in recs])
                results[mode] = {
                    "mean_s": float(times.mean()),
                    "p50_s": float(np.median(times)),
                    "min_s": float(times.min()),
                    "rounds": int(rounds),
                    "buffer_size": int(bsize),
                    "concurrency": int(conc),
                    # simulation metrics: deterministic given the seed
                    "staleness_mean": float(np.mean(
                        [r.staleness_mean for r in recs])),
                    "staleness_max": float(max(
                        r.staleness_max for r in recs)),
                    "waves_dispatched": int(waves),
                    "final_train_loss": float(recs[-1].train_loss),
                    "train_loss_curve": [float(r.train_loss)
                                         for r in recs],
                }
                r = results[mode]
                print(f"{mode:24s} mean {r['mean_s']*1e3:9.3f} ms"
                      f"  staleness {r['staleness_mean']:6.3f}"
                      f" (max {r['staleness_max']:4.1f})"
                      f"  waves {r['waves_dispatched']:4d}")
            except Exception as e:            # record, never skip silently
                results[mode] = {"error": f"{type(e).__name__}: {e}"}
                print(f"{mode:24s} FAILED: {results[mode]['error']}")
    payload = {
        "bench": "cohort_async_buffered",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "algorithm": algorithm,
        "clients_per_round": clients,
        "batches_per_client": batches_per_client,
        "batch": batch, "dim": dim, "hidden": hidden,
        "model_params": n_params,
        "staleness_alpha": 0.5,
        "modes": results,
        "note": ("staleness_*/waves_dispatched/final_train_loss are "
                 "virtual-time SIMULATION metrics — deterministic given "
                 "the seed, gated tightly by bench_gate.py; *_s keys are "
                 "wall-clock and gated loosely"),
    }
    det = results.get("deterministic+anchor", {})
    if "staleness_max" in det:
        # the regime-matrix anchor cell property, asserted on the receipt
        payload["anchor_zero_staleness"] = (det["staleness_max"] == 0.0)
    stream = results.get("heavytail+stream", {})
    if "staleness_mean" in stream:
        payload["heavytail_stream_staleness_mean"] = \
            stream["staleness_mean"]
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    for key in ("anchor_zero_staleness", "heavytail_stream_staleness_mean"):
        if key in payload:
            print(f"{key}: {payload[key]}")
    print(f"-> {out}")
    return payload


def run_codec_sweep(clients: int = 16, rounds: int = 10, warmup: int = 2,
                    batches_per_client: int = 4, batch: int = None,
                    dim: int = None, hidden: int = None, classes: int = 10,
                    algorithm: str = "feddpc", out: str = None) -> Dict:
    """Delta-codec receipt (DESIGN.md §13): the same vectorized (sharded
    when >1 device) round under each uplink codec, plus int8 with
    server-side error feedback.

    Byte accounting has two independent receipts per mode, both
    deterministic functions of the model shapes (gated exactly):

      comm_bytes_up   what the cohort's LIVE clients ship per round —
                      ``RoundRecord.comm_bytes_up``, i.e. the payload
                      arrays as actually wired (q codes + per-leaf
                      scale/zero vectors)
      stage_bytes     the encoded cohort stack's device-placement bytes
                      (``EncodedCohort.nbytes`` — what
                      ``CohortPlacer.place_encoded`` moves over the bus)

    ``final_train_loss`` is a simulation metric (deterministic given the
    seed — lossy codecs quantize deterministically); wall-clock keys are
    gated loosely like every other sweep's."""
    from repro.codec import make_codec, tree_nbytes

    batch = 8 if batch is None else batch
    dim = 512 if dim is None else dim
    hidden = 2048 if hidden is None else hidden
    out = out or DEFAULT_OUT_CODEC
    sharded = len(jax.devices()) > 1
    params, loss_fn, batch_fn = build_task(
        clients, batches_per_client, batch, dim, hidden, classes)
    n_params = sum(int(np.prod(np.shape(x))) for x in jax.tree.leaves(params))
    raw_client_bytes = tree_nbytes(params)        # f32 delta, one client
    codec_modes = [
        ("identity", dict(codec="identity")),
        ("bf16", dict(codec="bf16")),
        ("int8", dict(codec="int8")),
        ("int8+ef", dict(codec="int8", codec_ef=True)),
    ]
    results = {}
    for mode, overrides in codec_modes:
        try:
            cfg = ExecConfig(rounds=warmup + rounds, clients_per_round=clients,
                             seed=0, eval_every=10 ** 9,
                             shard_clients=sharded, **overrides)
            algo = AlgoConfig(name=algorithm, eta_l=0.05, eta_g=0.1)
            with FederatedTrainer(loss_fn, params, clients, batch_fn,
                                  cfg, None, algo=algo) as tr:
                for t in range(warmup):               # compile warm
                    tr.run_round(t)
                recs = [tr.run_round(t)
                        for t in range(warmup, warmup + rounds)]
            times = np.asarray([r.seconds for r in recs])
            # accounting receipts: per-client wire bytes and the
            # K-stack's device-stage bytes depend only on the codec and
            # the model shapes (encoded_template is shape-level)
            codec_obj = make_codec(overrides["codec"])
            stats = {
                "mean_s": float(times.mean()),
                "p50_s": float(np.median(times)),
                "min_s": float(times.min()),
                "rounds": int(rounds),
                "comm_bytes_up": int(recs[-1].comm_bytes_up),
                "client_bytes_up": int(codec_obj.client_bytes(params)),
                "stage_bytes": int(tree_nbytes(
                    codec_obj.encoded_template(params, clients))),
                "error_feedback": bool(overrides.get("codec_ef", False)),
                "final_train_loss": float(recs[-1].train_loss),
                "train_loss_curve": [float(r.train_loss) for r in recs],
            }
            results[mode] = stats
            print(f"{mode:10s} mean {stats['mean_s']*1e3:9.3f} ms"
                  f"  round bytes {stats['comm_bytes_up']:>11d}"
                  f"  stage bytes {stats['stage_bytes']:>11d}")
        except Exception as e:                # record, never skip silently
            results[mode] = {"error": f"{type(e).__name__}: {e}"}
            print(f"{mode:10s} FAILED: {results[mode]['error']}")

    def bytes_of(m, key="comm_bytes_up"):
        return results.get(m, {}).get(key)

    payload = {
        "bench": "cohort_codec",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "sharded": sharded,
        "algorithm": algorithm,
        "clients_per_round": clients,
        "batches_per_client": batches_per_client,
        "batch": batch, "dim": dim, "hidden": hidden,
        "model_params": n_params,
        "raw_client_bytes": raw_client_bytes,
        "modes": results,
        "note": ("client_bytes_up/stage_bytes are deterministic functions "
                 "of the model shapes and the codec wire format "
                 "(DESIGN.md §13) — gated exactly; identity must equal "
                 "raw_client_bytes bitwise"),
    }
    ident, i8 = bytes_of("identity"), bytes_of("int8")
    if ident and i8:
        payload["comm_bytes_reduction_int8_vs_identity"] = ident / i8
        # the §13 acceptance bool, held exactly by the bench gate
        payload["int8_halves_uplink"] = ident / i8 >= 2.0
    b16 = bytes_of("bf16")
    if ident and b16:
        payload["comm_bytes_reduction_bf16_vs_identity"] = ident / b16
    sid, si8 = bytes_of("identity", "stage_bytes"), bytes_of("int8",
                                                             "stage_bytes")
    if sid and si8:
        payload["stage_bytes_reduction_int8_vs_identity"] = sid / si8
        payload["int8_shrinks_stage_bytes"] = si8 < sid
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    for key in ("comm_bytes_reduction_int8_vs_identity",
                "comm_bytes_reduction_bf16_vs_identity",
                "stage_bytes_reduction_int8_vs_identity",
                "int8_halves_uplink", "int8_shrinks_stage_bytes"):
        if key in payload:
            print(f"{key}: {payload[key]}")
    print(f"-> {out}")
    return payload


def run_multihost(clients: int = 16, rounds: int = 10, warmup: int = 2,
                  batches_per_client: int = 4, batch: int = None,
                  dim: int = None, hidden: int = None, classes: int = 10,
                  algorithm: str = "feddpc", out: str = None) -> Dict:
    """Hierarchical edge-aggregation receipt (DESIGN.md §15): the same
    client-sharded round flat (the server's fold consumes all K client
    deltas) vs hierarchical (E edge aggregators — one per host in a
    --processes job — each fold their local cohort slice into one
    partial summary; the server consumes E), plus the hierarchical fold
    through the Pallas epilogue and the int8 dequant path.

    Wall-clock keys gate loosely as always; the comm split is exact
    shape arithmetic, gated exactly: every mode's clients ship
    ``comm_bytes_up``; flat rounds report edge_up=0 / server_up=up,
    hierarchical rounds pay ``comm_bytes_up`` on the client->edge hop
    and E raw-f32 summaries on the edge->server hop. ``server_fanin``
    (K flat, E hierarchical) is the headline reduction."""
    batch = 8 if batch is None else batch
    dim = 256 if dim is None else dim
    hidden = 512 if hidden is None else hidden
    out = out or DEFAULT_OUT_MULTIHOST
    nproc = jax.process_count()
    edges = nproc if nproc > 1 else 2
    params, loss_fn, batch_fn = build_task(
        clients, batches_per_client, batch, dim, hidden, classes)
    n_params = sum(int(np.prod(np.shape(x))) for x in jax.tree.leaves(params))
    modes = [
        ("flat", dict(shard_clients=True, prefetch=True)),
        ("hier", dict(shard_clients=True, prefetch=True, edges=edges)),
        ("hier+kernel", dict(shard_clients=True, prefetch=True,
                             edges=edges, use_kernel=True)),
        ("hier+int8", dict(shard_clients=True, prefetch=True,
                           edges=edges, codec="int8")),
    ]
    results = {}
    for mode, overrides in modes:
        try:
            exec_kw = dict(overrides)
            hyper = default_hyper(algorithm,
                                  use_kernel=exec_kw.pop("use_kernel",
                                                         False))
            cfg = ExecConfig(rounds=warmup + rounds,
                             clients_per_round=clients, seed=0,
                             eval_every=10 ** 9, **exec_kw)
            algo = AlgoConfig(name=algorithm, eta_l=0.05, eta_g=0.1,
                              hyper=hyper)
            with FederatedTrainer(loss_fn, params, clients, batch_fn,
                                  cfg, None, algo=algo) as tr:
                for t in range(warmup):               # compile warm
                    tr.run_round(t)
                recs = [tr.run_round(t)
                        for t in range(warmup, warmup + rounds)]
            times = np.asarray([r.seconds for r in recs])
            results[mode] = {
                "mean_s": float(times.mean()),
                "p50_s": float(np.median(times)),
                "min_s": float(times.min()),
                "ingest_mean_s": float(np.mean(
                    [r.ingest_host_seconds + r.ingest_device_seconds
                     for r in recs])),
                "rounds": int(rounds),
                "server_fanin": int(edges if "edges" in overrides
                                    else clients),
                "comm_bytes_up": int(recs[-1].comm_bytes_up),
                "comm_bytes_edge_up": int(recs[-1].comm_bytes_edge_up),
                "comm_bytes_server_up": int(recs[-1].comm_bytes_server_up),
                "train_loss_curve": [float(r.train_loss) for r in recs],
            }
            r = results[mode]
            print(f"{mode:12s} mean {r['mean_s']*1e3:9.3f} ms"
                  f"  fan-in {r['server_fanin']:3d}"
                  f"  server uplink {r['comm_bytes_server_up']:>12d} B",
                  flush=True)
        except Exception as e:                # record, never skip silently
            results[mode] = {"error": f"{type(e).__name__}: {e}"}
            print(f"{mode:12s} FAILED: {results[mode]['error']}",
                  flush=True)
    payload = {
        "bench": "cohort_multihost_hier",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "processes": int(nproc),
        "edges": int(edges),
        "algorithm": algorithm,
        "clients_per_round": clients,
        "batches_per_client": batches_per_client,
        "batch": batch, "dim": dim, "hidden": hidden,
        "model_params": n_params,
        "modes": results,
        "note": ("comm_bytes_edge_up/server_up split the uplink across "
                 "the two hierarchy hops (exact shape arithmetic); "
                 "server_fanin is what the server's fold consumes per "
                 "round — K raw deltas flat, E partial summaries "
                 "hierarchical (DESIGN.md §15)"),
    }
    flat, hier = results.get("flat", {}), results.get("hier", {})
    if flat.get("server_fanin") and hier.get("server_fanin"):
        payload["server_fanin_flat"] = flat["server_fanin"]
        payload["server_fanin_hier"] = hier["server_fanin"]
        payload["server_fanin_reduced"] = \
            hier["server_fanin"] < flat["server_fanin"]
    if flat.get("comm_bytes_server_up") and \
            hier.get("comm_bytes_server_up"):
        payload["server_uplink_reduction_hier_vs_flat"] = \
            flat["comm_bytes_server_up"] / hier["comm_bytes_server_up"]
    if jax.process_index() == 0:
        with open(out, "w") as f:
            json.dump(payload, f, indent=1)
        for key in ("server_fanin_flat", "server_fanin_hier",
                    "server_fanin_reduced",
                    "server_uplink_reduction_hier_vs_flat"):
            if key in payload:
                print(f"{key}: {payload[key]}")
        print(f"-> {out}")
    return payload


def run_block_sweep(rounds: int = 10, warmup: int = 2, out: str = None,
                    classes: int = 10) -> Dict:
    """Batched-epilogue block receipt: kernel.batched_epilogue keeps all
    K clients' (rows, 128) tiles VMEM-resident at once, so the viable
    row block shrinks as K grows (default DEFAULT_ROWS/K, floor 8).
    This sweep times the row-block x K grid directly on one synthetic
    1M-element leaf — the crossover artifact behind that default.
    Off-TPU the kernel runs in interpret mode: the *_ms keys are then a
    correctness/shape artifact (gated loosely), while vmem_block_bytes
    is the exact VMEM footprint arithmetic."""
    import time as _time

    from repro.kernels.feddpc_project import kernel as KR

    out = out or DEFAULT_OUT_BLOCKS
    interpret = jax.default_backend() != "tpu"
    m_rows = 2048                       # 2048 x 128 f32 leaf = 1 MiB
    cells = {}
    for k in (4, 16, 64):
        r = np.random.RandomState(k)
        d3 = jnp.asarray(r.randn(k, m_rows, KR.LANE) * 1e-2, jnp.float32)
        p2 = jnp.asarray(r.randn(m_rows, KR.LANE) * 1e-2, jnp.float32)
        w2 = jnp.asarray(r.randn(m_rows, KR.LANE), jnp.float32)
        coefs = jnp.asarray(r.rand(k), jnp.float32)
        scales = jnp.asarray(1.0 + r.rand(k), jnp.float32)
        ref = None
        for rows in (8, 64, 512):
            label = f"k{k}_rows{rows}"
            try:
                def step():
                    return KR.batched_epilogue(d3, p2, w2, coefs, scales,
                                               0.1, rows=rows,
                                               interpret=interpret)
                w_out, dt = step()                       # compile + warm
                jax.block_until_ready((w_out, dt))
                if ref is None:
                    ref = (np.asarray(w_out), np.asarray(dt))
                else:                 # block size must not change math
                    np.testing.assert_allclose(np.asarray(w_out), ref[0],
                                               rtol=1e-5, atol=1e-6)
                    np.testing.assert_allclose(np.asarray(dt), ref[1],
                                               rtol=1e-5, atol=1e-6)
                times = []
                for _ in range(max(1, warmup - 1)):
                    jax.block_until_ready(step())
                for _ in range(rounds):
                    t0 = _time.perf_counter()
                    jax.block_until_ready(step())
                    times.append(_time.perf_counter() - t0)
                times = np.asarray(times)
                cells[label] = {
                    "mean_ms": float(times.mean() * 1e3),
                    "min_ms": float(times.min() * 1e3),
                    "rounds": int(rounds),
                    # K tiles of (rows, 128) f32 resident at once
                    "vmem_block_bytes": int(k * rows * KR.LANE * 4),
                    "block_consistent": True,
                }
                c = cells[label]
                print(f"{label:14s} mean {c['mean_ms']:9.3f} ms"
                      f"  VMEM block {c['vmem_block_bytes']:>9d} B")
            except Exception as e:            # record, never skip silently
                cells[label] = {"error": f"{type(e).__name__}: {e}"}
                print(f"{label:14s} FAILED: {cells[label]['error']}")
    payload = {
        "bench": "epilogue_block_sweep",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "interpret": bool(interpret),
        "lane": int(KR.LANE),
        "leaf_elems": int(m_rows * KR.LANE),
        "default_rows": int(KR.DEFAULT_ROWS),
        "modes": cells,
        "note": ("K (rows, 128) f32 tiles stay VMEM-resident per grid "
                 "step, so vmem_block_bytes = K*rows*128*4 is the "
                 "footprint the DEFAULT_ROWS/K row-block default keeps "
                 "bounded; block_consistent asserts the row block never "
                 "changes the math (allclose across the sweep)"),
    }
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"-> {out}")
    return payload


def run(clients: int = 16, rounds: int = 10, warmup: int = 2,
        batches_per_client: int = 4, batch: int = 8, dim: int = 512,
        hidden: int = 2048, classes: int = 10, algorithm: str = "feddpc",
        model_shards: int = 1, out: str = None) -> Dict:
    out = out or (DEFAULT_OUT_2AXIS if model_shards > 1 else DEFAULT_OUT)
    params, loss_fn, batch_fn = build_task(
        clients, batches_per_client, batch, dim, hidden, classes)
    n_params = sum(int(np.prod(np.shape(x))) for x in jax.tree.leaves(params))
    results = {}
    for mode, overrides in modes_for(model_shards):
        try:
            results[mode] = bench(
                overrides, params=params, loss_fn=loss_fn, batch_fn=batch_fn,
                k=clients, rounds=rounds, warmup=warmup, algorithm=algorithm)
            print(f"{mode:28s} mean {results[mode]['mean_s']*1e3:9.3f} ms"
                  f"  ingest {results[mode]['ingest_mean_s']*1e3:8.3f} ms")
        except Exception as e:                    # record, never skip silently
            results[mode] = {"error": f"{type(e).__name__}: {e}"}
            print(f"{mode:28s} FAILED: {results[mode]['error']}")

    def mean(m):
        return results.get(m, {}).get("mean_s")

    def ing(m):
        return results.get(m, {}).get("ingest_mean_s")

    payload = {
        "bench": ("cohort_round_2axis" if model_shards > 1
                  else "cohort_round_sharded"),
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "model_shards": model_shards,
        "algorithm": algorithm,
        "clients_per_round": clients,
        "batches_per_client": batches_per_client,
        "batch": batch, "dim": dim, "hidden": hidden,
        "model_params": n_params,
        "kernel_note": ("use_kernel modes run the Pallas epilogue in "
                        "INTERPRET mode on CPU — a correctness artifact, "
                        "not a perf number; the fused-pass win is a TPU "
                        "property (see kernels/feddpc_project/kernel.py)"),
        "modes": results,
    }
    if mean("serial") and mean("vectorized"):
        payload["speedup_vectorized_vs_serial"] = \
            mean("serial") / mean("vectorized")
    if mean("vectorized") and mean("sharded"):
        payload["speedup_sharded_vs_vectorized"] = \
            mean("vectorized") / mean("sharded")
    if ing("vectorized") and ing("vectorized+prefetch") is not None:
        payload["ingest_reduction_prefetch"] = \
            1.0 - ing("vectorized+prefetch") / ing("vectorized")
    if mean("vectorized") and mean("sharded2d"):
        payload["speedup_sharded2d_vs_vectorized"] = \
            mean("vectorized") / mean("sharded2d")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    for key in ("speedup_vectorized_vs_serial", "speedup_sharded_vs_vectorized",
                "speedup_sharded2d_vs_vectorized",
                "ingest_reduction_prefetch"):
        if key in payload:
            print(f"{key}: {payload[key]:.3f}")
    print(f"-> {out}")
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--batches-per-client", type=int, default=4)
    # batch/dim/hidden default per sweep (cohort: 8/512/2048 compute-
    # bound; --ingest-sweep: 32/1024/512 transfer-visible) — None here
    # means "that sweep's default"
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--dim", type=int, default=None)
    ap.add_argument("--hidden", type=int, default=None)
    ap.add_argument("--algorithm", default="feddpc")
    ap.add_argument("--devices", type=int, default=None,
                    help="force N host devices (must be set before jax "
                         "initializes; handled at module import)")
    ap.add_argument("--model-shards", type=int, default=1,
                    help=">1 appends the two-axis (clients x model) "
                         "sweep; receipts default to "
                         "BENCH_cohort_2axis.json")
    ap.add_argument("--ingest-sweep", action="store_true",
                    help="run the staged-ingest receipt instead: "
                         "prefetch_depth {1,2,4,8} x {host,device} "
                         "staging -> BENCH_ingest.json (DESIGN.md §10)")
    ap.add_argument("--async-sweep", action="store_true",
                    help="run the buffered-async receipt instead: every "
                         "runtime model x {anchor, streaming} points -> "
                         "BENCH_async.json (DESIGN.md §11)")
    ap.add_argument("--codec-sweep", action="store_true",
                    help="run the delta-codec receipt instead: codec "
                         "{identity, bf16, int8, int8+ef} uplink/stage "
                         "byte accounting -> BENCH_codec.json "
                         "(DESIGN.md §13)")
    ap.add_argument("--multihost", action="store_true",
                    help="run the hierarchical edge-aggregation receipt "
                         "instead: flat vs E-edge two-level fold with "
                         "the split uplink accounting -> "
                         "BENCH_multihost.json (DESIGN.md §15)")
    ap.add_argument("--processes", type=int, default=None,
                    help="re-exec as an N-process jax.distributed job "
                         "via launch/distributed.spawn_local (handled "
                         "at module import, like --devices, which then "
                         "means the TOTAL device count split across "
                         "processes); process 0 writes the receipt")
    ap.add_argument("--block-sweep", action="store_true",
                    help="run the batched-epilogue block receipt "
                         "instead: row-block x K grid with exact VMEM "
                         "footprint arithmetic -> BENCH_blocks.json")
    ap.add_argument("--out", default=None,
                    help="defaults to BENCH_cohort_sharded.json, "
                         "BENCH_cohort_2axis.json with --model-shards, "
                         "BENCH_ingest.json with --ingest-sweep, "
                         "BENCH_async.json with --async-sweep, or "
                         "BENCH_codec.json with --codec-sweep")
    a = ap.parse_args(argv)
    # multi-process children (spawned at module import by --processes):
    # join the jax.distributed job before the first device query; a
    # no-op in single-process runs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.distributed import maybe_initialize
    maybe_initialize()
    enable_compile_cache()
    if a.block_sweep:
        run_block_sweep(rounds=a.rounds, warmup=a.warmup, out=a.out)
    elif a.multihost:
        run_multihost(clients=a.clients, rounds=a.rounds,
                      warmup=a.warmup,
                      batches_per_client=a.batches_per_client,
                      batch=a.batch, dim=a.dim, hidden=a.hidden,
                      algorithm=a.algorithm, out=a.out)
    elif a.codec_sweep:
        run_codec_sweep(clients=a.clients, rounds=a.rounds,
                        warmup=a.warmup,
                        batches_per_client=a.batches_per_client,
                        batch=a.batch, dim=a.dim, hidden=a.hidden,
                        algorithm=a.algorithm, out=a.out)
    elif a.async_sweep:
        run_async_sweep(clients=a.clients, rounds=a.rounds,
                        warmup=a.warmup,
                        batches_per_client=a.batches_per_client,
                        batch=a.batch, dim=a.dim, hidden=a.hidden,
                        algorithm=a.algorithm, out=a.out)
    elif a.ingest_sweep:
        run_ingest_sweep(clients=a.clients, rounds=a.rounds,
                         warmup=a.warmup,
                         batches_per_client=a.batches_per_client,
                         batch=a.batch, dim=a.dim, hidden=a.hidden,
                         algorithm=a.algorithm, out=a.out)
    else:
        run(clients=a.clients, rounds=a.rounds, warmup=a.warmup,
            batches_per_client=a.batches_per_client,
            batch=8 if a.batch is None else a.batch,
            dim=512 if a.dim is None else a.dim,
            hidden=2048 if a.hidden is None else a.hidden,
            algorithm=a.algorithm,
            model_shards=a.model_shards, out=a.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
