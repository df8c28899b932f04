"""End-to-end federated training driver (simulation mode — reproduces the
paper's experiments on synthetic heterogeneous data).

  PYTHONPATH=src python -m repro.launch.train \
      --model lenet5 --algorithm feddpc --rounds 50 --alpha 0.2 \
      --clients 100 --participation 0.1 --eta-l 0.01 --eta-g 0.01

Built on the composable engine (DESIGN.md §3): the participation model is
selectable (--sampler uniform|weighted|cyclic|markov), vision data
streams through the staged ingest pipeline (DESIGN.md §10) — synthetic
by default, or the REAL disk-backed datasets via --dataset
cifar10|cifar100|tiny-imagenet --data-root <standard download dir>,
with --prefetch-depth/--host-staged steering the staging ring —
--shard-clients/--model-shards turn on the sharded cohort round
(--model-shards M > 1 builds the two-axis (clients, model) mesh of
DESIGN.md §2 — per-leaf model-sharded params for >HBM configs), and
--ckpt-dir/--ckpt-every/--resume checkpoint the full TrainerState so an
interrupted run continues exactly where it stopped (mesh-shape changes
across save/resume included). The chaos-hardening levers of DESIGN.md
§12 ride along: --guard validates every client delta, --round-deadline
bounds each round in virtual time, --fault-plan replays a seeded
injector schedule, and --ingest-max-restarts supervises the staging
producer. --edges folds the cohort through hierarchical edge
aggregators (DESIGN.md §15) — under launch/distributed.spawn_local the
same driver runs one process per "host" — and --health-log streams the
run-health monitor's verdicts to a JSONL tracker file.

Also supports federated *LM* training with any assigned architecture's
smoke config (--model starcoder2-3b etc.) — the beyond-paper scenario
(cross-silo federated pretraining).
"""
from __future__ import annotations

import argparse
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, get_config
from repro.core.api import AlgoConfig, ExecConfig, FederatedTrainer
from repro.core.baselines import default_hyper
from repro.core.samplers import (CyclicSampler, MarkovSampler,
                                 UniformSampler, WeightedSampler)
from repro.data.dirichlet import dirichlet_partition
from repro.data.synthetic import make_lm_dataset
from repro.ingest import (CIFAR10Source, CIFAR100Source, ListDataSource,
                          StreamingImageSource, TinyImageNetSource,
                          build_federated_image_data)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.models.vision import (VisionConfig, init_vision, vision_accuracy,
                                 vision_loss_fn)


def build_vision_task(args):
    family = "lenet5" if args.model == "lenet5" else "resnet18"
    if args.dataset == "synthetic":
        nclass = {"lenet5": 10, "resnet18-gn": args.num_classes}.get(
            args.model, args.num_classes)
        data = build_federated_image_data(
            num_classes=nclass, num_clients=args.clients, alpha=args.alpha,
            samples_per_class=args.samples_per_class, seed=args.seed)
        # streaming: per-round batches materialize on the ingest path
        source = StreamingImageSource(data, args.batch_size,
                                      args.local_epochs)
        te_x, te_y = data.test_images, data.test_labels
        image_size = 32
    else:
        # disk-backed reader (ingest/datasets.py): Dirichlet-partitioned
        # on load, records decode/augment lazily on the staging thread
        if not args.data_root:
            raise SystemExit(f"--dataset {args.dataset} needs --data-root "
                             "(the standard download directory)")
        src_cls = {"cifar10": CIFAR10Source, "cifar100": CIFAR100Source,
                   "tiny-imagenet": TinyImageNetSource}[args.dataset]
        src_kw = {}
        if args.dataset == "tiny-imagenet":
            src_kw["decode_workers"] = args.decode_workers
        source = src_cls(args.data_root, num_clients=args.clients,
                         alpha=args.alpha, batch_size=args.batch_size,
                         local_epochs=args.local_epochs,
                         augment=args.augment, seed=args.seed, **src_kw)
        nclass = source.num_classes
        te_x, te_y = source.test_arrays()
        image_size = 64 if args.dataset == "tiny-imagenet" else 32
    vc = VisionConfig(name=args.model, family=family, num_classes=nclass,
                      image_size=image_size)
    params = init_vision(vc, jax.random.PRNGKey(args.seed))
    loss_fn = functools.partial(vision_loss_fn, vc)
    te_x, te_y = jnp.asarray(te_x), jnp.asarray(te_y)
    # the test set is an argument, not a closed-over constant: baked in,
    # it would make the eval program as large as the test set
    accuracy = jax.jit(functools.partial(vision_accuracy, vc))
    eval_fn = lambda p: accuracy(p, te_x, te_y)
    return params, loss_fn, source, eval_fn, args.clients


def build_lm_task(args):
    cfg = get_config(args.model, smoke=True)
    tokens, topics = make_lm_dataset(args.clients * 16, args.seq_len,
                                     cfg.vocab_size, seed=args.seed)
    parts = dirichlet_partition(topics, args.clients, args.alpha,
                                seed=args.seed, min_size=1)
    params = tf.init_lm(cfg, jax.random.PRNGKey(args.seed), jnp.float32)

    def loss_fn(p, batch):
        return tf.loss_fn(cfg, p, batch)

    def batch_fn(c, t):
        idx = parts[c]
        rng = np.random.RandomState(hash((c, t)) % (2 ** 31))
        sel = idx[rng.permutation(len(idx))][:args.batch_size]
        if len(sel) < args.batch_size:
            sel = np.concatenate([sel] * ((args.batch_size // max(len(sel), 1))
                                          + 1))[:args.batch_size]
        tk = tokens[sel]
        return [{"tokens": tk[:, :-1], "labels": tk[:, 1:]}]

    ho = tokens[:64]
    ho_batch = {"tokens": jnp.asarray(ho[:, :-1]),
                "labels": jnp.asarray(ho[:, 1:])}

    @jax.jit
    def eval_fn(p):    # negative perplexity proxy -> "accuracy" slot
        return -loss_fn(p, ho_batch)

    return params, loss_fn, ListDataSource(batch_fn), eval_fn, args.clients


def build_sampler(args, source, num_clients: int, cohort: int):
    if args.sampler == "uniform":
        return UniformSampler(num_clients, cohort)
    if args.sampler == "weighted":
        if hasattr(source, "client_weights"):   # image sources, disk-backed
            weights = source.client_weights()
        else:   # LM task: uniform shard sizes, degenerate but valid
            weights = np.ones(num_clients)
        return WeightedSampler(weights, cohort)
    if args.sampler == "cyclic":
        return CyclicSampler(num_clients, cohort)
    if args.sampler == "markov":
        return MarkovSampler(num_clients, cohort,
                             p_on=args.markov_p_on, p_off=args.markov_p_off)
    raise ValueError(args.sampler)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="lenet5",
                    choices=["lenet5", "resnet18-gn", *ARCH_IDS])
    ap.add_argument("--algorithm", default="feddpc")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--participation", type=float, default=0.1)
    ap.add_argument("--sampler", default="uniform",
                    choices=["uniform", "weighted", "cyclic", "markov"])
    ap.add_argument("--markov-p-on", type=float, default=0.5)
    ap.add_argument("--markov-p-off", type=float, default=0.5)
    ap.add_argument("--dataset", default="synthetic",
                    choices=["synthetic", "cifar10", "cifar100",
                             "tiny-imagenet"],
                    help="vision data: offline synthetic (default) or a "
                         "disk-backed reader over the standard download "
                         "layout under --data-root (DESIGN.md §10)")
    ap.add_argument("--data-root", default=None,
                    help="directory holding cifar-10-batches-py / "
                         "cifar-100-python / tiny-imagenet-200")
    ap.add_argument("--augment", action="store_true",
                    help="random crop + flip on the ingest path "
                         "(disk-backed datasets)")
    ap.add_argument("--alpha", type=float, default=0.2)
    ap.add_argument("--eta-l", type=float, default=0.01)
    ap.add_argument("--eta-g", type=float, default=0.01)
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--local-epochs", type=int, default=1)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--samples-per-class", type=int, default=100)
    ap.add_argument("--num-classes", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--serial", action="store_true",
                    help="per-client dispatch instead of the fused "
                         "cohort-vectorized round (debug/reference path)")
    ap.add_argument("--shard-clients", action="store_true",
                    help="shard the cohort's client axis over the local "
                         "devices (DESIGN.md §2)")
    ap.add_argument("--model-shards", type=int, default=1,
                    help="model-axis shards per client slice: >1 builds "
                         "the two-axis (clients, model) mesh so params/"
                         "server state shard per leaf over `model` (the "
                         ">HBM layout); must divide the device count")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="staging-ring depth: cohort buffers the ingest "
                         "pipeline cycles through (DESIGN.md §10)")
    ap.add_argument("--host-staged", action="store_true",
                    help="keep the device-place stage on the consumer "
                         "thread (H2D at dispatch) instead of the "
                         "staging thread — the ingest bench's baseline")
    ap.add_argument("--async-buffer", action="store_true",
                    help="buffered-async rounds (DESIGN.md §11): waves "
                         "train against possibly-stale snapshots and the "
                         "server steps every --buffer-size arrivals with "
                         "staleness-discounted aggregation")
    ap.add_argument("--buffer-size", type=int, default=None,
                    help="arrivals per async server step (default: the "
                         "cohort size — the sync-equivalent anchor)")
    ap.add_argument("--staleness-alpha", type=float, default=0.5,
                    help="staleness discount exponent: w(s)=(1+s)^-alpha")
    ap.add_argument("--async-concurrency", type=int, default=1,
                    help="max waves in flight at once (>1 lets fresh "
                         "waves overlap stale stragglers)")
    ap.add_argument("--runtime", default="deterministic",
                    choices=["deterministic", "exponential", "heavytail",
                             "markov"],
                    help="client runtime model: arrival latencies + "
                         "dropout of the async waves (core/runtime.py)")
    ap.add_argument("--runtime-dropout", type=float, default=0.0,
                    help="per-wave client dropout probability of the "
                         "exponential/heavytail/markov runtime models")
    ap.add_argument("--ingest-stall-s", type=float, default=None,
                    help="staging-ring stall deadline in seconds (a hung "
                         "producer raises instead of spinning forever)")
    ap.add_argument("--guard", action="store_true",
                    help="update-guard validation (DESIGN.md §12): "
                         "quarantine non-finite / exploded-norm client "
                         "deltas, clip outliers against the rolling "
                         "robust norm threshold")
    ap.add_argument("--round-deadline", type=float, default=None,
                    help="virtual-seconds round deadline (DESIGN.md "
                         "§12): sync rounds drop-and-mask clients whose "
                         "runtime draw misses it; async rounds fold the "
                         "partial buffer (needs a --runtime model)")
    ap.add_argument("--fault-plan", default=None,
                    help="chaos harness: JSON FaultPlan config (inline "
                         "string or @/path/to/plan.json) — the seeded "
                         "injector schedule of core/faults.py")
    ap.add_argument("--codec", default=None,
                    help="delta codec for the client->server uplink "
                         "(DESIGN.md §13): identity | bf16 | int8 | "
                         "int8_sym | int8_sr — quantized wire payloads "
                         "with per-leaf scales; identity is bitwise "
                         "equal to no codec")
    ap.add_argument("--server-opt", default="sgd",
                    choices=["sgd", "fedadam", "fedyogi"],
                    help="server-side optimizer (DESIGN.md §14): "
                         "precondition every rule's post-projection "
                         "aggregate with fedadam/fedyogi moments; sgd "
                         "(default) is today's step, bitwise")
    ap.add_argument("--health", action="store_true",
                    help="run-health monitor (DESIGN.md §14): rolling-"
                         "median loss spike / NaN detection, staleness "
                         "and quarantine-rate trend alarms over the "
                         "RoundRecord stream")
    ap.add_argument("--health-patience", type=int, default=None,
                    help="early-stop after N consecutive alarmed rounds "
                         "(needs --health; default: alarms only)")
    ap.add_argument("--health-log", default=None,
                    help="stream every health verdict to this JSONL "
                         "tracker file as the run goes (needs --health; "
                         "one JSON object per round, flushed per "
                         "verdict — in multi-process jobs process 0 "
                         "writes)")
    ap.add_argument("--edges", type=int, default=None,
                    help="hierarchical edge aggregation (DESIGN.md §15): "
                         "fold the cohort through E edge aggregators "
                         "(must divide the padded cohort) so the server "
                         "consumes E partial summaries instead of K raw "
                         "deltas; in multi-process jobs each process is "
                         "one edge over its local client shard")
    ap.add_argument("--codec-ef", action="store_true",
                    help="server-side error feedback for a lossy "
                         "--codec: clients ship delta + the running "
                         "mean quantization residual, so compression "
                         "error cancels across rounds instead of "
                         "accumulating (needs a lossy codec)")
    ap.add_argument("--decode-workers", type=int, default=0,
                    help="bounded thread pool for the per-image file "
                         "decode of path-indexed datasets "
                         "(tiny-imagenet); 0 = serial decode, output "
                         "order is identical either way")
    ap.add_argument("--ingest-max-restarts", type=int, default=0,
                    help="supervised staging-producer restarts: retry a "
                         "crashed produce up to N times (bounded "
                         "exponential backoff) before failing the run")
    ap.add_argument("--out", default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save the TrainerState every N rounds (0 = only "
                         "at the end, and only when --ckpt-dir is set)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest TrainerState from --ckpt-dir "
                         "and continue the run exactly where it stopped")
    return ap


def build_configs(args, num_clients: int):
    """(AlgoConfig, ExecConfig) the CLI runs for parsed ``args``."""
    cohort = max(1, int(round(num_clients * args.participation)))
    algo = AlgoConfig(name=args.algorithm, eta_l=args.eta_l,
                      eta_g=args.eta_g,
                      hyper=default_hyper(args.algorithm, lam=args.lam),
                      server_opt=args.server_opt)
    cfg = ExecConfig(
        rounds=args.rounds, clients_per_round=cohort, seed=args.seed,
        eval_every=args.eval_every, vectorize=not args.serial,
        shard_clients=args.shard_clients, shard_model=args.model_shards,
        prefetch_depth=args.prefetch_depth,
        device_stage=not args.host_staged,
        async_buffer=args.async_buffer, buffer_size=args.buffer_size,
        staleness_alpha=args.staleness_alpha,
        async_concurrency=args.async_concurrency,
        ingest_stall_s=args.ingest_stall_s,
        guard=args.guard, round_deadline=args.round_deadline,
        ingest_max_restarts=args.ingest_max_restarts,
        codec=args.codec, codec_ef=(True if args.codec_ef else None),
        health=args.health, health_patience=args.health_patience,
        health_log=args.health_log, edges=args.edges,
        decode_workers=args.decode_workers,
        batch_size=args.batch_size, local_epochs=args.local_epochs)
    return algo, cfg


def main(argv=None):
    args = build_parser().parse_args(argv)

    # multi-process jobs (DESIGN.md §15): wire jax.distributed from the
    # REPRO_DIST_* environment BEFORE the first device query; a no-op in
    # single-process runs
    from repro.launch.distributed import maybe_initialize
    maybe_initialize()
    enable_compile_cache()

    if args.model in ("lenet5", "resnet18-gn"):
        params, loss_fn, source, eval_fn, k = build_vision_task(args)
    else:
        params, loss_fn, source, eval_fn, k = build_lm_task(args)

    algo, cfg = build_configs(args, k)
    sampler = build_sampler(args, source, k, cfg.clients_per_round)
    runtime = None
    if args.async_buffer or args.round_deadline is not None:
        from repro.core.runtime import make_runtime
        rt_kw = ({} if args.runtime == "deterministic"
                 else {"dropout": args.runtime_dropout})
        runtime = make_runtime(args.runtime, k, **rt_kw)
    fault_plan = None
    if args.fault_plan:
        from repro.core.faults import FaultPlan
        raw = args.fault_plan
        if raw.startswith("@"):
            with open(raw[1:]) as fh:
                raw = fh.read()
        fault_plan = FaultPlan.from_config(json.loads(raw))

    if args.resume:
        if not args.ckpt_dir:
            raise SystemExit("--resume needs --ckpt-dir")
        trainer = FederatedTrainer.resume(
            args.ckpt_dir, loss_fn, params, k, source, cfg, eval_fn,
            algo=algo, sampler=sampler, runtime=runtime,
            fault_plan=fault_plan)
        print(f"resumed from {args.ckpt_dir} at round {trainer.start_round}")
    else:
        trainer = FederatedTrainer(loss_fn, params, k, source, cfg, eval_fn,
                                   algo=algo, sampler=sampler,
                                   runtime=runtime, fault_plan=fault_plan)
    with trainer:
        if args.ckpt_dir and args.ckpt_every > 0:
            for t in range(trainer.start_round, args.rounds):
                rec = trainer.run_round(t)
                print(f"[{args.algorithm}] round {t:4d} "
                      f"loss={rec.train_loss:.4f}")
                if (t + 1) % args.ckpt_every == 0:
                    trainer.save(args.ckpt_dir)
            trainer.finalize()
            hist = trainer.history
        else:
            hist = trainer.run(verbose=True)
        if args.ckpt_dir:
            path = trainer.save(args.ckpt_dir)
            print("checkpoint written to", path)
        best, at = trainer.best_accuracy
        if args.health and trainer.health_report is not None:
            hr = trainer.health_report
            print(f"health: {hr.alarmed_rounds} alarmed rounds "
                  f"(spikes {hr.spike_rounds}, "
                  f"nonfinite {hr.nonfinite_rounds})"
                  + (" — EARLY STOP" if hr.should_stop else ""))
    print(f"best eval {best} @ round {at}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump([r.__dict__ for r in hist], f, indent=1, default=float)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
