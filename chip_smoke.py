#!/usr/bin/env python3
"""Chip smoke test: the FedDPC training path, run once on a TPU.

    python3 chip_smoke.py              # one chip: phases A and B
    python3 chip_smoke.py --chips 4    # four chips: the sharded rounds

The task is the paper's CIFAR-100 setting (FedDPC §5.2.1) at full model
width: ResNet-18-GN (configs/paper_resnet18.py, 11,220,132 parameters)
on seeded synthetic CIFAR-100-shaped data, 100 clients, Dirichlet
alpha=0.2, participation 0.1 (K=10), 1 local epoch, seed 0, cut to 3
rounds. The per-client batch is 64, the CLI's default: compiled for a
v5e, one vmapped local step over the cohort needs about 8 GB of
temporaries at 64 and about 14 GB at the paper's 256, of 16 GB.

One chip:
  A. the train CLI (``repro.launch.train.main``) with its default jnp
     server epilogue;
  B. the same task, built by the same ``build_vision_task``, through
     ``FederatedTrainer`` with the Pallas epilogue
     (``FedDPCHyper(use_kernel=True)``). Its compiled round program must
     hold the Mosaic custom call, so no kernel ran interpreted.
  Every per-round train loss is finite, and A's and B's agree within
  ``AB_RTOL``. Then each of the six FedDPC kernels runs compiled on the
  chip against its ref.py oracle, within ``KERNEL_TOL``.

Four chips (``--chips 4``), and nothing else: the task on one chip as
the reference, then client-sharded over 4 chips (K padded to 12), then
on a 2x2 clients x model mesh. Each sharded run's losses agree with the
reference within ``SHARDED_RTOL``, and its cohort inputs and params span
all 4 chips.

Everything runs in this one process, which holds the chips. The script
exits non-zero, printing no result, when JAX's first device is not a
TPU. Its last stdout line is one JSON object naming the device. The
round times it prints are a smoke check, not a benchmark metric.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out"

TASK = ["--model", "resnet18-gn", "--num-classes", "100",
        "--samples-per-class", "500", "--clients", "100", "--alpha", "0.2",
        "--participation", "0.1", "--batch-size", "64",
        "--local-epochs", "1", "--seed", "0", "--rounds", "3",
        "--eval-every", "3"]

# A and B run the same data, sampling and local training, so their
# round-1 losses are equal. They differ in how the server epilogue sums
# the K client terms (the kernel one client at a time, XLA in its own
# order), about an f32 ulp in the server update. The next rounds' local
# SGD runs its f32 convolutions at the TPU's default precision, one bf16
# pass, where an ulp can flip a bf16 rounding, so the difference grows
# round by round: 8.2e-5 relative at round 3 on a v5e. The loss check
# is end-to-end consistency; KERNEL_TOL holds the arithmetic itself.
AB_RTOL = 5e-4
# Each kernel against its oracle: the same f32 arithmetic in another
# summation order, relative to the output's largest magnitude.
KERNEL_TOL = 1e-5
# The sharded rounds differ from round 1 on: each chip runs the vmapped
# convolutions over its own slice of the cohort (3 or 5 clients, not
# 10), which XLA tiles and accumulates differently at the bf16-pass
# precision above, and the M local steps compound it. They also take the
# jnp epilogue (a Mosaic kernel cannot be partitioned) and reduce across
# chips in another order. Measured on a v5e: 8.5e-4 (sharded1d) and
# 8.8e-4 (sharded2d) by round 3, so this bound has little room.
SHARDED_RTOL = 1e-3

MODEL_PARAMS = 11_220_132       # configs/paper_resnet18.CONFIG


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(1)


class CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self, jax):
        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.counts[event.rsplit("_", 1)[-1]] += 1

    def snapshot(self):
        return dict(self.counts)

    def since(self, before):
        return {k: self.counts[k] - before.get(k, 0)
                for k in ("hits", "misses")}


def losses_of(history):
    return [float(r["train_loss"]) for r in history]


def run_cli(task, tag):
    """Runs the train CLI in-process; returns its round records."""
    from repro.launch import train
    out = OUT / f"smoke_{tag}.json"
    rc = train.main(task + ["--out", str(out)])
    if rc != 0:
        fail(f"{tag}: train.main returned {rc}")
    return json.loads(out.read_text())


def run_trainer(task, *, use_kernel, extra=()):
    """The CLI's task and configuration through FederatedTrainer with the
    chosen epilogue. Returns (round records, trainer, abstract arguments
    of the last round call, jitted round)."""
    import jax
    import numpy as np
    from repro.core.api import FederatedTrainer
    from repro.core.baselines import FedDPCHyper
    from repro.launch import train

    args = train.build_parser().parse_args(list(task) + list(extra))
    params, loss_fn, source, eval_fn, k = train.build_vision_task(args)
    algo, cfg = train.build_configs(args, k)
    algo = dataclasses.replace(
        algo, hyper=FedDPCHyper(lam=args.lam, use_kernel=use_kernel))
    sampler = train.build_sampler(args, source, k, cfg.clients_per_round)
    trainer = FederatedTrainer(loss_fn, params, k, source, cfg, eval_fn,
                               algo=algo, sampler=sampler)
    # keep the abstract arguments of the round program's last call, to
    # compile that same program again and read its text
    jitted, seen = trainer._cohort_round, {}

    def round_call(*a):
        seen["args"] = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                           sharding=getattr(x, "sharding",
                                                            None)), a)
        return jitted(*a)

    trainer._cohort_round = round_call
    with trainer:
        history = [dataclasses.asdict(r) for r in trainer.run()]
    return history, trainer, seen["args"], jitted


def round_program(jitted, abstract_args):
    """The compiled round program of the last round call."""
    return jitted.lower(*abstract_args).compile()


def epilogue_of(text: str) -> str:
    return "pallas" if "tpu_custom_call" in text else "jnp"


def check_width(trainer):
    import jax
    n = sum(x.size for x in jax.tree.leaves(trainer.params))
    if n != MODEL_PARAMS:
        fail(f"model has {n} params, expected {MODEL_PARAMS}")


def report(tag, history, device):
    import numpy as np
    losses = losses_of(history)
    if not np.all(np.isfinite(losses)):
        fail(f"{tag}: non-finite train loss {losses}")
    stats = device.memory_stats() or {}
    print(f"{tag}: train_loss per round {losses}")
    print(f"{tag}: warm round wall time {history[-1]['seconds']} s "
          f"(smoke timing, not a metric); peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    return losses


def compare(tag, got, want, rtol):
    import numpy as np
    rel = np.abs(np.subtract(got, want)) / np.abs(want)
    print(f"{tag}: max relative loss difference {float(rel.max())} "
          f"(tolerance {rtol})")
    if not np.allclose(got, want, rtol=rtol, atol=0.0):
        fail(f"{tag}: losses {got} differ from {want} beyond rtol={rtol}")


def one_chip(jax, cache):
    device = jax.devices()[0]
    hist_a = run_cli(TASK, "phase_a")
    loss_a = report("phase A (CLI, jnp epilogue)", hist_a, device)

    before = cache.snapshot()
    hist_b, trainer, abstract, jitted = run_trainer(TASK, use_kernel=True)
    hits = cache.since(before)
    check_width(trainer)
    loss_b = report("phase B (FederatedTrainer, Pallas epilogue)", hist_b,
                    device)
    print(f"phase B: persistent compilation cache hits={hits['hits']} "
          f"misses={hits['misses']}; compile served from the cache: "
          f"{hits['hits'] > 0 and hits['misses'] == 0}")
    program = round_program(jitted, abstract)
    text = program.as_text()
    n_calls = text.count("tpu_custom_call")
    print(f"phase B: round program holds {n_calls} tpu_custom_call sites "
          f"(epilogue: {epilogue_of(text)}); compiler's temp estimate "
          f"{program.memory_analysis().temp_size_in_bytes} bytes")
    if n_calls == 0:
        fail("phase B: the round program holds no Mosaic custom call")
    compare("phase A vs B", loss_b, loss_a, AB_RTOL)
    check_kernels(jax)


def check_kernels(jax):
    """Each FedDPC Pallas kernel, compiled, on the chip against its ref.py
    oracle at ResNet-18's largest leaf (3x3x512x512: 18432 rows of 128),
    K=10, int8 payloads for the dequant pair. The error is the largest
    absolute difference over the output's largest magnitude."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.feddpc_project import kernel as K, ref as R
    m, k, eta = 3 * 3 * 512 * 512 // K.LANE, 10, 0.3
    r = jax.random.split(jax.random.PRNGKey(0), 9)
    d3 = jax.random.normal(r[0], (k, m, K.LANE))
    p2 = jax.random.normal(r[1], (m, K.LANE))
    w2 = jax.random.normal(r[2], (m, K.LANE))
    coefs = jax.random.normal(r[3], (k,))
    scales = 1.0 + jnp.abs(jax.random.normal(r[4], (k,)))
    wgts = jax.random.uniform(r[5], (k,), minval=0.1, maxval=1.0)
    q3 = jax.random.randint(r[6], (k, m, K.LANE), -128, 128, jnp.int8)
    qs = jax.random.uniform(r[7], (k,), minval=1e-3, maxval=1e-2)
    qz = 1e-3 * jax.random.normal(r[8], (k,))
    # arrays go in as arguments: closed over, they would be compiled in
    a = {"d3": d3, "p2": p2, "w2": w2, "c": coefs, "s": scales, "wt": wgts,
         "q3": q3, "qs": qs, "qz": qz,
         "bad": d3[0].at[::997, 5].set(jnp.nan)}   # non-finite guard column
    compiled = dict(interpret=False)
    cases = {
        "fused_dots": (
            lambda a: K.fused_dots(a["d3"][0], a["p2"], **compiled).sum(0),
            lambda a: R.dots_ref(a["d3"][0], a["p2"])),
        "guard_dots": (
            lambda a: K.guard_dots(a["bad"], a["p2"], **compiled).sum(0),
            lambda a: R.guard_dots_ref(a["bad"], a["p2"])),
        "fused_epilogue": (
            lambda a: K.fused_epilogue(a["d3"][0], a["p2"], a["c"][0],
                                       a["s"][0], **compiled),
            lambda a: R.epilogue_ref(a["d3"][0], a["p2"], a["c"][0],
                                     a["s"][0])),
        "batched_epilogue": (
            lambda a: K.batched_epilogue(a["d3"], a["p2"], a["w2"], a["c"],
                                         a["s"], eta, **compiled),
            lambda a: R.batched_epilogue_ref(a["d3"], a["p2"], a["w2"],
                                             a["c"], a["s"], eta)),
        "buffer_fold": (
            lambda a: K.buffer_fold(a["d3"], a["p2"], a["w2"], a["c"],
                                    a["s"], a["wt"], eta, **compiled),
            lambda a: R.buffer_fold_ref(a["d3"], a["p2"], a["w2"], a["c"],
                                        a["s"], a["wt"], eta)),
        "dequant_batched_epilogue": (
            lambda a: K.dequant_batched_epilogue(
                a["q3"], a["p2"], a["w2"], a["c"], a["s"], eta, a["qs"],
                a["qz"], **compiled),
            lambda a: R.dequant_batched_epilogue_ref(
                a["q3"], a["p2"], a["w2"], a["c"], a["s"], eta, a["qs"],
                a["qz"])),
        "dequant_buffer_fold": (
            lambda a: K.dequant_buffer_fold(
                a["q3"], a["p2"], a["w2"], a["c"], a["s"], a["wt"], eta,
                a["qs"], a["qz"], **compiled),
            lambda a: R.dequant_buffer_fold_ref(
                a["q3"], a["p2"], a["w2"], a["c"], a["s"], a["wt"], eta,
                a["qs"], a["qz"])),
    }
    for name, (kernel, oracle) in cases.items():
        got, want = jax.jit(kernel)(a), jax.jit(oracle)(a)
        err = max(float(np.max(np.abs(np.subtract(g, w))) / np.max(np.abs(w)))
                  for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        print(f"kernel check: {name} vs ref.py, error {err} "
              f"(tolerance {KERNEL_TOL})")
        if not err <= KERNEL_TOL:
            fail(f"kernel check: {name} differs from ref.py by {err}")


def spans(tree, n) -> bool:
    import jax
    return all(len(x.sharding.device_set) == n for x in jax.tree.leaves(tree))


def four_chips(jax, cache):
    device = jax.devices()[0]
    runs = {"reference (1 chip)": (),
            "sharded1d (clients over 4 chips)": ("--shard-clients",),
            "sharded2d (2x2 clients x model)": ("--shard-clients",
                                                "--model-shards", "2")}
    ref = None
    for tag, extra in runs.items():
        hist, trainer, abstract, jitted = run_trainer(TASK, use_kernel=True,
                                                      extra=extra)
        check_width(trainer)
        losses = report(tag, hist, device)
        print(f"{tag}: epilogue {epilogue_of(round_program(jitted, abstract).as_text())}"
              f", cohort padded to {trainer._pad_to}")
        if ref is None:
            ref = losses
            continue
        batches = abstract[2]
        split = all(not x.sharding.is_fully_replicated
                    for x in jax.tree.leaves(batches))
        if not (split and spans(batches, 4) and spans(trainer.params, 4)):
            fail(f"{tag}: cohort inputs or params do not span 4 devices")
        compare(f"{tag} vs reference", losses, ref, SHARDED_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded rounds against a "
                         "one-chip reference")
    opts = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; JAX's first device is {devices[0].platform}")
    if len(devices) < opts.chips:
        fail(f"--chips {opts.chips} needs {opts.chips} devices, "
             f"JAX sees {len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    cache = CacheEvents(jax)
    OUT.mkdir(exist_ok=True)
    t0 = time.perf_counter()
    (four_chips if opts.chips == 4 else one_chip)(jax, cache)
    print(f"smoke wall time {time.perf_counter() - t0} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
