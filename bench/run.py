#!/usr/bin/env python3
"""FedDPC on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name. The cell is an entry of ``workloads`` in
``BENCHMARK.json``; its configuration is ``bench/configs/<config>.json``,
which names its task ``bench/tasks/<task>.py``; its traffic is
``bench/traffic/<traffic>.json``; the limits of its correctness check
are ``bench/workloads/<cell>.json``; each per-layer metric is read by
``bench/layer_metrics/<metric>.py``. A new configuration, cell, metric
or task is new files, and no edit here.

A task module holds what is particular to a task's data and kernels:

* ``TRAFFIC_FIELDS``: ``{field: type}``, the traffic file's fields of
  the task's own data, besides the fields every cell has
  (``traffic.Traffic``); they are loaded, strictly, into
  ``Traffic.data``. A module without it declares none.
* ``build(config, traffic, seed)``: the run's federated job, an object
  with ``chips_used``, ``run_round(t)``, ``useful_steps(t)``,
  ``computed_steps()``, ``samples_per_step``, ``flops_per_sample``,
  ``epilogue_bytes()``, ``kernels()`` (kernel name -> a test on a
  trace's op event name), ``memory_note()``, ``program()``,
  ``close()``, ``reference()`` and ``compare(program, reference)``,
  and optionally with

  - ``work(rounds)``: ``{kernel or scope: {"flops": n, "bytes": n}}``,
    what the listed rounds require, counted from shapes and the
    benchmark's own batch rule; a reader finds it for the traced
    window's rounds as ``ctx["work"]``;
  - ``scopes()``: program scope -> a test on a trace's op event name
    (``tracing.scope_tests``); a reader finds each scope's device
    seconds over the traced window as ``ctx["scope_s"]``.

A run: set-up (data and weights from the seed, the trainer, the shape
bucket fixed, the first three rounds, which compile or load the round
program and are compared with the reference later), then a window of
``--seconds`` of rounds through ``FederatedTrainer.run_round``, then the
reference. ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` traces the window and prints its per-layer metrics. The
last line of standard output is one JSON object; the numbers the
correctness check compared, each with its limit, are the last lines of
standard error and the last key of that object.

It exits non-zero with no result when JAX finds no TPU or fewer chips
than the cell asks for, and when the program (``src/repro``) is absent.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                     # noqa: E402
from contextlib import nullcontext                  # noqa: E402
import gc                                           # noqa: E402
import importlib.util                               # noqa: E402
import json                                         # noqa: E402
import os                                           # noqa: E402
import statistics                                   # noqa: E402
import sys                                          # noqa: E402
from pathlib import Path                            # noqa: E402
from typing import Any, Dict, List                  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(Exception):
    """A run that must end without a result."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise Refused(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(name: str, root: Path = ROOT) -> Dict[str, Any]:
    """The cell's entry in BENCHMARK.json, with the files it names."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = dict(cells[name])
    bench = root / "bench"
    config = json.loads((bench / "configs" / f"{cell['config']}.json")
                        .read_text())
    cell["config_data"] = config
    cell["traffic_path"] = bench / "traffic" / f"{cell['traffic']}.json"
    cell["limits"] = json.loads((bench / "workloads" / f"{name}.json")
                                .read_text())["limits"]
    cell["task_path"] = bench / "tasks" / f"{config['task']}.py"
    cell["end_to_end"] = [m for m in spec["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in spec["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def load_task(cell: Dict[str, Any]):
    """The cell's task module, and its traffic loaded with the fields
    that the task declares."""
    import traffic as traffic_mod
    task_mod = load_module(cell["task_path"],
                           f"task_{cell['config_data']['task']}")
    return task_mod, traffic_mod.Traffic.load(
        cell["traffic_path"], getattr(task_mod, "TRAFFIC_FIELDS", {}))


def layer_readers(names: List[str], root: Path = ROOT):
    return {n: load_module(root / "bench" / "layer_metrics" / f"{n}.py",
                           f"layer_metric_{n}").read for n in names}


class CompileCounter:
    """Counts XLA compilations and persistent-cache reads, through JAX's
    monitoring events."""

    def __init__(self, jax):
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def setup_paths(root: Path):
    """Puts the program and the benchmark on the import path and turns on
    JAX's persistent compilation cache in a fixed directory inside the
    checkout, so that every run of a cell after the first loads its
    programs (the program's own cache helper is pointed there too)."""
    if not (root / "src" / "repro").is_dir():
        raise Refused(f"the program is not in this checkout "
                      f"({root / 'src' / 'repro'} is missing)")
    cache = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    for p in (str(root / "src"), str(root / "bench")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(jax, chips: int, allow_cpu: bool):
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise Refused(f"needs a TPU; JAX's first device is "
                      f"{devices[0].platform}")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees "
                      f"{len(devices)}")
    return devices[:chips]


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile, by linear interpolation between order
    statistics (numpy's default)."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: Path = ROOT, allow_cpu: bool = False) -> Dict[str, Any]:
    """One run; returns the result object. ``allow_cpu`` skips the look
    for a chip and is for the harness's own tests alone."""
    cell = find_cell(workload, root)
    setup_paths(root)
    import jax
    devices = check_devices(jax, cell["chips"], allow_cpu)
    counter = CompileCounter(jax)

    from peaks import peaks as peak_table
    config = cell["config_data"]
    task_mod, traffic = load_task(cell)
    log = lambda *a: print(*a, file=sys.stderr, flush=True)   # noqa: E731

    # ---- set-up: data, weights, trainer, the first three rounds ----
    task = task_mod.build(config, traffic, seed)
    if task.chips_used != cell["chips"]:
        raise Refused(f"the task uses {task.chips_used} chips, the cell "
                      f"asks for {cell['chips']}")
    for t in range(3):
        task.run_round(t)
    t = first = 3
    setup_s = time.perf_counter() - T_START
    compiles_before = counter.compiles

    # ---- the window ----
    readers = layer_readers([m["name"] for m in cell["per_layer"]], root) \
        if trace else {}
    records, useful = [], 0
    tracer = None
    if trace:
        import tracing
        tracer = tracing.Tracer(jax, root / ".bench_trace")
        tracer.start()
    w0 = time.perf_counter()
    while True:
        with (tracer.span("bench.round", t) if tracer else nullcontext()):
            rec = task.run_round(t)
        with (tracer.span("bench.between", t) if tracer else nullcontext()):
            records.append(rec)
            useful += task.useful_steps(t)
            t += 1
            done = time.perf_counter() - w0 >= seconds
        if done:
            break
    window_s = time.perf_counter() - w0
    if tracer:
        tracer.stop()
    compiles_in_window = counter.compiles - compiles_before
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    reduced = None
    if tracer:
        r0 = time.perf_counter()
        scopes = task.scopes() if hasattr(task, "scopes") else {}
        reduced = tracer.reduce(task.kernels(), scopes)
        log(f"trace reduced in {time.perf_counter() - r0} s; device "
            f"seconds by scope {reduced['scope_s']}, by kernel "
            f"{reduced['kernel_s']}")
    log(f"compiles in window: {compiles_in_window}; persistent cache "
        f"hits so far: {counter.cache_hits}")
    reserved = max(s.get("peak_bytes_reserved", 0) for s in stats)
    log(f"device memory: peak_bytes_in_use {peak_bytes}, "
        f"peak_bytes_reserved {reserved} (fullest chip); "
        f"{task.memory_note()}")

    # ---- the measurement ----
    round_s = [r.seconds for r in records]
    samples = useful * task.samples_per_step
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    metrics: Dict[str, Dict[str, Any]] = {}
    out: Dict[str, Any] = {}
    if not trace:
        values = {"samples_per_s": samples / window_s,
                  "round_s.p90": percentile(round_s, 90),
                  "setup_s": setup_s}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = {"records": records, "useful_steps": useful,
               "computed_steps": task.computed_steps() * len(records),
               "samples": samples, "window_s": window_s,
               "flops_per_sample": task.flops_per_sample,
               "epilogue_bytes": task.epilogue_bytes(),
               "peaks": peak_table(devices[0].device_kind),
               "chips": len(devices), "trace": reduced,
               "work": (task.work(list(range(first, t)))
                        if hasattr(task, "work") else {}),
               "scope_s": reduced["scope_s"]}
        for m in cell["per_layer"]:
            value = readers[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    log(f"window: {len(records)} rounds in {window_s} s; round_s median "
        f"{statistics.median(round_s)}; useful samples {samples}; set-up "
        f"{setup_s} s")

    # ---- correctness: the reference, once the program's state is freed
    program = task.program()
    task.close()
    gc.collect()
    r0 = time.perf_counter()
    reference = task.reference()
    log(f"reference: 3 rounds in {time.perf_counter() - r0} s")
    numbers = task.compare(program, reference)
    import fl
    correct, lines = fl.judge(numbers, cell["limits"])
    result = {"correct": correct, "attempted": len(records),
              "failed": 0 if correct else len(records),
              "metrics": metrics, "device": device, **out,
              "compared": {k: {"value": numbers.get(k), "limit": v}
                           for k, v in cell["limits"].items()}}
    for line in lines:
        log(line)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
