#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; not run by the
benchmark's own runs.

    python3 bench/calibrate.py --workload <cell> --seeds <n> [--first <seed>]
        [--planted-seeds <m>]

For each seed, in one process: the set-up of a run (data, weights, the
trainer, the first three rounds through ``run_round``), then the
reference, and the comparison of the program with it (the lower
reading); then, for the first ``--planted-seeds`` seeds (all by
default), each in the program's place against the same reference:

* ``control``: the reference computed one precision lower (bfloat16),
* ``half_batch``: the reference with each local step seeing half its
  batch, the mean taken over that half;
* ``highest``: the reference at float32 ``highest``, a witness of how
  far the stated precision lies from the most precise one; and
  ``program_vs_highest``, the program against that witness.

A state left unchanged reads 1 by the comparison's measure and needs no
run. Prints one JSON line per seed and reading, and writes them all to
``chiprun_out/calibrate-<cell>.jsonl`` when that directory exists.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def readings(task, planted: bool = True):
    """Yields (name, numbers) for one seed's task, whose first three
    rounds have run; the planted readings and the witness too where
    ``planted``."""
    program = task.program()
    task.close()
    gc.collect()
    tic = time.perf_counter()
    reference = task.reference()
    log(f"reference: {time.perf_counter() - tic} s")
    yield "program", task.compare(program, reference)
    if not planted:
        return
    plants = {"control": dict(precision="control"),
              "half_batch": dict(half_batch=True),
              "highest": dict(precision="highest")}
    for name, kw in plants.items():
        tic = time.perf_counter()
        planted = task.reference(**kw)
        log(f"{name}: {time.perf_counter() - tic} s")
        planted["cohorts"] = program["cohorts"]
        yield name, task.compare(planted, reference)
        if name == "highest":
            yield "program_vs_highest", task.compare(program, planted)


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def main(argv=None, root: Path = ROOT, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=1_000_003)
    ap.add_argument("--planted-seeds", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import run as harness
    cell = harness.find_cell(args.workload, root)
    harness.setup_paths(root)
    import jax
    harness.check_devices(jax, cell["chips"], allow_cpu)
    task_mod, traffic = harness.load_task(cell)
    out_dir = root / "chiprun_out"
    sink = (open(out_dir / f"calibrate-{args.workload}.jsonl", "a")
            if out_dir.is_dir() else None)
    try:
        for i in range(args.seeds):
            seed = args.first + 7919 * i
            tic = time.perf_counter()
            task = task_mod.build(cell["config_data"], traffic, seed)
            for t in range(3):
                task.run_round(t)
            planted = args.planted_seeds is None or i < args.planted_seeds
            for name, numbers in readings(task, planted):
                line = json.dumps({"cell": args.workload, "seed": seed,
                                   "reading": name, **numbers})
                print(line, flush=True)
                if sink:
                    print(line, file=sink, flush=True)
            log(f"seed {seed}: {time.perf_counter() - tic} s")
            del task
            gc.collect()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
