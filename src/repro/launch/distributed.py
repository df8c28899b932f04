"""Multi-process (multi-host) runtime for the cohort trainer
(DESIGN.md §15).

Three pieces, all jax-lazy (importing this module never initializes a
backend, same contract as launch/mesh.py):

  ``maybe_initialize()``   reads the ``REPRO_DIST_*`` environment and, when
      present, wires ``jax.distributed`` up BEFORE any device query: on
      CPU the gloo cross-process collective backend must be selected
      before ``jax.distributed.initialize`` or every cross-process jit
      fails with "Multiprocess computations aren't implemented on the
      CPU backend". After it returns, ``jax.devices()`` spans every
      process and ``jax.local_devices()`` is this host's slice. A no-op
      (returning None) outside a spawned/distributed environment, so
      single-process entry points can call it unconditionally.

  ``spawn_local(argv, num_processes)``   the single-machine N-process
      spawner for offline CI: re-executes ``argv`` N times with the
      coordinator/process-id environment set (127.0.0.1 coordinator, a
      freshly bound port — no external network), collects the exit
      statuses, and raises on any failure. Each child calls
      ``maybe_initialize()`` and becomes one "host" of the cohort.

  process gating + a KV-store ``barrier``   ``is_coordinator()`` gates
      side effects (checkpoint writes, receipts) to process 0;
      ``barrier(name)`` synchronizes processes through the distributed
      KV store (each process publishes a key, then blocks on every
      peer's), which is how the checkpoint writer keeps non-writers from
      racing past a save/restore point.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from dataclasses import dataclass
from typing import Optional, Sequence

# environment contract between spawn_local and maybe_initialize
ENV_COORD = "REPRO_DIST_COORD"
ENV_NPROCS = "REPRO_DIST_NPROCS"
ENV_PID = "REPRO_DIST_PID"
ENV_LOCAL_DEVICES = "REPRO_DIST_LOCAL_DEVICES"

_initialized = False


@dataclass(frozen=True)
class DistContext:
    """What maybe_initialize() resolved: this process's place in the job."""
    coordinator: str
    num_processes: int
    process_id: int


def dist_env(environ=None) -> Optional[DistContext]:
    """Parse the REPRO_DIST_* contract; None outside a distributed job."""
    env = os.environ if environ is None else environ
    coord = env.get(ENV_COORD)
    if not coord:
        return None
    return DistContext(coordinator=coord,
                       num_processes=int(env.get(ENV_NPROCS, "1")),
                       process_id=int(env.get(ENV_PID, "0")))


def maybe_initialize() -> Optional[DistContext]:
    """Initialize jax.distributed from the environment, once.

    Must run before the first device query (anything that instantiates a
    backend). On the CPU backend the gloo collectives implementation is
    selected first — without it cross-process computations fail to
    compile — gated on the JAX_PLATFORMS *environment* rather than
    ``jax.default_backend()``, which would itself initialize a backend
    prematurely.
    """
    global _initialized
    ctx = dist_env()
    if ctx is None:
        return None
    import jax
    if not _initialized:
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=ctx.coordinator,
                                   num_processes=ctx.num_processes,
                                   process_id=ctx.process_id)
        _initialized = True
    return ctx


def is_coordinator() -> bool:
    """True on process 0 (and in any single-process run) — the one
    process that writes checkpoints / receipts (DESIGN.md §15)."""
    import jax
    return jax.process_index() == 0


def process_count() -> int:
    import jax
    return jax.process_count()


def _kv_client():
    # the coordination-service KV store jax.distributed.initialize stands
    # up; jax exposes no public handle, so reach through _src — gated
    # behind initialize() having run (global_state.client is None
    # otherwise)
    from jax._src import distributed as jd
    client = jd.global_state.client
    if client is None:
        raise RuntimeError(
            "barrier() needs jax.distributed initialized "
            "(maybe_initialize() found no REPRO_DIST_* environment)")
    return client


def barrier(name: str, *, timeout_s: float = 120.0) -> None:
    """Synchronize every process at ``name`` through the distributed KV
    store: publish <name>/<pid>, then block until every peer's key is
    visible. ``name`` must be unique per synchronization point (keys are
    write-once per job). No-op in single-process runs."""
    import jax
    n = jax.process_count()
    if n <= 1:
        return
    client = _kv_client()
    pid = jax.process_index()
    client.key_value_set(f"repro/barrier/{name}/{pid}", "1")
    deadline_ms = int(timeout_s * 1000)
    for peer in range(n):
        client.blocking_key_value_get(f"repro/barrier/{name}/{peer}",
                                      deadline_ms)


def kv_allmax(name: str, value: int, *, timeout_s: float = 120.0) -> int:
    """All-reduce-max of a host-side int through the KV store: every
    process publishes its value under <name>/<pid> and reads every
    peer's, returning the max. Used where hosts must agree on a
    host-side capacity (e.g. the cohort stacker's max-batches high-water
    mark) without a device collective. Single-process: identity."""
    import jax
    n = jax.process_count()
    if n <= 1:
        return int(value)
    client = _kv_client()
    pid = jax.process_index()
    client.key_value_set(f"repro/allmax/{name}/{pid}", str(int(value)))
    deadline_ms = int(timeout_s * 1000)
    best = int(value)
    for peer in range(n):
        got = client.blocking_key_value_get(
            f"repro/allmax/{name}/{peer}", deadline_ms)
        best = max(best, int(got))
    return best


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn_local(argv: Sequence[str], num_processes: int, *,
                devices_per_process: Optional[int] = None,
                env: Optional[dict] = None,
                timeout_s: float = 600.0,
                capture: bool = True):
    """Run ``argv`` as ``num_processes`` local processes forming one
    distributed jax job on the CPU: the stand-in for a real multi-host
    launch (coordinator on 127.0.0.1, no external network).

    Each child gets the REPRO_DIST_* contract plus ``JAX_PLATFORMS=cpu``
    and, when ``devices_per_process`` is set, an XLA_FLAGS host-device
    override so every "host" exposes that many CPU devices. The children
    must call :func:`maybe_initialize` before their first device query.
    An accelerator belongs to one process at a time and this launcher
    binds none to its children, so a caller environment (``os.environ``
    updated by ``env``) that asks for any platform other than the CPU
    is refused with ValueError instead of being overridden or handing
    every child the same chips.

    Returns the list of ``subprocess.CompletedProcess``-like results
    (returncode/stdout/stderr per child); raises RuntimeError if any
    child fails, with the failing children's tails in the message.
    """
    base_env = {**os.environ, **(env or {})}
    asked = base_env.get("JAX_PLATFORMS", "")
    if asked not in ("", "cpu"):
        raise ValueError(
            f"spawn_local runs its {num_processes} children on the CPU, "
            f"but JAX_PLATFORMS={asked!r} asks for another platform: an "
            "accelerator takes one process per chip, and this launcher "
            "binds no chip to any child")
    coord = f"127.0.0.1:{free_port()}"
    procs = []
    for pid in range(num_processes):
        child_env = dict(base_env)
        child_env[ENV_COORD] = coord
        child_env[ENV_NPROCS] = str(num_processes)
        child_env[ENV_PID] = str(pid)
        child_env["JAX_PLATFORMS"] = "cpu"
        if devices_per_process is not None:
            child_env[ENV_LOCAL_DEVICES] = str(devices_per_process)
            flags = child_env.get("XLA_FLAGS", "")
            # replace any inherited device-count force rather than append
            # (XLA honors the LAST occurrence, but a stale flag in a test
            # runner's env is a confusing thing to leave in place)
            flags = " ".join(
                f for f in flags.split()
                if not f.startswith("--xla_force_host_platform_device_count"))
            child_env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{devices_per_process}").strip()
        procs.append(subprocess.Popen(
            list(argv), env=child_env,
            stdout=subprocess.PIPE if capture else None,
            stderr=subprocess.PIPE if capture else None,
            text=True))
    results = []
    deadline = time.monotonic() + timeout_s
    for p in procs:
        try:
            out, err = p.communicate(
                timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            out, err = p.communicate()
            results.append((p.returncode if p.returncode is not None
                            else -9, out, err))
            raise RuntimeError(
                f"spawn_local: child timed out after {timeout_s}s\n"
                f"--- stdout tail ---\n{(out or '')[-2000:]}\n"
                f"--- stderr tail ---\n{(err or '')[-2000:]}")
        results.append((p.returncode, out, err))
    bad = [(i, rc, out, err) for i, (rc, out, err) in enumerate(results)
           if rc != 0]
    if bad:
        msgs = []
        for i, rc, out, err in bad:
            msgs.append(f"child {i} exited {rc}\n"
                        f"--- stdout tail ---\n{(out or '')[-2000:]}\n"
                        f"--- stderr tail ---\n{(err or '')[-2000:]}")
        raise RuntimeError("spawn_local: " + "\n".join(msgs))
    return results
