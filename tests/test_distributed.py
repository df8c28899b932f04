"""Multi-process runtime contracts (repro.launch.distributed,
DESIGN.md §15): the REPRO_DIST_* environment contract, the
single-machine N-process spawner used by offline CI, the KV-store
barrier / all-max agreement primitives, and failure surfacing. The
actual hierarchical-round equivalence checks live in
tests/test_regime_matrix.py (test_multihost_two_process).
"""
import os
import sys

import pytest

from repro.launch.distributed import (ENV_COORD, ENV_NPROCS, ENV_PID,
                                      DistContext, dist_env, free_port,
                                      spawn_local)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(ROOT, "tests", "_dist_smoke_worker.py")
ENV = {"PYTHONPATH": os.path.join(ROOT, "src")}


def test_dist_env_parses_the_contract():
    env = {ENV_COORD: "127.0.0.1:4321", ENV_NPROCS: "4", ENV_PID: "2"}
    assert dist_env(env) == DistContext(coordinator="127.0.0.1:4321",
                                        num_processes=4, process_id=2)
    # defaults when only the coordinator is set
    assert dist_env({ENV_COORD: "h:1"}) == DistContext(
        coordinator="h:1", num_processes=1, process_id=0)


def test_dist_env_is_none_outside_a_job():
    assert dist_env({}) is None
    assert dist_env({ENV_NPROCS: "2", ENV_PID: "0"}) is None


def test_free_port_binds():
    p = free_port()
    assert 0 < p < 65536


def test_spawn_local_two_process_smoke():
    """2 local processes form one jax.distributed job: topology, the KV
    barrier, and the all-max agreement all work with no network beyond
    127.0.0.1."""
    results = spawn_local([sys.executable, WORKER], 2,
                          devices_per_process=1, env=ENV, timeout_s=300)
    assert len(results) == 2
    for rc, out, _err in results:
        assert rc == 0
        assert "DIST_SMOKE_OK" in out


def test_spawn_local_surfaces_a_failing_child():
    """A child that dies mid-job raises with that child's output tail —
    the offline-CI operator sees WHICH process failed and why."""
    with pytest.raises(RuntimeError, match="child 1 exited 3"):
        spawn_local([sys.executable, WORKER, "--fail"], 2,
                    devices_per_process=1, env=ENV, timeout_s=300)


@pytest.mark.parametrize("asked", [None, "", "cpu"])
def test_spawn_local_puts_every_child_on_the_cpu(asked, monkeypatch):
    """Whatever CPU-compatible JAX_PLATFORMS the caller has (unset,
    empty, cpu), every child runs with JAX_PLATFORMS=cpu."""
    if asked is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", asked)
    probe = "import os; print(os.environ['JAX_PLATFORMS'])"
    results = spawn_local([sys.executable, "-c", probe], 2, timeout_s=60)
    assert [out.strip() for _rc, out, _err in results] == ["cpu", "cpu"]


@pytest.mark.parametrize("where", ["os.environ", "env"])
def test_spawn_local_refuses_an_accelerator_platform(where, monkeypatch):
    """A caller asking for the TPU is refused before any child starts: an
    accelerator takes one process per chip and spawn_local binds none."""
    env = None
    if where == "env":
        env = {"JAX_PLATFORMS": "tpu"}
    else:
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(ValueError, match="one process per chip"):
        spawn_local([sys.executable, "-c", "raise SystemExit(7)"], 2,
                    env=env, timeout_s=60)
