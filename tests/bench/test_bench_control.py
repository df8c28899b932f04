"""The correctness check of the benchmark can fail: its control (the
reference one precision lower, in the program's place) and each fault
that a training cell can have come out as not correct, at a size that a
CPU test run holds. The limits are the repository's own."""
import json

import jax
import jax.numpy as jnp
import pytest

import benchtiny
from benchtiny import CELL, REPO

import calibrate  # noqa: E402  (bench/, put on the path by benchtiny)
import fl  # noqa: E402
import run as harness  # noqa: E402

LIMITS_OF = ["resnet18.paper"]


def _limits(cell):
    return json.loads((REPO / "bench" / "workloads" / f"{cell}.json")
                      .read_text())["limits"]


@pytest.fixture(scope="module")
def tiny_readings(tmp_path_factory):
    root = benchtiny.make_root(tmp_path_factory.mktemp("control"))
    task = benchtiny.build(root, 2 ** 31 + 3)
    for t in range(3):
        task.run_round(t)
    return dict(calibrate.readings(task))


@pytest.mark.parametrize("limits_of", LIMITS_OF)
def test_program_passes_and_control_fails(tiny_readings, limits_of):
    limits = _limits(limits_of)
    assert fl.judge(tiny_readings["program"], limits)[0]
    ok, lines = fl.judge(tiny_readings["control"], limits)
    assert not ok, lines
    assert not fl.judge(tiny_readings["half_batch"], limits)[0]


def _copying(fn, keep):
    """A round that runs ``fn`` but hands back what ``keep`` makes of
    its inputs and outputs; keeps ``lower`` for the harness."""
    def call(state, params, *rest):
        saved = jax.tree.map(lambda x: jnp.array(x, copy=True),
                             (state, params))
        return keep(saved, fn(state, params, *rest))
    call.lower = fn.lower
    return call


def _planted_round(monkeypatch, keep):
    from repro.core import round as round_mod
    real = round_mod.make_cohort_round
    monkeypatch.setattr(round_mod, "make_cohort_round",
                        lambda *a, **k: _copying(real(*a, **k), keep))


FAULTS = {
    # a step that returns its state unchanged
    "state_unchanged": lambda mp: _planted_round(
        mp, lambda saved, out: (saved[1], saved[0]) + tuple(out[2:])),
    # an answer altered where it is produced: the round's losses
    "loss_altered": lambda mp: _planted_round(
        mp, lambda saved, out: (out[0], out[1], out[2] * 1.05, out[3])),
}


def _half_batch(monkeypatch):
    """Half of each local batch left out, the mean over the rest."""
    from repro.models import vision
    real = vision.vision_loss_fn

    def half(cfg, p, batch):
        n = batch["labels"].shape[0] // 2
        return real(cfg, p, {k: v[:n] for k, v in batch.items()})

    monkeypatch.setattr(vision, "vision_loss_fn", half)


FAULTS["half_batch"] = _half_batch


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_timed_path_is_not_correct(tmp_path, monkeypatch,
                                                fault):
    root = benchtiny.make_root(tmp_path)
    FAULTS[fault](monkeypatch)
    result = harness.run(CELL, 2 ** 31 + 5, 0.3, False, root=root,
                         allow_cpu=True)
    assert result["correct"] is False, result["compared"]
