"""The traffic loader (bench/traffic.py): the common fields are
``Traffic``'s, a task's own fields are the ones its module declares, and
the file is read strictly. The paper's traffic files load as they are,
to the population, partition and cohorts they gave before the task's
fields moved out of ``Traffic``."""
import hashlib
import json

import numpy as np
import pytest

from benchtiny import REPO

import run as harness  # noqa: E402  (bench/, put on the path by benchtiny)
import traffic as traffic_mod  # noqa: E402

VISION = harness.load_module(REPO / "bench" / "tasks" / "vision.py",
                             "task_vision_fields")
PAPER = REPO / "bench" / "traffic" / "paper-cifar100.json"


def _digest(arrays):
    d = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        d.update(str((a.dtype.str, a.shape)).encode())
        d.update(a.tobytes())
    return d.hexdigest()


def test_paper_cifar100_loads_as_before():
    """The values, and the digests of the population's labels, of its
    Dirichlet partition and of the first three cohorts, as the loader
    gave them when ``samples_per_class`` and ``image_noise`` were fields
    of ``Traffic``."""
    t = traffic_mod.Traffic.load(PAPER, VISION.TRAFFIC_FIELDS)
    assert {k: getattr(t, k) for k in (
        "name", "num_clients", "clients_per_round", "batch_size",
        "local_epochs", "dirichlet_alpha", "population_seed", "eta_l",
        "eta_g", "lam", "shard_clients")} == {
        "name": "paper-cifar100", "num_clients": 100,
        "clients_per_round": 10, "batch_size": 256, "local_epochs": 1,
        "dirichlet_alpha": 0.2, "population_seed": 0, "eta_l": 0.01,
        "eta_g": 0.01, "lam": 1.0, "shard_clients": False}
    assert dict(t.data) == {"samples_per_class": 500, "image_noise": 0.35}
    labels, parts = traffic_mod.population(t, 100,
                                           t.data["samples_per_class"])
    rng = traffic_mod.arrivals_rng(t)
    sched = traffic_mod.CohortSchedule(t.num_clients, t.clients_per_round)
    cohorts = [sched.next(rng) for _ in range(3)]
    assert _digest([labels]) == ("fb1bbef1dbcbeb6621d3a45b6acf1f843c7561de"
                                 "a2a71170d7f053ba254be43c")
    assert _digest(parts) == ("ea6f174cfe46d0beed55a3449f80e9b6ad4b7392cb"
                              "23cf3da70f71e63b560ebe")
    assert _digest(cohorts) == ("404c120959950432df5540bb09d88463ee5ceb86"
                                "1e7420c68259048fbe0ba841")
    assert traffic_mod.max_steps(parts, t) == 3


def test_paper_traffic_files_load_with_the_vision_fields():
    for path in sorted((REPO / "bench" / "traffic").glob("*.json")):
        t = traffic_mod.Traffic.load(path, VISION.TRAFFIC_FIELDS)
        assert set(t.data) == set(VISION.TRAFFIC_FIELDS), path
        assert isinstance(t.data["image_noise"], float)


@pytest.mark.parametrize("change, refusal", [
    ({"seq_len": 128}, r"unknown \['seq_len'\]"),
    ({"image_noise": None}, r"missing \['image_noise'\]"),
    ({"eta_l": None}, r"missing \['eta_l'\]"),
    ({"samples_per_class": 500.0}, "samples_per_class is 500.0, not of"),
    ({"samples_per_class": True}, "samples_per_class is True, not of"),
    ({"shard_clients": 0}, "shard_clients is 0, not of type bool"),
    ({"image_noise": "0.35"}, "image_noise is '0.35', not of type float"),
])
def test_loader_refuses(tmp_path, change, refusal):
    raw = json.loads(PAPER.read_text())
    raw.update(change)
    raw = {k: v for k, v in raw.items() if v is not None}
    path = tmp_path / "t.json"
    path.write_text(json.dumps(raw))
    with pytest.raises(ValueError, match=refusal):
        traffic_mod.Traffic.load(path, VISION.TRAFFIC_FIELDS)


def test_task_fields_are_the_tasks_own(tmp_path):
    """Another task's fields load into ``data`` and nowhere else; a
    whole number stands for a float; a task may not redeclare a common
    field."""
    raw = {k: v for k, v in json.loads(PAPER.read_text()).items()
           if k not in VISION.TRAFFIC_FIELDS}
    raw.update(seq_len=128, vocab=32000)
    path = tmp_path / "tokens.json"
    path.write_text(json.dumps(raw))
    t = traffic_mod.Traffic.load(path, {"seq_len": int, "vocab": float})
    assert dict(t.data) == {"seq_len": 128, "vocab": 32000.0}
    assert isinstance(t.data["vocab"], float)
    assert not hasattr(t, "seq_len")
    with pytest.raises(TypeError):
        t.data["seq_len"] = 1
    with pytest.raises(ValueError, match="common traffic fields"):
        traffic_mod.Traffic.load(path, {"seq_len": int, "vocab": int,
                                        "batch_size": int})
