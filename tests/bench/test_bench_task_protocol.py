"""A cell of a task that is not an image task is new files alone: a
token task (tests/bench/fixtures/tokens) with its own configuration,
traffic fields, limits and a reader of its work count and scope
seconds, copied into a checkout of the benchmark, runs through
bench/run.py's ``run`` on the CPU, and no file that was in the
checkout changes."""
import hashlib
import json
import shutil
from contextlib import nullcontext
from pathlib import Path

import pytest

import benchtiny
from benchtiny import REPO

import run as harness  # noqa: E402  (bench/, put on the path by benchtiny)

FIXTURE = REPO / "tests" / "bench" / "fixtures" / "tokens"
TOKEN_CELL = "tokens.cell"
METRIC = "local.flops_per_s"
LOCAL_NS, INNER_NS, SERVER_NS, ROUND_NS = 600, 200, 50, 1000


def _digests(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def _add_token_cell(root: Path):
    """The new files, and the new cell's and metric's entries in
    BENCHMARK.json."""
    for src in FIXTURE.rglob("*"):
        if src.is_file() and "__pycache__" not in src.parts:
            dst = root / "bench" / src.relative_to(FIXTURE)
            assert not dst.exists(), dst
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(src, dst)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": TOKEN_CELL, "config": "tiny-tokens",
                              "traffic": "tiny-tokens", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": METRIC, "unit": "FLOP/s",
                              "better": "higher", "source": "device_trace",
                              "layer": "local training",
                              "moves": "samples_per_s",
                              "workloads": [TOKEN_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


class SyntheticTracer:
    """Stands in for ``tracing.Tracer`` where no chip can be traced. Its
    trace holds, in each round, two nested events of instructions that
    the task's ``fl.local`` scope names and one that its ``fl.server``
    scope names, as ``tracing.scope_ops`` found them in the compiled
    round; the real ``tracing.reduce`` reads it."""

    found = {}

    def __init__(self, jax, directory):
        self.rounds = 0

    def start(self):
        pass

    def stop(self):
        pass

    def span(self, name, t):
        self.rounds += name == "bench.round"
        return nullcontext()

    def reduce(self, kernels, scopes):
        import tracing
        local = sorted(self.found["fl.local"] - self.found["fl.server"])
        server = sorted(self.found["fl.server"] - self.found["fl.local"])
        ops, host = [], []
        for r in range(self.rounds):
            t = r * ROUND_NS
            ops += [(f"%{local[0]} = f32[] while()", t, t + LOCAL_NS),
                    (f"%{local[1]} = f32[] fusion()", t + 100,
                     t + 100 + INNER_NS),
                    (f"%{server[0]} = f32[] fusion()", t + LOCAL_NS,
                     t + LOCAL_NS + SERVER_NS)]
            host.append(("bench.round", t, t + ROUND_NS))
        return tracing.reduce(benchtiny.xspace({0: ops}, host).planes,
                              kernels, scopes)


@pytest.fixture
def token_root(tmp_path, monkeypatch):
    root = benchtiny.make_root(tmp_path)
    before = _digests(root)
    _add_token_cell(root)
    import peaks
    import tracing
    real = tracing.scope_ops
    monkeypatch.setattr(SyntheticTracer, "found", {})

    def recorded(text, scope):
        SyntheticTracer.found[scope] = real(text, scope)
        return SyntheticTracer.found[scope]

    monkeypatch.setattr(tracing, "scope_ops", recorded)
    monkeypatch.setattr(tracing, "Tracer", SyntheticTracer)
    monkeypatch.setattr(peaks, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    yield root
    after = _digests(root)
    assert {k: after[k] for k in before if k.name != "BENCHMARK.json"} \
        == {k: v for k, v in before.items() if k.name != "BENCHMARK.json"}


@pytest.mark.parametrize("trace", [False, True])
def test_token_cell_of_new_files_runs(token_root, trace):
    result = harness.run(TOKEN_CELL, 2 ** 31 + 29, 0.5, trace,
                         root=token_root, allow_cpu=True)
    assert result["correct"] is True, result["compared"]
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(result)
    assert list(result)[-1] == "compared"
    assert result["attempted"] > 0 and result["failed"] == 0
    for v in result["compared"].values():
        assert set(v) == {"value", "limit"}
    json.dumps(result)
    if not trace:
        assert set(result["metrics"]) == {"samples_per_s", "round_s.p90",
                                          "setup_s"}
        return
    # the only per-layer metric that lists the cell is its own reader;
    # it reads the task's count over the window's rounds (the rounds
    # after the three of set-up) and the scope's union per round
    assert set(result["metrics"]) == {METRIC}
    rounds = result["attempted"]
    task = benchtiny.build(token_root, 2 ** 31 + 29, cell=TOKEN_CELL)
    try:
        for t in range(3 + rounds):
            task.run_round(t)
        flops = task.work(list(range(3, 3 + rounds)))["fl.local"]["flops"]
        steps = sum(task.useful_steps(t) for t in range(3, 3 + rounds))
    finally:
        task.close()
    assert flops == steps * 8 * 12 * 6 * 16 * 64
    assert result["metrics"][METRIC] == {
        "value": pytest.approx(flops / (rounds * LOCAL_NS * 1e-9)),
        "unit": "FLOP/s"}
    assert result["device"]["busy_s"] == pytest.approx(
        rounds * (LOCAL_NS + SERVER_NS) * 1e-9)


def test_vision_task_scopes_and_work(tmp_path):
    """The vision task names the round program's stage scopes: in the
    tiny cell's compiled round (no codec, no guard) the local scan's
    loops lie in ``fl.local``, the reduction pass and the epilogue (on
    the CPU, the interpreted kernel's loops) in ``fl.server``, and no
    loop in both; its work count is the epilogue's per round times the
    rounds."""
    import flops
    import tracing
    root = benchtiny.make_root(tmp_path)
    task = benchtiny.build(root, 2 ** 31 + 31)
    try:
        task.run_round(0)
        text = task._round_program().as_text()
        scopes = task.scopes()
        work = task.work([3, 4, 5])
    finally:
        task.close()
    assert set(scopes) == {"fl.local", "fl.codec", "fl.guard", "fl.server",
                           "fl.server.reduce", "fl.server.epilogue"}
    names = {s: tracing.scope_ops(text, s) for s in scopes}
    assert not names["fl.codec"] and not names["fl.guard"]
    loops = {line.split(" = ", 1)[0].strip().removeprefix("ROOT ")
             .lstrip("%") for line in text.splitlines()
             if " while(" in line}
    assert loops & names["fl.local"]
    assert loops <= names["fl.local"] ^ names["fl.server"]
    for s in ("fl.server.reduce", "fl.server.epilogue"):
        assert names[s] and names[s] <= names["fl.server"]
    assert not names["fl.local"] & names["fl.server"]
    for s, found in names.items():
        for n in found:
            assert scopes[s](f"%{n} = f32[] fusion()"), (s, n)
        assert not scopes[s]("%no.such.op = f32[] fusion()")
    k = task.traffic.clients_per_round
    assert work == {"batched_epilogue": {
        "flops": 3 * (4 * k + 3) * task.num_params,
        "bytes": 3 * flops.epilogue_bytes(task.num_params, k)}}
