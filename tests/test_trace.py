"""Host spans and device scopes of the federated round (repro.trace,
DESIGN.md §16): the spans a profiled run records, on which thread and
inside which parent; that RoundRecord's times are those spans'
durations; that the spans change nothing with no profiler running; the
scopes in the compiled round; and the trace summary on a synthetic
trace with known intervals."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.core.api import AlgoConfig, ExecConfig, FederatedTrainer

NUM_CLIENTS = 6
K = 3
ROUNDS = 4
CALLER = ("fl.round", "fl.ingest.wait", "fl.round.dispatch",
          "fl.round.sync", "fl.round.record")
PRODUCER = ("fl.ingest.stage", "fl.ingest.sample", "fl.ingest.read",
            "fl.ingest.stack", "fl.ingest.place")


def loss_fn(p, batch):
    pred = batch["x"] @ p["w"] + p["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def make_params(seed=0):
    r = np.random.RandomState(seed)
    return {"w": jnp.asarray(r.randn(4, 3), jnp.float32),
            "b": jnp.asarray(r.randn(3), jnp.float32)}


def slow_batch_fn(c, t):
    """A client's ragged batches, read slowly: the round waits on the
    staging thread, so the wait spans are long."""
    time.sleep(0.02)
    r = np.random.RandomState(1000 * c + t)
    return [{"x": r.randn(8, 4).astype(np.float32),
             "y": r.randn(8, 3).astype(np.float32)}
            for _ in range((c % 3) + 1)]


def run_trainer(**exec_kw):
    kw = dict(clients_per_round=K, seed=7, eval_every=10 ** 9)
    kw.update(exec_kw)
    with FederatedTrainer(loss_fn, make_params(), NUM_CLIENTS,
                          slow_batch_fn, ExecConfig(rounds=ROUNDS, **kw),
                          algo=AlgoConfig(eta_l=0.05, eta_g=0.1)) as tr:
        tr.run()
    return tr


def profiled(directory, **exec_kw):
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        tr = run_trainer(**exec_kw)
    finally:
        jax.profiler.stop_trace()
    return tr, trace.load(str(directory))


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("trace")


@pytest.fixture(scope="module")
def traced(trace_dir):
    return profiled(trace_dir)


def test_every_span_once_per_round_on_its_thread(traced):
    tr, data = traced
    spans = trace.host_spans(data.planes)
    for name in CALLER + PRODUCER:
        assert sorted(s.round for s in spans if s.name == name) == \
            list(range(ROUNDS)), name
    caller = {s.line for s in spans if s.name in CALLER}
    producer = {s.line for s in spans if s.name in PRODUCER}
    assert len(caller) == 1 and len(producer) == 1
    assert caller != producer


def test_children_nest_in_their_round(traced):
    _, data = traced
    spans = trace.host_spans(data.planes)
    for parent, children in (("fl.round", CALLER[1:]),
                             ("fl.ingest.stage", PRODUCER[1:])):
        outer = {s.round: s for s in spans if s.name == parent}
        for s in spans:
            if s.name in children:
                o = outer[s.round]
                assert o.start_ns <= s.start_ns <= s.end_ns <= o.end_ns, \
                    (parent, s)


def test_record_times_are_the_span_durations(traced):
    """One reading, not two timers: each time on RoundRecord is its
    span's duration (the two clocks are read a few instructions
    apart)."""
    tr, data = traced
    dur = {(s.name, s.round): (s.end_ns - s.start_ns) / 1e9
           for s in trace.host_spans(data.planes)}
    fields = {"fl.round": "seconds", "fl.ingest.wait": "ingest_host_seconds",
              "fl.ingest.stage": "ingest_stage_seconds",
              "fl.ingest.place": "ingest_place_seconds",
              "fl.round.sync": "sync_seconds"}
    for rec in tr.history:
        for name, field in fields.items():
            assert getattr(rec, field) == pytest.approx(
                dur[name, rec.round], abs=0.01), (name, rec.round)
        assert rec.ingest_device_seconds == 0.0     # placed by the stager
    # the staging thread's reads (3 clients x 20 ms) make every stage
    # long, and some round waits on it
    assert all(r.ingest_stage_seconds >= 0.06 for r in tr.history)
    assert any(r.ingest_ready == 0 and r.ingest_host_seconds > 0.03
               for r in tr.history)


def test_counters_on_the_record(traced):
    tr, _ = traced
    sizes = [(c % 3) + 1 for c in range(NUM_CLIENTS)]
    bucket = 0
    for rec, cohort in zip(tr.history, tr.schedule):
        bucket = max([bucket] + [sizes[int(c)] for c in cohort])  # grow-once
        assert rec.steps_useful == sum(sizes[int(c)] for c in cohort)
        assert rec.steps_computed == K * bucket
        assert 0 <= rec.ingest_ready <= 2


def test_summary_of_a_cpu_trace(traced, trace_dir, capsys):
    _, data = traced
    s = trace.summarize(data.planes)
    assert s["chips"] == 0 and s["idle_s"] is None
    for name in CALLER + PRODUCER:
        assert s["spans"][name]["count"] == ROUNDS
    assert s["spans"]["fl.round"]["seconds"] <= s["window_s"]
    assert trace.main([str(trace_dir)]) == 0
    out = capsys.readouterr().out
    assert all(name in out for name in CALLER + PRODUCER)


def _losses_and_diagnostics(tr):
    return ([r.train_loss for r in tr.history],
            [r.diagnostics for r in tr.history],
            [np.asarray(x) for x in jax.tree.leaves(tr.params)])


def test_spans_inert_without_a_profiler(monkeypatch, traced):
    """With no profiler running the spans record nothing and change
    nothing: the same losses, diagnostics and weights as a run whose
    annotations are replaced by no-ops, and as the profiled run."""
    plain = _losses_and_diagnostics(run_trainer())

    class NoSpan:
        def __init__(self, *a, **k):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", NoSpan)
    bare = _losses_and_diagnostics(run_trainer())
    for got in (bare, _losses_and_diagnostics(traced[0])):
        assert got[0] == plain[0]
        assert got[1] == plain[1]
        for a, b in zip(got[2], plain[2]):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("exec_kw", [{"prefetch": False},
                                     {"vectorize": False}])
def test_unprefetched_paths_stage_on_the_caller(tmp_path, exec_kw):
    """The blocking and serial paths stage on the caller's thread, under
    its fl.round, from the same code."""
    tr, data = profiled(tmp_path, **exec_kw)
    spans = trace.host_spans(data.planes)
    rounds = {s.round: s for s in spans if s.name == "fl.round"}
    assert sorted(rounds) == list(range(ROUNDS))
    for name in ("fl.ingest.stage", "fl.ingest.sample", "fl.ingest.read",
                 "fl.ingest.stack"):
        got = [s for s in spans if s.name == name]
        assert sorted(s.round for s in got) == list(range(ROUNDS)), name
        for s in got:
            o = rounds[s.round]
            assert s.line == o.line
            assert o.start_ns <= s.start_ns <= s.end_ns <= o.end_ns
    for rec in tr.history:
        assert rec.ingest_host_seconds == rec.ingest_stage_seconds > 0.06
        assert rec.ingest_ready == 0


def test_scopes_in_the_compiled_round():
    """The round program's stages are named scopes of its ops' op_name;
    the backward pass of local training carries ``transpose(``."""
    with FederatedTrainer(loss_fn, make_params(), NUM_CLIENTS,
                          slow_batch_fn,
                          ExecConfig(rounds=1, clients_per_round=K, seed=7,
                                     eval_every=10 ** 9),
                          algo=AlgoConfig(eta_l=0.05, eta_g=0.1)) as tr:
        inner, shapes = tr._cohort_round, []

        def spy(*args):
            shapes.append(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), args))
            return inner(*args)

        tr._cohort_round = spy
        tr.run()
        text = inner.lower(*shapes[0]).compile().as_text()
    names = set()
    for line in text.splitlines():
        if 'op_name="' in line:
            names.add(line.split('op_name="', 1)[1].split('"', 1)[0])
    scopes = {"/".join(p for p in n.split("/") if p.startswith("fl."))
              for n in names}
    assert {"fl.local", "fl.server/fl.server.reduce",
            "fl.server/fl.server.epilogue"} <= scopes
    assert any("fl.local" in n and "transpose(" in n for n in names)


# ---------------- the summary on a synthetic trace ----------------

NS = 1000   # picoseconds per nanosecond


def _xspace(device_ops, host_lines):
    """A text-proto XSpace: ``device_ops`` maps a chip to (start_ns,
    end_ns) op events; ``host_lines`` is a list of host threads, each a
    list of (name, start_ns, end_ns) spans."""
    def plane(pid, name, lines):
        names = sorted({n for line in lines for n, _, _ in line})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = " ".join(
            f'lines {{ id: {j + 1} name: "t{j}" timestamp_ns: 0 ' + " ".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {s * NS} "
                f"duration_ps: {(e - s) * NS} }}" for n, s, e in line) + " }"
            for j, line in enumerate(lines))
        meta = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                        f"name: {json.dumps(n)} }} }}" for n, i in ids.items())
        return f'planes {{ id: {pid} name: "{name}" {body} {meta} }}'

    planes = [plane(chip + 1, f"/device:TPU:{chip}",
                    [[("%fusion.1 = f32[] fusion()", s, e) for s, e in ops]])
              for chip, ops in device_ops.items()]
    planes = [p.replace('name: "t0"', 'name: "XLA Ops"') for p in planes]
    planes.append(plane(99, "/host:CPU", host_lines))
    return jax.profiler.ProfileData.from_text_proto("\n".join(planes))


def test_summary_of_known_intervals():
    """One round [0, 100) ns: the caller waits [0, 40), dispatches,
    syncs [45, 95) and records; the stager works [0, 38) on its own
    thread. The chip is busy [42, 94): idle [0, 42) and [94, 100)."""
    caller = [("fl.round", 0, 100), ("fl.ingest.wait", 0, 40),
              ("fl.round.dispatch", 40, 45), ("fl.round.sync", 45, 95),
              ("fl.round.record", 95, 100), ("bench.round", 0, 100)]
    stager = [("fl.ingest.stage", 0, 38), ("fl.ingest.read", 2, 30)]
    data = _xspace({0: [(42, 94), (50, 60)]}, [caller, stager])
    s = trace.summarize(data.planes)
    assert s["chips"] == 1
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["idle_s"] == pytest.approx(48e-9)
    assert "bench.round" not in s["spans"]
    spans = s["spans"]
    assert spans["fl.round"]["count"] == 1
    assert spans["fl.round"]["idle_s"] == pytest.approx(48e-9)
    assert spans["fl.ingest.wait"]["idle_s"] == pytest.approx(40e-9)
    assert spans["fl.ingest.stage"]["idle_s"] == pytest.approx(38e-9)
    assert spans["fl.round.sync"]["idle_s"] == pytest.approx(1e-9)
    assert spans["fl.ingest.read"]["seconds"] == pytest.approx(28e-9)
    # the idle time, charged to the innermost span of the caller's thread
    assert s["idle_self_s"] == pytest.approx(
        {"fl.round": 0.0, "fl.ingest.wait": 40e-9,
         "fl.round.dispatch": 2e-9, "fl.round.sync": 1e-9,
         "fl.round.record": 5e-9, "none": 0.0})


def test_summary_needs_a_round():
    data = _xspace({0: [(0, 5)]}, [[("fl.ingest.wait", 0, 10)]])
    with pytest.raises(ValueError, match="no fl.round span"):
        trace.summarize(data.planes)
