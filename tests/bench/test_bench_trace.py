"""The benchmark's trace reduction (bench/tracing.py), on synthetic traces
with known intervals."""
import json
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "bench"))

import benchtiny  # noqa: E402
import tracing  # noqa: E402


def test_interval_arithmetic():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                               (5, 8)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tracing.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tracing.clip([(0, 4), (6, 9), (10, 12)], 2, 8) == [(2, 4), (6, 8)]


def test_synthetic_trace_known_intervals():
    """Two chips, a window [100, 1100) ns of two rounds. Chip 0 is busy
    [100, 400) with a compute op, [600, 700) with an all-reduce that
    overlaps compute in [650, 700), and runs the epilogue kernel in
    [800, 900). Chip 1 mirrors it with a fully exposed all-gather."""
    kernel = "%custom-call.7 = f32[8,128] custom-call(), custom_call_target=\"tpu_custom_call\""
    chip0 = [("%fusion.1 = f32[] fusion()", 100, 400),
             ("%all-reduce.2 = f32[] all-reduce()", 600, 700),
             ("%fusion.3 = f32[] fusion()", 650, 760),
             (kernel, 800, 900),
             ("%fusion.9 = f32[] fusion()", 0, 50)]      # before the window
    chip1 = [("%fusion.1 = f32[] fusion()", 100, 300),
             ("%all-gather.5 = f32[] all-gather()", 300, 500)]
    host = [("bench.round", 100, 600), ("bench.between", 600, 610),
            ("bench.round", 610, 1100), ("bench.between", 1100, 1105)]
    data = benchtiny.xspace({0: chip0, 1: chip1}, host)
    got = tracing.reduce(data.planes, {
        "batched_epilogue": lambda n: n.startswith("%custom-call.7 ")})
    assert got["chips"] == 2
    assert got["window_s"] == pytest.approx(1000e-9)
    # chip 0: [100,400) + [600,760) + [800,900) = 560; chip 1: 400
    assert got["busy_s"] == pytest.approx((560 + 400) / 2 * 1e-9)
    # exposed collective: chip 0 [600,650) = 50, chip 1 [300,500) = 200
    assert got["collective_exposed_s"] == pytest.approx(125e-9)
    assert got["kernel_s"]["batched_epilogue"] == pytest.approx(50e-9)
    ops = dict(got["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx((300 + 200) / 2 * 1e-9)
    assert "fusion.9" not in ops
    gaps = got["breakdown"]["idle_gaps"]
    # the longest gap: chip 1's [500, 1100), mostly under the 2nd round
    assert gaps[0] == ["host:bench.round", pytest.approx(600e-9)]
    assert len(gaps) <= tracing.TOP


def test_scope_time_is_a_union_not_a_sum():
    """A ``while`` op's event covers the events of its body and of a
    loop nested in it, all ops of one scope: the scope's time is their
    union (the summed times would count the loop three times). An op
    outside the scope is not counted, and the window clips."""
    local = {"while.343", "while.344", "fusion.7", "copy.2"}
    chip0 = [("%while.343 = (f32[]) while(%t), body=%b", 100, 700),
             ("%while.344 = (f32[]) while(%u), body=%c", 150, 650),
             ("%fusion.7 = f32[] fusion(%a), kind=kOutput", 200, 300),
             ("%copy.2 = f32[] copy(%a)", 690, 720),       # past the while
             ("%fusion.9 = f32[] fusion(%w), kind=kLoop", 730, 760),
             ("%while.343 = (f32[]) while(%t), body=%b", 900, 1300)]
    chip1 = [("%while.343 = (f32[]) while(%t), body=%b", 100, 300),
             ("%fusion.9 = f32[] fusion(%w), kind=kLoop", 300, 400)]
    host = [("bench.round", 100, 800), ("bench.round", 850, 1100)]
    got = tracing.reduce(
        benchtiny.xspace({0: chip0, 1: chip1}, host).planes, {},
        {"fl.local": lambda n: tracing.op_name(n) in local,
         "fl.server": lambda n: tracing.op_name(n) == "fusion.9",
         "fl.guard": lambda n: False})
    # chip 0: [100, 720) + [900, 1100) clipped = 820; chip 1: 200
    assert got["scope_s"]["fl.local"] == pytest.approx((820 + 200) / 2e9)
    assert got["scope_s"]["fl.server"] == pytest.approx((30 + 100) / 2e9)
    assert got["scope_s"]["fl.guard"] == 0.0
    ops = dict(got["breakdown"]["device_ops"])     # the breakdown sums
    assert ops["while.343"] == pytest.approx((600 + 200 + 200) / 2e9)


def test_scope_ops_from_compiled_text():
    """On the CPU, a jitted function with nested ``jax.named_scope``s, a
    scan (a ``while`` loop) and a gradient inside a scope: each scope's
    instructions are found in the compiled text, the loop's body and
    condition with the loop, the backward pass's ops of a scope under
    ``jvp(...)``/``transpose(...)`` with it, and nothing of one scope in
    a scope beside it."""
    import jax
    import jax.numpy as jnp

    def loss(w, x):
        with jax.named_scope("attn"):
            h = jnp.tanh(x @ w)
        return (h ** 2).sum()

    def f(w, xs):
        with jax.named_scope("fl.local"):
            def body(c, x):
                return c - 0.1 * jax.grad(loss)(c, x), None
            w2, _ = jax.lax.scan(body, w, xs)
        with jax.named_scope("fl.server"):
            with jax.named_scope("fl.server.reduce"):
                s = (w2 * w2).sum()
            with jax.named_scope("fl.server.epilogue"):
                return jnp.exp(w2) / s

    text = jax.jit(f).lower(jnp.ones((4, 4)),
                            jnp.ones((3, 2, 4))).compile().as_text()
    lines = {tracing.op_name(line.strip().removeprefix("ROOT ")): line
             for line in text.splitlines() if " = " in line
             and line[:1].isspace()}
    scopes = {k: tracing.scope_ops(text, k) for k in (
        "fl.local", "attn", "fl.server", "fl.server.reduce",
        "fl.server.epilogue", "fl.codec")}
    loops = [n for n, line in lines.items() if " while(" in line]
    assert loops and set(loops) <= scopes["fl.local"]
    for loop in loops:           # its body and condition, whole
        for comp in re.findall(r"(?:body|condition)=%?([\w.-]+)",
                               lines[loop]):
            assert _members(text, comp) <= scopes["fl.local"], comp
    assert scopes["attn"] and scopes["attn"] <= scopes["fl.local"]
    assert any("transpose(jvp(attn))" in lines[n] for n in scopes["attn"])
    assert scopes["fl.server.reduce"] and scopes["fl.server.epilogue"]
    assert scopes["fl.server.reduce"] | scopes["fl.server.epilogue"] \
        <= scopes["fl.server"]
    assert not scopes["fl.server.reduce"] & scopes["fl.server.epilogue"]
    assert not scopes["fl.local"] & scopes["fl.server"]
    assert scopes["fl.codec"] == set()


def _members(text, computation):
    """The instruction names of one computation of an HLO text."""
    out, inside = set(), False
    for line in text.splitlines():
        if not line[:1].isspace():
            inside = re.match(rf"^(?:ENTRY\s+)?%?{re.escape(computation)}"
                              rf"\s", line) is not None
        elif inside and " = " in line:
            out.add(tracing.op_name(line.strip().removeprefix("ROOT ")))
    return out


def test_no_device_ops_is_an_error():
    data = benchtiny.xspace({}, [("bench.round", 0, 10)])
    with pytest.raises(ValueError, match="no TPU op events"):
        tracing.reduce(data.planes, {})


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e in a traced run of resnet18.paper,
    trimmed to two rounds (the device op line and the harness's host
    spans): the device plane and its op line are found, the epilogue's
    events are told apart by the names the compiled round gives its
    calls (one per weight leaf, once a round), and the reduction's
    numbers hold together."""
    import gzip
    import jax
    fixtures = HERE / "fixtures"
    calls = set(json.loads(
        (fixtures / "resnet18.paper.epilogue_calls.json").read_text()))
    data = jax.profiler.ProfileData.from_serialized_xspace(gzip.decompress(
        (fixtures / "resnet18.paper.two_rounds.xplane.pb.gz").read_bytes()))
    got = tracing.reduce(data.planes, {
        "batched_epilogue": lambda n: tracing.op_name(n) in calls})
    assert got["chips"] == 1
    assert 0 < got["busy_s"] < got["window_s"]
    assert 0.8 < got["window_s"] < 1.5          # two rounds of ~0.46 s
    events = [e for p in data.planes if p.name == "/device:TPU:0"
              for line in p.lines for e in line.events
              if tracing.op_name(e.name) in calls]
    assert len(calls) == 62 and len(events) == 2 * len(calls)
    assert 0 < got["kernel_s"]["batched_epilogue"] < 0.01
    gaps = got["breakdown"]["idle_gaps"]
    assert gaps and all(name == "host:bench.round" for name, _ in gaps)
    assert sum(s for _, s in gaps) <= got["window_s"] - got["busy_s"] + 1e-9
