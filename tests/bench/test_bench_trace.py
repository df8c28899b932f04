"""The benchmark's trace reduction (bench/tracing.py), on synthetic traces
with known intervals."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "bench"))

import tracing  # noqa: E402

NS = 1000   # picoseconds per nanosecond


def _xspace(device_ops, host_spans):
    """A text-proto XSpace: ``device_ops`` maps a chip index to
    (name, start_ns, end_ns) op events; ``host_spans`` is a list of
    (name, start_ns, end_ns) on one host thread."""
    meta, ids = [], {}

    def mid(name):
        if name not in ids:
            ids[name] = len(ids) + 1
            meta.append(name)
        return ids[name]

    def events(evs):
        return "\n".join(
            f"events {{ metadata_id: {mid(n)} offset_ps: {s * NS} "
            f"duration_ps: {(e - s) * NS} }}" for n, s, e in evs)

    def metadata():
        return "\n".join(
            f'event_metadata {{ key: {ids[n]} value {{ id: {ids[n]} '
            f'name: {json.dumps(n)} }} }}' for n in meta)

    planes = []
    for chip, ops in device_ops.items():
        ids.clear()
        meta.clear()
        body = events(ops)
        planes.append(f'planes {{ id: {chip + 1} name: "/device:TPU:{chip}" '
                      f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 '
                      f'{body} }} {metadata()} }}')
    ids.clear()
    meta.clear()
    body = events(host_spans)
    planes.append(f'planes {{ id: 99 name: "/host:CPU" lines {{ id: 1 '
                  f'name: "python3" timestamp_ns: 0 {body} }} '
                  f'{metadata()} }}')
    import jax
    return jax.profiler.ProfileData.from_text_proto("\n".join(planes))


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
    data = _xspace({0: chip0, 1: chip1}, host)
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


def test_no_device_ops_is_an_error():
    data = _xspace({}, [("bench.round", 0, 10)])
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
