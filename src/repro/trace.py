"""Host spans of the federated round, on the device trace's clock
(DESIGN.md §16).

``span(name, round=t)`` marks a stretch of host work as a profiler
TraceMe event (``jax.profiler.TraceAnnotation``): with no profiler
running it records nothing and costs about a microsecond. ``timed``
does the same and also measures the stretch with ``perf_counter``; its
``seconds`` is what ``RoundRecord`` reports, so a record's times and the
trace's spans are one reading, not two timers. Spans of one round carry
the same ``round`` argument, on whichever thread they run.

The round program marks its stages with ``jax.named_scope`` (``fl.local``,
``fl.codec``, ``fl.guard``, ``fl.server``, ``fl.server.reduce``,
``fl.server.epilogue``): HLO metadata only, the ops are unchanged, and a
device op is found in a scope by its instruction's ``op_name``.

``summarize`` reads a trace back: per ``fl.*`` span name its summed
seconds, its count and the device idle time inside it, and each idle
nanosecond charged to the innermost span of the driving thread that
covers it. ``python -m repro.trace <trace dir>`` prints that table.
"""
from __future__ import annotations

import glob
import os
import re
import sys
import time
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Tuple

import jax

PREFIX = "fl."
ROUND = "fl.round"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

Interval = Tuple[int, int]


def span(name: str, **args):
    """A TraceMe span around a ``with`` block; ``args`` (``round=t``)
    become the event's stats."""
    return jax.profiler.TraceAnnotation(name, **args)


class timed:
    """``span`` that also times its block: ``seconds`` is set when the
    block exits, raise or not."""

    __slots__ = ("_span", "_tic", "seconds")

    def __init__(self, name: str, **args):
        self._span = jax.profiler.TraceAnnotation(name, **args)
        self.seconds = 0.0

    def __enter__(self) -> "timed":
        self._span.__enter__()
        self._tic = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._tic
        self._span.__exit__(*exc)
        return False


# ---- reading a trace back ----


class HostSpan(NamedTuple):
    name: str
    round: int
    line: int          # index of the host thread's line in its plane
    start_ns: int
    end_ns: int


def host_spans(planes, prefix: str = PREFIX) -> List[HostSpan]:
    """Every host event whose name starts with ``prefix``."""
    out = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefix):
                    stats = dict(e.stats)
                    out.append(HostSpan(e.name, int(stats.get("round", -1)),
                                        i, int(e.start_ns), int(e.end_ns)))
    return out


def device_busy(planes) -> Dict[int, List[Interval]]:
    """Per TPU chip, the union of the intervals in which an op ran."""
    out = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out[int(m.group(1))] = union(
                        (int(e.start_ns), int(e.end_ns)) for e in line.events)
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` outside the disjoint
    sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _self_intervals(spans: List[HostSpan]) -> List[Tuple[str, List[Interval]]]:
    """Spans of one thread nest: each span's interval less its
    children's."""
    order = sorted(spans, key=lambda s: (s.start_ns, -s.end_ns))
    children: Dict[int, List[Interval]] = defaultdict(list)
    stack: List[int] = []
    for i, s in enumerate(order):
        while stack and order[stack[-1]].end_ns <= s.start_ns:
            stack.pop()
        if stack:
            children[stack[-1]].append((s.start_ns, s.end_ns))
        stack.append(i)
    return [(s.name, subtract([(s.start_ns, s.end_ns)],
                              union(children[i])))
            for i, s in enumerate(order)]


def summarize(planes) -> Dict[str, object]:
    """The ``fl.*`` spans of a trace, from the first ``fl.round`` span's
    start to the last one's end. Device numbers are means over the TPU
    chips, and None where the trace holds none."""
    planes = list(planes)
    spans = host_spans(planes)
    rounds = [s for s in spans if s.name == ROUND]
    if not rounds:
        raise ValueError(f"the trace holds no {ROUND} span")
    lo = min(s.start_ns for s in rounds)
    hi = max(s.end_ns for s in rounds)
    busy = device_busy(planes)
    chips = len(busy)

    def idle_in(ivs: List[Interval]) -> float:
        ivs = [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]
        return sum(length(subtract(ivs, b)) for b in busy.values()
                   ) / chips / 1e9

    by_name: Dict[str, List[HostSpan]] = defaultdict(list)
    for s in spans:
        by_name[s.name].append(s)
    table = {}
    for name, ss in sorted(by_name.items()):
        ivs = union((s.start_ns, s.end_ns) for s in ss)
        table[name] = {
            "seconds": sum(s.end_ns - s.start_ns for s in ss) / 1e9,
            "count": len(ss),
            "idle_s": idle_in(ivs) if chips else None}
    idle_self = None
    if chips:
        idle_self = defaultdict(float)
        for line in {s.line for s in rounds}:
            own = [s for s in spans if s.line == line]
            for name, ivs in _self_intervals(own):
                idle_self[name] += idle_in(ivs)
        idle_self["none"] = idle_in([(lo, hi)]) - sum(idle_self.values())
        idle_self = dict(idle_self)
    return {"window_s": (hi - lo) / 1e9, "chips": chips,
            "idle_s": idle_in([(lo, hi)]) if chips else None,
            "spans": table, "idle_self_s": idle_self}


def load(path: str):
    """A trace (``jax.profiler.ProfileData``; read its ``planes`` anew
    for each pass), from an ``.xplane.pb`` or the directory a profiler
    session wrote (the newest file under it)."""
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(files, key=os.path.getmtime)
    return jax.profiler.ProfileData.from_file(path)


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python -m repro.trace <trace dir or .xplane.pb>",
              file=sys.stderr)
        return 2
    s = summarize(load(args[0]).planes)
    print(f"window {s['window_s']:.6f} s, device idle "
          f"{s['idle_s']} s over {s['chips']} chip(s)")
    print(f"{'span':<22}{'count':>7}{'seconds':>14}{'idle inside s':>16}")
    for name, row in s["spans"].items():
        idle = "" if row["idle_s"] is None else f"{row['idle_s']:.6f}"
        print(f"{name:<22}{row['count']:>7}{row['seconds']:>14.6f}"
              f"{idle:>16}")
    if s["idle_self_s"]:
        print("device idle by the innermost span of the driving thread:")
        for name, v in sorted(s["idle_self_s"].items(), key=lambda kv: -kv[1]):
            print(f"  {name:<22}{v:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
