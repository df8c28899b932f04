"""The profiler trace of a run's window, and its reduction to numbers.

``Tracer`` records the window with JAX's profiler (host tracing at the
level of user annotations, no Python tracer, so the trace stays small)
and marks the harness's own host spans with ``TraceAnnotation``:
``bench.round`` around each ``run_round`` call and ``bench.between``
around the harness's work between rounds. ``reduce`` turns an
``.xplane.pb`` into:

* ``busy_s``: per chip, the union of the intervals in which an XLA op
  ran (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), inside
  the window; the mean over the chips;
* ``window_s``: the window, from the first ``bench.round`` span's start
  to the last one's end on the host;
* ``kernel_s``: per named kernel, the summed device time of its events,
  mean over the chips;
* ``scope_s``: per program scope (a ``jax.named_scope`` of the round
  program), the union of the intervals of its ops, per chip, inside
  the window; the mean over the chips. A union and never a sum: a
  ``while`` op's event covers the events of the ops of its body, and
  those of a loop nested in it, and each is an op of the scope;
* ``collective_exposed_s``: per chip, the time collective ops ran while
  no other op did; the mean over the chips;
* ``breakdown``: the ten device ops that took most time, and the ten
  longest idle gaps, each named by the host span it fell in.

``scope_ops`` finds a scope's ops in the compiled program's text, and
``scope_tests`` makes a task's ``scopes()`` from them.
"""
from __future__ import annotations

import glob
import re
import shutil
from collections import defaultdict
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Set, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ROUND_SPAN, BETWEEN_SPAN = "bench.round", "bench.between"
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)[-.\w]*\s*=|"
                        r"^%?(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")
TOP = 10
# HLO text: a computation's header, an instruction, its op_name, and the
# computations an instruction calls
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s.*\{\s*$")
INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.-]+)\s+=\s")
OP_NAME = re.compile(r'\bop_name="([^"]*)"')
CALLS = re.compile(r"\b(?:body|condition|calls|to_apply|true_computation|"
                   r"false_computation)=%?([\w.-]+)")
CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)="
                        r"\{([^}]*)\}")
TRANSFORM = re.compile(r"^[\w.-]*\((.*)\)$")

Interval = Tuple[int, int]
Tests = Dict[str, Callable[[str], bool]]


def op_name(event_name: str) -> str:
    """An XLA op event is named by its HLO instruction text; its name is
    what precedes ' = '."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _in_scope(op_path: str, scope: str) -> bool:
    """Whether ``scope`` is a component of an op_name path. A component
    under a transformation, such as ``transpose(jvp(attn))`` in a
    backward pass, is the scope it wraps."""
    for part in op_path.split("/"):
        while part != scope:
            m = TRANSFORM.match(part)
            if not m:
                break
            part = m.group(1)
        if part == scope:
            return True
    return False


def scope_ops(hlo_text: str, scope: str) -> Set[str]:
    """Names of the instructions of a compiled program's text (HLO,
    ``compiled.as_text()``) that belong to ``scope``: those whose
    metadata ``op_name`` has the scope as a path component, and, in a
    computation that such an instruction calls, at any depth (a ``while``
    loop's body and condition, a conditional's branches), those that
    carry no ``op_name`` of their own, as the loop's counter and copies
    do. An op of another scope that XLA fused into a fusion of this one
    keeps its own name; only the fusion runs, as one op."""
    members: Dict[str, List[str]] = defaultdict(list)
    callees: Dict[str, List[str]] = {}
    tagged: Set[str] = set()
    found: Set[str] = set()
    roots: List[str] = []
    comp = None
    for line in hlo_text.splitlines():
        head = None if line[:1].isspace() else COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        members[comp].append(name)
        called = CALLS.findall(line)
        for group in CALL_LISTS.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",")
                       if c.strip()]
        callees[name] = called
        op = OP_NAME.search(line)
        if op and op.group(1):
            tagged.add(name)
            if _in_scope(op.group(1), scope):
                found.add(name)
                roots += called
    seen: Set[str] = set()
    while roots:
        c = roots.pop()
        if c in seen:
            continue
        seen.add(c)
        for name in members.get(c, ()):
            if name not in tagged:
                found.add(name)
                roots += callees.get(name, [])
    return found


def scope_tests(hlo_text: str, scopes: Iterable[str]) -> Tests:
    """Each scope -> a test on a trace's op event name: whether the op
    is one of the scope's instructions (``scope_ops``)."""
    out = {}
    for scope in scopes:
        names = frozenset(scope_ops(hlo_text, scope))
        out[scope] = lambda n, names=names: op_name(n) in names
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


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals a not covered by the
    (disjoint, sorted) intervals b."""
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


def is_collective(event_name: str) -> bool:
    return bool(COLLECTIVE.search(event_name))


def reduce(planes, kernels: Tests, scopes: Optional[Tests] = None
           ) -> Dict[str, object]:
    """``planes``: a ProfileData's planes (or objects with the same
    ``name``/``lines``/``events`` shape). ``kernels`` maps a kernel's
    name, and ``scopes`` a program scope's, to a test on an op event's
    name."""
    scopes = scopes or {}
    spans: Dict[str, List[Interval]] = defaultdict(list)
    device_ops: Dict[int, List[Tuple[str, int, int]]] = {}
    seen: Dict[str, List[str]] = {}
    for plane in planes:
        seen[plane.name] = [line.name for line in plane.lines]
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[int(m.group(1))] = [
                        (e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in (ROUND_SPAN, BETWEEN_SPAN):
                        spans[e.name].append((int(e.start_ns),
                                              int(e.end_ns)))
    if not device_ops:
        raise ValueError(f"the trace holds no TPU op events; its planes "
                         f"and their lines: {seen}")
    if not spans[ROUND_SPAN]:
        raise ValueError(f"the trace holds no {ROUND_SPAN} host span")
    lo = min(s for s, _ in spans[ROUND_SPAN])
    hi = max(e for _, e in spans[ROUND_SPAN])
    chips = len(device_ops)
    busy, exposed = 0, 0
    kernel_ns: Dict[str, int] = {k: 0 for k in kernels}
    scope_ns: Dict[str, int] = {k: 0 for k in scopes}
    op_ns: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[int, int]] = []
    for ops in device_ops.values():
        inside = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        coll = union(clip([(s, e) for n, s, e in inside
                           if is_collective(n)], lo, hi))
        other = union(clip([(s, e) for n, s, e in inside
                            if not is_collective(n)], lo, hi))
        both = union(coll + other)
        busy += length(both)
        exposed += length(subtract(coll, other))
        for n, s, e in inside:
            d = min(e, hi) - max(s, lo)
            op_ns[op_name(n)] += d
            for k, test in kernels.items():
                if test(n):
                    kernel_ns[k] += d
        for k, test in scopes.items():
            scope_ns[k] += length(union(clip(
                [(s, e) for n, s, e in inside if test(n)], lo, hi)))
        gaps += subtract([(lo, hi)], both)
    window = hi - lo
    return {
        "busy_s": busy / chips / 1e9, "window_s": window / 1e9,
        "chips": chips,
        "kernel_s": {k: v / chips / 1e9 for k, v in kernel_ns.items()},
        "scope_s": {k: v / chips / 1e9 for k, v in scope_ns.items()},
        "collective_exposed_s": exposed / chips / 1e9,
        "breakdown": {
            "device_ops": [[n, v / chips / 1e9] for n, v in sorted(
                op_ns.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": _name_gaps(gaps, spans)},
    }


def _name_gaps(gaps: List[Interval], spans: Dict[str, List[Interval]]
               ) -> List[list]:
    """The longest idle gaps, each named by the host span that covers
    most of it (``host:<span>``, or ``host:none``)."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        best, cover = "none", 0
        for name, ivs in spans.items():
            c = length(clip(ivs, s, e))
            if c > cover:
                best, cover = name, c
        out.append([f"host:{best}", (e - s) / 1e9])
    return out


class Tracer:
    """Traces one window into ``directory`` and reduces it; the trace
    files are deleted once read."""

    def __init__(self, jax, directory: Path):
        self.jax = jax
        self.directory = Path(directory)

    def start(self):
        shutil.rmtree(self.directory, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1       # user annotations only
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(str(self.directory),
                                      profiler_options=opts)

    def span(self, name: str, t: int):
        return self.jax.profiler.TraceAnnotation(name, round=t)

    def stop(self):
        self.jax.profiler.stop_trace()

    def reduce(self, kernels: Tests, scopes: Optional[Tests] = None
               ) -> Dict[str, object]:
        try:
            files = glob.glob(str(self.directory / "**" / "*.xplane.pb"),
                              recursive=True)
            if len(files) != 1:
                raise ValueError(f"expected one trace file, found {files}")
            data = self.jax.profiler.ProfileData.from_file(files[0])
            return reduce(data.planes, kernels, scopes)
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)
