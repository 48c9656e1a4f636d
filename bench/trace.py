"""Reduction from a profiler trace to device metrics.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Device planes are named ``/device:<KIND>:<i>`` and carry the
operations that ran on the chip on their ``XLA Ops`` line; host planes
carry the benchmark's own ``TraceAnnotation`` spans, named ``bench.*``.
All times are in nanoseconds on the trace's one clock.

``reduce_trace`` gives, for the window that the ``bench.window`` span
marks:

* ``busy_s``: the union of the device's operation intervals, averaged over
  the devices; ``window_s``: the window's length;
* ``programs``: the same union for the operations of each XLA program (the
  ``<program>`` of an operation's name), averaged over the devices;
* ``device_ops``: the operations that took most device time, by self
  time (an operation's time less that of the operations nested in it, as
  a ``while`` holds its body), named ``<program>/<instruction> <opcode>``;
* ``idle_gaps``: the longest intervals in which no operation ran, each
  named by the benchmark spans that cover most of it (``none`` if none).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
#: the XLA programs that hold the parent transform: harvest runs
#: ``core/bfs.py:dp_transform`` (BFS, and SSSP through ``sssp_parents``)
#: on a batch's whole distance matrix, one jitted program
PARENT_PROGRAMS = ("jit_dp_transform",)
_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Complement of merged ``busy`` intervals within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a: Interval, spans: Sequence[Interval]) -> float:
    return sum(max(0.0, min(a[1], e) - max(a[0], s)) for s, e in spans)


def label_gap(gap: Interval, spans: Dict[str, List[Interval]]) -> str:
    """The benchmark spans covering at least half of ``gap``, by coverage
    (``none`` where no span does)."""
    length = gap[1] - gap[0]
    cover = [(overlap(gap, iv), name) for name, iv in spans.items()]
    names = [n for c, n in sorted(cover, reverse=True) if c >= 0.5 * length]
    return "+".join(names) if names else "none"


def self_times(ops: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Nanoseconds of each op name less the time of ops nested in it."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[list] = []       # [name, end, child_ns]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out[top[0]] -= top[2]
        if stack and e <= stack[-1][1]:
            stack[-1][2] += e - s
        out[name] += e - s
        stack.append([name, e, 0.0])
    for name, _, child in stack:
        out[name] -= child
    return out


def short_name(hlo: str, program: str = "") -> str:
    """``<program>/<instruction> <opcode>`` from an HLO instruction text."""
    inst, _, rest = hlo.partition(" = ")
    m = _OPCODE.search(" " + rest)
    label = f"{inst} {m.group(1)}" if m else inst[:60]
    return f"{program.split('(')[0]}/{label}" if program else label


def program_of(op_name: str) -> str:
    """The ``<program>`` part of ``<program>/<instruction> <opcode>``
    (empty where the name has none)."""
    head, sep, _ = op_name.partition("/")
    return head if sep else ""


def parent_transform_s(programs: Dict[str, float]) -> float:
    """Device seconds of the parent transform's programs."""
    return sum(programs.get(p, 0.0) for p in PARENT_PROGRAMS)


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host_spans: Dict[str, List[Interval]],
                  window: Optional[Interval] = None) -> dict:
    """Device metrics from raw events.

    device_ops: device name -> [(op name, start_ns, end_ns)].
    host_spans: span name -> [(start_ns, end_ns)] (``bench.*`` spans).
    window: (start_ns, end_ns); defaults to the ``bench.window`` span.
    """
    if window is None:
        win = host_spans.get(WINDOW_SPAN)
        if not win:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        window = (min(s for s, _ in win), max(e for _, e in win))
    lo, hi = window
    if not device_ops:
        raise ValueError("trace holds no device operations")
    by_op: Dict[str, float] = collections.defaultdict(float)
    by_program: Dict[str, float] = collections.defaultdict(float)
    busy_ns = []
    first_busy = None
    for dev, ops in sorted(device_ops.items()):
        merged = clip(union((s, e) for _, s, e in ops), lo, hi)
        busy_ns.append(sum(e - s for s, e in merged))
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        for name, ns in self_times(inside).items():
            by_op[name] += ns
        per_program = collections.defaultdict(list)
        for name, s, e in inside:
            per_program[program_of(name)].append((s, e))
        for prog, iv in per_program.items():
            by_program[prog] += sum(e - s for s, e in union(iv))
        if first_busy is None:
            first_busy = merged
    spans = {k[len(SPAN_PREFIX):]: clip(union(v), lo, hi)
             for k, v in host_spans.items()
             if k.startswith(SPAN_PREFIX) and k != WINDOW_SPAN}
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "programs": {p: ns / len(busy_ns) / 1e9
                     for p, ns in sorted(by_program.items())},
        "device_ops": [[name, ns / 1e9] for name, ns in top_ops],
        "idle_gaps": [[label_gap(g, spans), (g[1] - g[0]) / 1e9]
                      for g in idle[:TOP]],
    }


def read_xplane(path: str) -> Tuple[dict, dict]:
    """(device ops, host spans) from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops: Dict[str, list] = {}
    host_spans: Dict[str, list] = collections.defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.end_ns, e.name)
                          for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]

            def program(t):
                i = bisect.bisect_right(starts, t) - 1
                return mods[i][2] if i >= 0 and mods[i][1] >= t else ""
            ops = [(short_name(e.name, program(e.start_ns)), e.start_ns,
                    e.end_ns) for e in lines.get(OPS_LINE, [])]
            if ops:
                device_ops[plane.name] = ops
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host_spans[e.name].append((e.start_ns, e.end_ns))
    return device_ops, dict(host_spans)


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]


def reduce_trace(log_dir: str) -> dict:
    return reduce_events(*read_xplane(find_xplane(log_dir)))
