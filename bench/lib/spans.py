"""Reduction of a profiler trace's host spans to time per named step.

Reads the host plane (``/host:CPU``) of the ``.xplane.pb`` that
``jax.profiler`` writes, one line per host thread, and keeps the spans the
benchmark knows: its own ``window`` and ``engine_batch`` and the program's
spans (``SPANS``, as ``repro.tracing.SPANS`` names them). On each thread
spans nest; a span's direct children are the known spans nested right
inside it. For each span name, clipped to the ``window`` span:

- ``count``: spans that overlap the window;
- ``total_s``: their time;
- ``self_s``: their time minus what their direct children cover.

It needs no device plane, so a trace recorded on the CPU reduces too. A
trace of a program without these spans gives nothing for their names.

The span metrics' readers (``ms_per_query``) read the trace that
``bench/run.py --trace 1`` leaves in its ``TRACE_DIR``, once per run.
With a device plane, the device's idle time is put down to spans too:
``idle_by_label`` splits it by the deepest span open at each idle instant
on any host thread (a tie to the later start), and ``idle_gaps`` names the
longest gaps by the span open at each gap's middle. Run as a script on a
trace directory, it prints all three as JSON:

    python3 -m bench.lib.spans [.bench_trace]
"""

from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

from . import trace

#: where ``bench/run.py`` records a ``--trace 1`` window (its ``TRACE_DIR``)
TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_trace"

HOST_PLANE = trace.HOST_PLANE
WINDOW_SPAN = trace.WINDOW_SPAN
#: the benchmark's own spans around the program (``bench/deployments``)
BENCH_SPANS = (WINDOW_SPAN, "engine_batch")
#: the program's spans, as ``repro.tracing.SPANS`` names them
SPANS = (
    "admission.window",
    "admission.batch",
    "endpoint.run",
    "endpoint.parse",
    "algebra.evaluate",
    "engine.execute_batch",
    "engine.scan_launch",
    "engine.scan_fetch",
    "engine.scan_unpack",
    "engine.host_join",
    "device_join.run",
    "device_join.fetch",
    "scheduler.schedule",
    "gc",
)
KNOWN = BENCH_SPANS + SPANS


@dataclass
class Span:
    name: str
    start: int                    # ns on the profiler's clock
    end: int
    depth: int                    # known spans it is nested in, same thread
    children: list = field(default_factory=list)


def host_lines(pd) -> list[list[Span]]:
    """Per host thread, its known spans in order of start, each with its
    depth and direct children."""
    host = next((p for p in pd.planes if p.name == HOST_PLANE), None)
    if host is None:
        return []
    out = []
    for line in host.lines:
        evs = sorted(((int(e.start_ns), -int(e.duration_ns), e.name)
                      for e in line.events if e.name in KNOWN))
        spans: list[Span] = []
        stack: list[Span] = []
        for a, neg_d, name in evs:
            while stack and stack[-1].end <= a:
                stack.pop()
            s = Span(name, a, a - neg_d, len(stack))
            if stack:
                stack[-1].children.append(s)
            stack.append(s)
            spans.append(s)
        if spans:
            out.append(spans)
    return out


def window_of(lines: list[list[Span]]) -> tuple[int, int]:
    for spans in lines:
        for s in spans:
            if s.name == WINDOW_SPAN:
                return s.start, s.end
    raise ValueError("the trace has no 'window' host span")


def _covered(intervals: list[tuple[int, int]]) -> int:
    total, reach = 0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total


def reduce_lines(lines: list[list[Span]]) -> dict:
    """``{name: {"count", "total_s", "self_s"}}`` for every known span
    name that overlaps the window."""
    w0, w1 = window_of(lines)

    def clip(s):
        return max(s.start, w0), min(s.end, w1)

    out: dict[str, dict] = {}
    for spans in lines:
        for s in spans:
            a, b = clip(s)
            if b <= a:
                continue
            kids = [(max(ca, a), min(cb, b))
                    for ca, cb in map(clip, s.children)]
            busy = _covered([(ka, kb) for ka, kb in kids if kb > ka])
            rec = out.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                          "self_s": 0.0})
            rec["count"] += 1
            rec["total_s"] += (b - a) / 1e9
            rec["self_s"] += (b - a - busy) / 1e9
    return out


def deepest_at(lines: list[list[Span]], points: list[int]) -> list:
    """For each point (ns), the deepest known span open at it on any host
    thread, a tie going to the later start; None where none is open."""
    order = sorted(range(len(points)), key=points.__getitem__)
    best: list = [None] * len(points)
    for spans in lines:
        stack: list[Span] = []
        j = 0
        for i in order:
            t = points[i]
            while j < len(spans) and spans[j].start <= t:
                while stack and stack[-1].end <= spans[j].start:
                    stack.pop()
                stack.append(spans[j])
                j += 1
            while stack and stack[-1].end <= t:
                stack.pop()
            if stack:
                s = stack[-1]
                b = best[i]
                if b is None or (s.depth, s.start) > (b.depth, b.start):
                    best[i] = s
    return best


_loaded: dict = {}


def load(trace_dir: Path):
    """The ``ProfileData`` of the trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(trace.find_xplane(trace_dir)))


def window_spans(rec: dict) -> dict:
    """``reduce_lines`` of the run's trace in ``TRACE_DIR``, read once per
    trace file; {} for a run that was not traced."""
    if not rec.get("trace"):
        return {}
    path = trace.find_xplane(TRACE_DIR)
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = reduce_lines(host_lines(load(TRACE_DIR)))
    return _loaded[key]


def ms_per_query(rec: dict, name: str) -> float | None:
    """Self time of the spans ``name`` in the traced window, in ms per
    answer attempted in it; None where the trace has no such span."""
    s = window_spans(rec).get(name)
    n = rec["window"]["attempted"]
    return 1000.0 * s["self_s"] / n if s and n else None


def idle_intervals(pd, w0: int, w1: int) -> list[tuple[int, int]]:
    """The intervals (ns) of ``[w0, w1)`` in which no XLA operation ran on
    any TPU device plane, as ``trace.reduce_profile`` counts them."""
    busy = []
    for plane in pd.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                a = max(int(ev.start_ns), w0)
                b = min(int(ev.start_ns + ev.duration_ns), w1)
                if b > a:
                    busy.append((a, b))
    idle, prev = [], w0
    for a, b in trace._union(busy) + [(w1, w1)]:
        if a > prev:
            idle.append((prev, a))
        prev = max(prev, b)
    return idle


def _label(s) -> str:
    return WINDOW_SPAN if s is None else s.name


def idle_gaps(lines: list[list[Span]], idle: list[tuple[int, int]],
              top: int = trace.TOP) -> list[list]:
    """The ``top`` longest idle intervals as ``[label, seconds]``, each
    named by the deepest span open at its middle."""
    open_at = deepest_at(lines, [(a + b) // 2 for a, b in idle])
    gaps = [[_label(s), (b - a) / 1e9] for (a, b), s in zip(idle, open_at)]
    return sorted(gaps, key=lambda g: -g[1])[:top]


def idle_by_label(lines: list[list[Span]],
                  idle: list[tuple[int, int]]) -> dict[str, float]:
    """Seconds of the ``idle`` intervals (ns) under each label, longest
    first: each piece between two span boundaries goes to the deepest span
    open in it, or to ``window``."""
    cuts = sorted({t for spans in lines for s in spans
                   for t in (s.start, s.end)})
    pieces = []
    for a, b in idle:
        inner = cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)]
        edges = [a] + inner + [b]
        pieces += zip(edges, edges[1:])
    open_at = deepest_at(lines, [(a + b) // 2 for a, b in pieces])
    out: dict[str, float] = defaultdict(float)
    for (a, b), s in zip(pieces, open_at):
        out[_label(s)] += (b - a) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main(argv: list[str]) -> int:
    pd = load(Path(argv[0]) if argv else TRACE_DIR)
    lines = host_lines(pd)
    idle = idle_intervals(pd, *window_of(lines))
    print(json.dumps({"spans": reduce_lines(lines),
                      "idle_by_label": idle_by_label(lines, idle),
                      "idle_gaps": idle_gaps(lines, idle)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
