"""Reduction of a profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``. The window is the benchmark's own host span
``window``; everything is clipped to it.

- ``busy_s``: the union of the intervals of the device's XLA operations,
  averaged over the devices traced; ``window_s`` the window's length.
- ``kernels``: per query kernel, the device seconds of its events and the
  bytes its operation needs (``bench.lib.work``) summed over those events,
  from the operand shapes in each event's HLO text. A kernel is known by
  the name of its custom call, which is the name of its jitted wrapper
  (``KERNELS``).
- ``breakdown``: the ten device operations that took most time (named
  ``<XLA module>/<HLO instruction>``), and the
  ten longest idle gaps, each named by the benchmark's innermost host span
  open at the gap's middle (``engine_batch``: the program is running an
  admission batch; ``window``: between batches).
"""

from __future__ import annotations

import bisect
import re
from pathlib import Path

from . import work

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"
HOST_SPANS = ("engine_batch",)
TOP = 10


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: Path) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(find_xplane(trace_dir))))


#: the query kernels, by the name their custom call takes in the HLO text
#: of the device's "XLA Ops" events (the jitted wrapper's name)
KERNELS = {
    "scan_probe": "scan_probe",
    "probe_sorted_many": "probe_sorted",
    "triple_scan_many": "triple_scan",
    "triple_scan": "triple_scan",
}
_OP = re.compile(r"^%([\w.-]+?)(?:\.\d+)? = ")


def op_name(text: str) -> str:
    """The HLO instruction name an "XLA Ops" event is named by
    (``%scan_probe.1 = (...) custom-call(...)`` -> ``scan_probe``)."""
    m = _OP.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def kernel_of(text: str) -> str | None:
    """The query kernel an XLA operation runs, or None."""
    if "custom-call(" not in text:
        return None
    return KERNELS.get(op_name(text))


def operand_shapes(text: str) -> list[tuple[int, ...]]:
    """Operand shapes of a custom call, from its HLO text
    (``s32[4194304]{0:T(1024)}`` -> ``(4194304,)``)."""
    args = text.split("custom-call(", 1)[1].split("custom_call_target", 1)[0]
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"[su]32\[([\d,]*)\]", args)]


def kernel_bytes(kernel: str, shapes: list[tuple[int, ...]]) -> int:
    """Bytes of one call's operation from the shapes its kernel receives:
    columns padded to whole blocks of rows that match nothing, keys padded
    to whole blocks (a block is at most 2048 rows, at most 0.1% of a
    shard or a view of the sizes measured here)."""
    if kernel == "triple_scan":          # pattern(s), then three [T] columns
        return work.triple_scan_bytes(shapes[-1][0])
    if kernel == "scan_probe":           # meta, keys [K], three [T] columns
        return work.scan_probe_bytes(shapes[-1][0], shapes[1][0])
    if kernel == "probe_sorted":         # meta, keys [K], probes [Q, P]
        q, p = shapes[2]
        return work.probe_sorted_bytes(shapes[1][0], q * p)
    raise ValueError(f"unknown kernel {kernel!r}")


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce_profile(pd) -> dict:
    planes = list(pd.planes)
    host = next((p for p in planes if p.name == HOST_PLANE), None)
    spans: list[tuple[str, int, int]] = []
    if host is not None:
        for line in host.lines:
            for ev in line.events:
                if ev.name in (WINDOW_SPAN,) + HOST_SPANS:
                    spans.append((ev.name, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)))
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace has no 'window' host span")
    w0, w1 = windows[0]
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("the trace has no TPU device plane")

    busy_ns = 0
    ops: dict[str, float] = {}
    kernels: dict[str, dict] = {}
    all_iv: list[tuple[int, int]] = []
    for plane in devices:
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                          e.name.split("(", 1)[0])
                         for e in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in modules]
        iv = []
        for ev in lines.get(OPS_LINE, []):
            a = max(int(ev.start_ns), w0)
            b = min(int(ev.start_ns + ev.duration_ns), w1)
            if b <= a:
                continue
            iv.append((a, b))
            i = bisect.bisect_right(starts, int(ev.start_ns)) - 1
            module = (modules[i][2] if i >= 0
                      and int(ev.start_ns) < modules[i][1] else "?")
            name = f"{module}/{op_name(ev.name)}"
            ops[name] = ops.get(name, 0.0) + (b - a) / 1e9
            k = kernel_of(ev.name)
            if k is not None:
                rec = kernels.setdefault(k, {"seconds": 0.0, "bytes": 0,
                                             "events": 0})
                rec["seconds"] += (b - a) / 1e9
                rec["events"] += 1
                rec["bytes"] += kernel_bytes(k, operand_shapes(ev.name))
        merged = _union(iv)
        busy_ns += sum(b - a for a, b in merged)
        all_iv += merged
    merged = _union(all_iv)
    gaps = []
    prev = w0
    for a, b in merged + [(w1, w1)]:
        if a > prev:
            mid = (prev + a) // 2
            label = WINDOW_SPAN
            for name, s0, s1 in spans:
                if name != WINDOW_SPAN and s0 <= mid < s1:
                    label = name
            gaps.append((label, (a - prev) / 1e9))
        prev = max(prev, b)
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_ns / 1e9 / len(devices),
        "window_s": (w1 - w0) / 1e9,
        "kernels": kernels,
        "breakdown": {"device_ops": [[n, s] for n, s in top_ops],
                      "idle_gaps": [[n, s] for n, s in gaps[:TOP]]},
    }
