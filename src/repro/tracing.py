"""Named host spans on the profiler's timeline.

The read path opens one span at each layer boundary, so a profile of a
serving run (``jax.profiler.start_trace``) puts every host millisecond of a
batch down to a named step, on the same clock as the device's events:

- ``admission.window``: the dispatcher holding the micro-batch window open,
  from its first sight of an arrival to the drain;
- ``admission.batch`` (``batch``: the batch's ``seq``, which
  ``Ticket.batch_seq`` records; ``size``): one dispatched batch;
- ``endpoint.run``: memo lookups, parses and memo stores of one batch;
  ``endpoint.parse``: one compile on a plan-memo miss;
- ``algebra.evaluate``: one mixed batch of plans and BGPs;
- ``engine.execute_batch`` (``queries``): one engine batch;
- ``engine.scan_launch``: building scan patterns and enqueueing the scan
  kernels; ``engine.scan_fetch`` (``bytes``): waiting for the scan masks
  and copying them to the host; ``engine.scan_unpack``: turning the masks
  into candidate ids;
- ``engine.host_join``: one query's host join (``match_bgp``);
- ``device_join.run``: the device-resident queries of one engine batch;
  ``device_join.fetch``: their one bulk fetch;
- ``scheduler.schedule``: the scheduling solve of one cloud-edge round;
- ``gc`` (``generation``): one run of Python's cyclic collector, on
  whichever thread triggered it.

A span is a ``jax.profiler.TraceAnnotation``, which records only while the
profiler runs (about a microsecond otherwise). Without JAX loaded no
profiler can be recording, so a span is then a ``nullcontext`` and JAX is
never imported for it: the numpy backend runs without JAX.

Spans open per batch, per phase and per query, never per pattern, row or
join step. An admission batch of ``n`` read queries on one store opens at
most ``9 + n`` spans besides ``gc`` and ``endpoint.parse``: itself,
``endpoint.run``, ``algebra.evaluate``, ``engine.execute_batch``, the
three scan spans, the two device-join spans, and ``engine.host_join``
once a query; its window opens one more, and each plan-memo miss one
``endpoint.parse``.
"""

from __future__ import annotations

import contextlib
import gc
import sys

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

_annotation = None


def _annotation_type():
    """``jax.profiler.TraceAnnotation`` once JAX is loaded, else None."""
    global _annotation
    if _annotation is None:
        profiler = sys.modules.get("jax.profiler")
        if profiler is None:
            return None
        _annotation = profiler.TraceAnnotation
    return _annotation


def span(name: str, **meta):
    """A context manager that records ``name`` (with ``meta`` as its
    arguments) as a host span while the profiler runs."""
    ann = _annotation_type()
    if ann is None:
        return contextlib.nullcontext()
    return ann(name, **meta)


_gc_open: list = []


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        ann = _annotation_type()
        if ann is not None:
            s = ann("gc", generation=info["generation"])
            s.__enter__()
            _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def install_gc_spans() -> None:
    """Open a ``gc`` span for each collection from now on, in the whole
    process (``QueryEngine`` installs it); installing twice is a no-op."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
