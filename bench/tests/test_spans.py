"""The host-span reduction, its idle split and gap labels and the span
metrics' readers (``bench/lib/spans.py``), on a small trace recorded on
the CPU: spans
nested on one thread, the benchmark's ``window`` on another, one span
begun before the window and one after it. Device operations are laid over
the recorded host plane where the gap-label rule needs them, since a CPU
trace has no TPU plane."""

import sys
import threading
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from bench.lib import spans, trace  # noqa: E402

MS = 1_000_000


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    from jax.profiler import ProfileData, TraceAnnotation as ann

    d = tmp_path_factory.mktemp("trace")
    started, stop = threading.Event(), threading.Event()

    def bench_main():                     # the window, on its own thread
        with ann("window"):
            started.set()
            stop.wait()

    jax.profiler.start_trace(str(d))
    with ann("admission.batch"):          # begun before the window
        time.sleep(0.004)
        t = threading.Thread(target=bench_main)
        t.start()
        started.wait()
        with ann("engine.execute_batch"):
            with ann("engine.scan_fetch"):
                time.sleep(0.004)
            time.sleep(0.002)
            with ann("engine.host_join"):
                with ann("gc"):
                    time.sleep(0.002)
                time.sleep(0.004)
        time.sleep(0.002)
    time.sleep(0.003)                     # only the window is open
    with ann("endpoint.run"):
        time.sleep(0.003)
    stop.set()
    t.join()
    with ann("engine.host_join"):         # after the window
        time.sleep(0.002)
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(trace.find_xplane(d)))
    threads = spans.host_lines(pd)
    by_name = {}
    for line in threads:
        for s in line:
            by_name.setdefault(s.name, []).append(s)
    return pd, threads, by_name, d


def _s(ns):
    return ns / 1e9


def test_self_time_is_time_less_direct_children_clipped_to_window(recorded):
    _, threads, by, _ = recorded
    red = spans.reduce_lines(threads)
    w0, w1 = spans.window_of(threads)
    (batch,), (ex,), (fetch,), (gc,) = (by[n] for n in (
        "admission.batch", "engine.execute_batch", "engine.scan_fetch",
        "gc"))
    join = min(by["engine.host_join"], key=lambda s: s.start)
    run_ = by["endpoint.run"][0]
    assert batch.start < w0 < batch.end         # clipped at the start
    assert [c.name for c in batch.children] == ["engine.execute_batch"]
    assert (ex.depth, join.depth, gc.depth) == (1, 2, 3)

    def rec(name):
        return red[name]["count"], red[name]["total_s"], red[name]["self_s"]

    assert rec("admission.batch") == pytest.approx(
        (1, _s(batch.end - w0), _s(batch.end - w0 - (ex.end - ex.start))),
        abs=1e-12)
    assert rec("engine.execute_batch") == pytest.approx(
        (1, _s(ex.end - ex.start), _s(ex.end - ex.start - (
            fetch.end - fetch.start) - (join.end - join.start))),
        abs=1e-12)
    # the host join after the window is left out
    assert rec("engine.host_join") == pytest.approx(
        (1, _s(join.end - join.start),
         _s(join.end - join.start - (gc.end - gc.start))), abs=1e-12)
    assert red["engine.scan_fetch"]["self_s"] == red[
        "engine.scan_fetch"]["total_s"] >= 0.004
    assert red["engine.execute_batch"]["self_s"] >= 0.002
    assert red["engine.host_join"]["self_s"] >= 0.004
    assert red["endpoint.run"]["total_s"] == pytest.approx(
        _s(run_.end - run_.start), abs=1e-12)
    assert red["window"]["total_s"] == pytest.approx(_s(w1 - w0), abs=1e-12)
    assert set(red) == {"window", "admission.batch", "engine.execute_batch",
                        "engine.scan_fetch", "engine.host_join", "gc",
                        "endpoint.run"}


def test_deepest_span_labels_a_point_and_a_tie_goes_to_the_later_start(
        recorded):
    _, threads, by, _ = recorded
    w0, w1 = spans.window_of(threads)
    (batch,), (gc,), (run_,) = by["admission.batch"], by["gc"], by[
        "endpoint.run"]
    points = [(gc.start + gc.end) // 2,          # depth 3 against 0
              (run_.start + run_.end) // 2,      # depth 0 against 0
              (batch.end + run_.start) // 2,     # the window alone
              (batch.start + w0) // 2,           # before the window
              w1 + 10 * MS]                      # nothing open
    got = [None if s is None else s.name
           for s in spans.deepest_at(threads, points)]
    assert got == ["gc", "endpoint.run", "window", "admission.batch", None]


class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = (name, start_ns,
                                                      duration_ns)


class _Named:
    def __init__(self, name, items, key):
        self.name = name
        setattr(self, key, items)


class _Profile:
    """The recorded host plane and a TPU plane whose operations fill the
    window but for the given idle intervals."""

    def __init__(self, pd, w0, w1, idle):
        ops, prev = [], w0
        for a, b in sorted(idle) + [(w1, w1)]:
            if a > prev:
                ops.append(_Event("%fusion = s32[1]{0} fusion()", prev,
                                  a - prev))
            prev = b
        device = _Named("/device:TPU:0", [_Named("XLA Ops", ops, "events")],
                        "lines")
        self.planes = list(pd.planes) + [device]


def test_idle_split_and_gap_labels_by_the_deepest_span(recorded):
    pd, threads, by, _ = recorded
    w0, w1 = spans.window_of(threads)
    (gc,), (run_,), (batch,), (fetch,) = (by[n] for n in (
        "gc", "endpoint.run", "admission.batch", "engine.scan_fetch"))
    idle = [(gc.start + MS // 4, gc.end - MS // 4),
            (run_.start + MS // 4, run_.end - MS // 4),
            (batch.end + MS // 4, run_.start - MS // 4),
            (fetch.end - MS // 2, fetch.end + MS // 2)]   # across two spans
    profile = _Profile(pd, w0, w1, idle)
    got = spans.idle_intervals(profile, w0, w1)
    assert got == sorted(idle)
    labels = ["gc", "endpoint.run", "window", "engine.execute_batch"]
    want = sorted(([label, _s(b - a)] for label, (a, b) in zip(labels, idle)),
                  key=lambda g: -g[1])
    assert spans.idle_gaps(threads, got) == want
    # the benchmark's own breakdown is left as it was: it knows only its
    # own spans, so every gap of this trace reads ``window``
    reduced = trace.reduce_profile(profile)
    assert reduced["breakdown"]["idle_gaps"] == [["window", g] for _, g in
                                                 want]
    assert reduced["busy_s"] == pytest.approx(
        _s(w1 - w0 - sum(b - a for a, b in idle)), abs=1e-12)
    # each idle instant to the deepest span open at it
    split = spans.idle_by_label(threads, got)
    assert split == pytest.approx({
        "gc": _s(gc.end - gc.start - MS // 2),
        "endpoint.run": _s(run_.end - run_.start - MS // 2),
        "window": _s(run_.start - batch.end - MS // 2),
        "engine.scan_fetch": _s(MS // 2),
        "engine.execute_batch": _s(MS // 2)}, abs=1e-12)


def test_readers_of_the_span_metrics(recorded, monkeypatch, tmp_path):
    _, threads, _, d = recorded
    red = spans.reduce_lines(threads)
    assert spans.TRACE_DIR == run.TRACE_DIR
    monkeypatch.setattr(spans, "TRACE_DIR", d)
    batches = [{"size": 2, "scan_fetch_bytes": 20_000, "scan_rows_kept": 5},
               {"size": 2, "scan_fetch_bytes": 10_000, "scan_rows_kept": 7}]
    rec = {"trace": {"window_s": 1.0}, "window": {"attempted": 4},
           "batches": batches}
    assert run.reader("scan_fetch_ms_per_query")(rec) == pytest.approx(
        1000 * red["engine.scan_fetch"]["self_s"] / 4)
    assert run.reader("host_join_ms_per_query")(rec) == pytest.approx(
        1000 * red["engine.host_join"]["self_s"] / 4)
    assert run.reader("scan_fetch_bytes_per_row")(rec) == 2_500
    # a trace without the spans gives nothing
    assert run.reader("scan_unpack_ms_per_query")(rec) is None
    assert run.reader("algebra_ms_per_query")(rec) is None
    # nor does an untraced run, or a program without the spans or the
    # counters: its trace holds the benchmark's spans alone
    untraced = dict(rec, trace=None)
    assert run.reader("scan_fetch_ms_per_query")(untraced) is None
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    parent = {"trace": {"window_s": 1.0}, "window": {"attempted": 4},
              "batches": [{"size": 2, "scans_deduped": 0}]}
    import jax
    from jax.profiler import TraceAnnotation as ann

    jax.profiler.start_trace(str(tmp_path))
    with ann("window"):
        with ann("engine_batch"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    for name in ("scan_fetch_ms_per_query", "scan_unpack_ms_per_query",
                 "host_join_ms_per_query", "algebra_ms_per_query",
                 "scan_fetch_bytes_per_row"):
        assert run.reader(name)(parent) is None
