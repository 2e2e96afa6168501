"""What the harness needs of a deployment of the program."""

from __future__ import annotations

import time

import numpy as np


class Deployment:
    """One deployment of the program: its endpoint, the queue in front of
    it, and the engine whose counters the per-layer metrics read."""

    #: keyword arguments for ``AdmissionQueue`` besides the window
    queue_kw: dict = {}

    def __init__(self, endpoint) -> None:
        self.endpoint = endpoint

    @property
    def engine(self):
        return self.endpoint.engine

    def queue(self, spans=None):
        """A fresh admission queue in front of the endpoint, with the
        program's default window and batch size.

        ``spans`` (the trace run's span factory) wraps every call the
        queue makes into the endpoint in a host span."""
        from repro.runtime.admission import AdmissionQueue

        ep = self.endpoint if spans is None else _Spanned(self.endpoint,
                                                          spans)
        return AdmissionQueue(ep, **self.queue_kw)


class _Spanned:
    """The endpoint, with each batch call inside a named host span."""

    def __init__(self, endpoint, spans) -> None:
        self._ep = endpoint
        self._spans = spans

    def __getattr__(self, name):
        return getattr(self._ep, name)

    def query_many(self, texts):
        with self._spans("engine_batch"):
            return self._ep.query_many(texts)

    def run_round(self, user_texts, **kw):
        with self._spans("engine_batch"):
            return self._ep.run_round(user_texts, **kw)


def dictionary_of(graph):
    from repro.rdf.dictionary import Dictionary

    return Dictionary.from_arrays({"entities": graph.entities,
                                   "predicates": np.asarray(graph.predicates)})


def timed(times: dict, name: str, fn):
    t0 = time.perf_counter()
    out = fn()
    times[name] = time.perf_counter() - t0
    return out
