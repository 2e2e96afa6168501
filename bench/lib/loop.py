"""Closed-loop clients in front of the program's admission queue.

Each client thread takes the next request of the list, sends it, and
waits for its answer; a request is timed from just before its client
sends it to just after the answer is back. With ``deadline_s`` set, no
client sends after it, and the window closes when the last answer sent
before it is back, so the window holds all of the work it admitted.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

ANSWER_TIMEOUT_S = 300.0


@dataclass
class Outcome:
    index: int
    sent: float
    done: float
    table: object = None
    error: str | None = None


@dataclass
class LoopResult:
    start: float
    end: float
    outcomes: list[Outcome] = field(default_factory=list)
    #: whether the list ran out of requests
    exhausted: bool = False

    @property
    def seconds(self) -> float:
        return self.end - self.start


def run(queue, requests, clients: int,
        deadline_s: float | None = None) -> LoopResult:
    lock = threading.Lock()
    nxt = [0]
    res = LoopResult(start=time.perf_counter(), end=0.0)
    stop_at = None if deadline_s is None else res.start + deadline_s

    def client() -> None:
        while True:
            with lock:
                if stop_at is not None and time.perf_counter() >= stop_at:
                    return
                try:
                    req = requests[nxt[0]]
                except IndexError:
                    res.exhausted = True
                    return
                nxt[0] += 1
            sent = time.perf_counter()
            out = Outcome(req.index, sent, 0.0)
            try:
                ticket = queue.submit(req.text, user=req.user)
                out.table = ticket.result(timeout=ANSWER_TIMEOUT_S)
            except Exception as err:           # an answer that never came
                out.error = f"{type(err).__name__}: {err}"
            out.done = time.perf_counter()
            with lock:
                res.outcomes.append(out)

    threads = [threading.Thread(target=client, name=f"client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    res.end = time.perf_counter()
    res.outcomes.sort(key=lambda o: o.index)
    return res
