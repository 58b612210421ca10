"""Closed-loop clients driving ``repro.serve.AnnService``.

Each client holds at most one request: it submits, waits for its answer,
and submits the next at once.  The service is synchronous, so the loop
runs the clients in one thread: a submit that fills a batch flushes
inline; when every client is waiting and no batch has filled, the loop
sleeps until the oldest request's max-wait deadline and calls ``tick``,
which flushes.  Requests are taken in order from one seeded stream:
``sizes`` gives the queries of each request, which are the next rows of
the query pool (wrapping round).

A submit that fills a batch returns only when the flush is done, so the
clients behind it in the loop submit late.  Each answer keeps that lag:
a request is due when its client got the previous answer, and its
latency counts from then (``late_s + latency_s``), as a client's would
that had queued during the flush.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import List, Optional

import numpy as np

__all__ = ["ClosedLoop", "Answer", "Flush", "request_sizes"]


def request_sizes(count: int, lo: int, hi: int, seed: int,
                  stream: int) -> np.ndarray:
    """``count`` request sizes: each run of ``hi-lo+1`` requests holds every
    size in ``[lo, hi]`` once, in an order drawn from the seed, so every
    seed sends the same work in another order."""
    rng = np.random.default_rng([seed, 2741, stream])
    span = np.arange(lo, hi + 1)
    reps = -(-count // len(span))
    return np.concatenate([rng.permutation(span) for _ in range(reps)])[:count]


@dataclasses.dataclass
class Answer:
    rows: np.ndarray         # query-pool rows of the request
    ids: np.ndarray
    dists: np.ndarray
    submit_s: float          # service clock at submit
    latency_s: float         # submit -> results ready (the ticket's)
    late_s: float            # due (the client's previous answer) -> submit
    batch_id: int
    batch_size: int


@dataclasses.dataclass
class Flush:
    stats: object            # the flush's SearchStats
    rows: np.ndarray         # query-pool rows it served, in order
    done_s: float


@dataclasses.dataclass
class Result:
    answers: List[Answer]
    flushes: List[Flush]
    start_s: float
    end_s: float
    submitted: int
    late_submit_max_s: float
    late_submit_mean_s: float

    @property
    def queries(self) -> int:
        return int(sum(len(a.rows) for a in self.answers))


class ClosedLoop:
    def __init__(self, svc, pool: np.ndarray, sizes: np.ndarray,
                 clients: int, spans=None):
        self.svc = svc
        self.pool = pool
        self.sizes = sizes
        self.clients = clients
        self.next_req = 0
        self.next_row = 0
        self.spans = spans

    def _next_request(self) -> np.ndarray:
        n = int(self.sizes[self.next_req % len(self.sizes)])
        self.next_req += 1
        rows = (self.next_row + np.arange(n)) % len(self.pool)
        self.next_row += n
        return rows

    def _span(self, name):
        return self.spans(name) if self.spans else _NULL

    def run(self, seconds: Optional[float] = None,
            flushes: Optional[int] = None) -> Result:
        svc, clock = self.svc, self.svc.clock
        max_wait = svc.policy.max_wait_s
        idle = deque((c, None) for c in range(self.clients))
        outstanding = {}                  # client -> (ticket, rows)
        answers: List[Answer] = []
        done_flushes: List[Flush] = []
        late: List[float] = []
        late_of = {}
        submitted = 0
        start = clock()

        def collect() -> bool:
            """Record a flush if the last call made one; True at the end."""
            if svc.batches == collect.batches:
                return False
            collect.batches = svc.batches
            now = clock()
            rows = []
            for c in sorted(outstanding,
                            key=lambda c: outstanding[c][0].request_id):
                t, r = outstanding[c]
                if not t.done:
                    continue
                del outstanding[c]
                rows.append(r)
                answers.append(Answer(r, t.ids, t.dists, t.enqueued_at,
                                      t.latency_s, late_of[t.request_id],
                                      t.batch_id, t.batch_size))
                idle.append((c, t.enqueued_at + t.latency_s))
            done_flushes.append(Flush(svc.last_stats,
                                      np.concatenate(rows) if rows else
                                      np.zeros(0, np.int64), now))
            if flushes is not None:
                return len(done_flushes) >= flushes
            return now - start >= seconds

        collect.batches = svc.batches
        while True:
            finished = False
            while idle and not finished:
                c, ready = idle.popleft()
                rows = self._next_request()
                with self._span("bench.submit"):
                    now = clock()
                    lag = 0.0 if ready is None else now - ready
                    late.append(lag)
                    t = svc.submit(self.pool[rows])
                late_of[t.request_id] = lag
                submitted += 1
                outstanding[c] = (t, rows)
                finished = collect()
            if finished:
                break
            waiting = [t.enqueued_at for t, _ in outstanding.values()
                       if not t.done]
            if not waiting:
                continue
            with self._span("bench.max_wait"):
                pause = min(waiting) + max_wait - clock()
                if pause > 0:
                    time.sleep(pause)
                while not svc.tick():
                    pass
            if collect():
                break
        end = done_flushes[-1].done_s
        return Result(answers, done_flushes, start, end, submitted,
                      max(late, default=0.0),
                      float(np.mean(late)) if late else 0.0)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()
