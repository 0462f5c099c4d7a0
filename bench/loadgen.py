"""Load generation: seeded schedules, the open and the closed loop, and exact
percentiles over every request of a window.

A traffic mix is a JSON file of parameters under ``bench/traffic/``; this
module is the one generator that reads them:

``loop``       ``"open"`` (arrivals on a schedule, whether or not earlier
               requests are done) or ``"closed"`` (clients that each wait for
               their reply before sending the next request).
``rate_per_s`` open loop: the offered rate.  The window holds exactly
               ``round(rate_per_s * seconds)`` arrivals at uniformly drawn
               times, which is a Poisson process conditioned on its count, so
               every seed offers the same work in another order.
``clients``    closed loop: the number of client threads.
``variants``   how many of the configuration's query variants the mix uses;
               each is sent equally often, in a seeded order.

Before the window, ``WARM_S`` seconds of the same traffic run as set-up.  In
the open loop an arrival goes onto one of ``THREADS`` request threads, the
size of the server's own request pool (``QueryServer``'s default of 4
workers); arrivals beyond that wait for a thread, and the wait counts.

Latency runs from the time a request was due (open loop) or sent (closed
loop) to the time its answer was ready.  A request that failed, or had not
finished ``GRACE_S`` seconds after the window, counts as slower than every
served one.
"""
from __future__ import annotations

import gc
import math
import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

WARM_S = 3.0
GRACE_S = 60.0
THREADS = 4


@dataclass
class Outcome:
    """One request of a window; times in seconds from the window's start:
    due, handed to the request threads (``queued``), taken up by one of
    them (``sent``), answered (``done``)."""
    variant: int
    due: float
    queued: float = math.nan
    sent: float = math.nan
    done: float = math.nan
    ok: bool = False
    report: Any = None
    answer: Any = None
    error: str = ""


@dataclass
class Window:
    outcomes: List[Outcome]
    seconds: float                 # the window's length
    deadline: float                # grace deadline, from the window's start
    loop: str

    def latencies(self) -> List[float]:
        """Seconds per request; a failed request reads as the wait to the
        grace deadline, longer than any served one."""
        return [o.done - o.due if o.ok else self.deadline - o.due
                for o in self.outcomes]

    @property
    def failed(self) -> int:
        return sum(1 for o in self.outcomes if not o.ok)

    def completed_in_window(self) -> int:
        return sum(1 for o in self.outcomes
                   if o.ok and o.done <= self.seconds)


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p`` % of
    the values at or below it."""
    if not values:
        raise ValueError("no values")
    s = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(s)))
    return s[rank - 1]


def open_schedule(rate: float, seconds: float, variants: int,
                  rng: np.random.Generator) -> List[Tuple[float, int]]:
    """(due, variant) pairs: ``round(rate * seconds)`` uniform arrival times
    in [0, seconds), sorted, each variant equally often in seeded order."""
    n = max(1, int(round(rate * seconds)))
    dues = np.sort(rng.uniform(0.0, seconds, n))
    order = np.resize(np.arange(variants), n)
    rng.shuffle(order)
    return list(zip(dues.tolist(), order.tolist()))


def closed_sequences(clients: int, variants: int,
                     rng: np.random.Generator, length: int = 4096
                     ) -> List[List[int]]:
    """Per client, the seeded order in which it sends the variants (each
    variant equally often in every run of ``variants`` requests)."""
    return [np.concatenate([rng.permutation(variants)
                            for _ in range(length // variants + 1)]
                           ).tolist() for _ in range(clients)]


class _Threads:
    """Daemon worker threads fed from a queue: a request goes onto a thread
    at its due time.  Daemon, so a request that never returns cannot keep
    the process alive past the run."""

    def __init__(self, n: int, fn: Callable[[Outcome], None]):
        self.q: "queue.Queue[Optional[Outcome]]" = queue.Queue()
        self.fn = fn
        self.threads = [threading.Thread(target=self._loop, daemon=True,
                                         name=f"bench-client-{i}")
                        for i in range(n)]
        for t in self.threads:
            t.start()

    def _loop(self):
        while True:
            o = self.q.get()
            if o is None:
                return
            self.fn(o)

    def stop(self):
        for _ in self.threads:
            self.q.put(None)


Finish = Optional[Callable[[Outcome], None]]


def _serve_into(serve: Callable[[int], Tuple[Any, Any]], o: Outcome,
                t0: float, done_count: "threading.Semaphore",
                finish: Finish = None) -> None:
    """Serve one request; ``finish`` then sees the outcome, outside its
    latency."""
    o.sent = time.perf_counter() - t0
    try:
        o.report, o.answer = serve(o.variant)
        o.done = time.perf_counter() - t0
        o.ok = True
        if finish is not None:
            finish(o)
    except Exception as exc:          # noqa: BLE001 — a failed request
        o.ok = False
        o.error = f"{type(exc).__name__}: {exc}"[:500]
    finally:
        done_count.release()


def _wait_all(outcomes: List[Outcome], released: "threading.Semaphore",
              t0: float, deadline: float) -> None:
    for _ in outcomes:
        left = t0 + deadline - time.perf_counter()
        if left <= 0 or not released.acquire(timeout=left):
            return


def run_open(serve: Callable[[int], Tuple[Any, Any]],
             schedule: List[Tuple[float, int]], seconds: float,
             grace_s: float, threads: int,
             at_start: Optional[Callable[[float], None]] = None,
             finish: Finish = None) -> Window:
    """Send each scheduled request onto a thread at its due time."""
    outcomes = [Outcome(variant=v, due=d) for d, v in schedule]
    released = threading.Semaphore(0)
    t0 = time.perf_counter()
    pool = _Threads(threads,
                    lambda o: _serve_into(serve, o, t0, released, finish))
    if at_start is not None:
        at_start(t0)
    for o in outcomes:
        wait = t0 + o.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        o.queued = time.perf_counter() - t0
        pool.q.put(o)
    deadline = seconds + grace_s
    _wait_all(outcomes, released, t0, deadline)
    pool.stop()
    return Window(outcomes, seconds, deadline, "open")


def run_closed(serve: Callable[[int], Tuple[Any, Any]],
               sequences: List[List[int]], seconds: float, grace_s: float,
               at_start: Optional[Callable[[float], None]] = None,
               finish: Finish = None) -> Window:
    """Each client sends its next request once its previous one is done,
    until the window closes."""
    outcomes: List[Outcome] = []
    lock = threading.Lock()
    t0 = time.perf_counter()

    def client(seq):
        for v in seq:
            now = time.perf_counter() - t0
            if now >= seconds:
                return
            o = Outcome(variant=v, due=now, queued=now)
            with lock:
                outcomes.append(o)
            _serve_into(serve, o, t0, threading.Semaphore(0), finish)
            if not o.ok:
                return

    threads = [threading.Thread(target=client, args=(s,), daemon=True,
                                name=f"bench-client-{i}")
               for i, s in enumerate(sequences)]
    if at_start is not None:
        at_start(t0)
    for t in threads:
        t.start()
    deadline = seconds + grace_s
    for t in threads:
        t.join(max(0.0, t0 + deadline - time.perf_counter()))
    with lock:
        outs = list(outcomes)
    return Window(outs, seconds, deadline, "closed")


def drive(traffic: Dict[str, Any], serve: Callable[[int], Tuple[Any, Any]],
          seconds: float, rng: np.random.Generator,
          at_start: Optional[Callable[[float], None]] = None,
          finish: Finish = None) -> Window:
    """Run one window of the traffic mix, with the collector paused (a
    collection inside the window would put its pause into the tail)."""
    gc.collect()
    gc.disable()
    try:
        if traffic["loop"] == "open":
            sched = open_schedule(float(traffic["rate_per_s"]), seconds,
                                  int(traffic["variants"]), rng)
            return run_open(serve, sched, seconds, GRACE_S, THREADS,
                            at_start, finish)
        if traffic["loop"] == "closed":
            seqs = closed_sequences(int(traffic["clients"]),
                                    int(traffic["variants"]), rng)
            return run_closed(serve, seqs, seconds, GRACE_S, at_start,
                              finish)
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    finally:
        gc.enable()
