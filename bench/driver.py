"""The open-loop feeder that the measured window drives.

It feeds ``repro.serving.Engine`` from :func:`bench.traffic.arrivals`:

* before each ``Engine.step()`` it moves every request whose due time has
  passed into a FCFS queue (a backlog, all due at once, only as deep as a
  batch), and offers the queue's head to ``Engine.admit``
  while a slot is free.  A head refused with ``OutOfPages`` stays at the
  head; what ``take_preempted()`` returns goes back to the front;
* with nothing live and nothing due it sleeps until the next due time;
* it stamps every output token with the host clock when the ``admit`` or
  ``step`` call that produced it returns.  Both calls block on the logits,
  so a stamp is a completion time.

It keeps no policy beyond FCFS feeding.  Each call into the engine sits in a
``jax.profiler.TraceAnnotation`` span (``bench.admit``, ``bench.step``,
``bench.feed``, ``bench.sleep``) so that a trace can say what the host was
doing in each gap of the device.  Sampling runs inside ``Engine.step``.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Deque, Dict, Iterator, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving import Engine, Request
from repro.serving.paged_cache import OutOfPages

from traffic import Arrival


@dataclasses.dataclass
class Tracked:
    req: Request
    due: float  # host clock
    admit_start: Optional[float] = None  # first offer to admit
    times: List[float] = dataclasses.field(default_factory=list)  # per token
    failed: bool = False
    finished_at: Optional[float] = None


@dataclasses.dataclass
class Iteration:
    """One call into the engine."""

    kind: str  # "admit" or "step"
    start: float
    end: float
    ctx_lens: List[int]  # admit: [context length]; step: per live row
    free_pages: int  # after the call


class Driver:
    def __init__(self, engine: Engine, source: Iterator[Arrival],
                 clock: Callable[[], float] = time.perf_counter,
                 max_queue: Optional[int] = None):
        self.engine = engine
        self.clock = clock
        # an endless backlog is drawn only as deep as admission can reach
        self.max_queue = max_queue
        self._source = source
        self._next: Optional[Arrival] = next(source)
        self.origin: Optional[float] = None  # host time of due time 0
        self.queue: Deque[Tracked] = collections.deque()
        self.live: Dict[int, Tracked] = {}
        self.tracked: List[Tracked] = []
        self.iters: List[Iteration] = []
        self.steps = 0

    # -- the loop -----------------------------------------------------------
    def start(self) -> None:
        self.origin = self.clock()

    def _pull_due(self, now: float) -> None:
        while (self._next is not None and self.origin + self._next.due_s <= now
               and (self.max_queue is None or len(self.queue) < self.max_queue)):
            a = self._next
            req = Request(rid=a.rid, prompt=a.prompt, max_new_tokens=a.max_new_tokens)
            t = Tracked(req, self.origin + a.due_s)
            self.queue.append(t)
            self.tracked.append(t)
            self._next = next(self._source)

    def _stamp(self, now: float) -> None:
        done = []
        for rid, t in self.live.items():
            while len(t.times) < len(t.req.out_tokens):
                t.times.append(now)
            if t.req.finished_s:
                t.finished_at = now
                done.append(rid)
        for rid in done:
            del self.live[rid]

    def _record(self, kind, start, end, ctx_lens):
        self.iters.append(Iteration(kind, start, end, ctx_lens,
                                    self.engine.pool.free_pages))

    def _admit_due(self) -> None:
        eng = self.engine
        while self.queue and eng.has_free_slot():
            t = self.queue[0]
            start = self.clock()
            if t.admit_start is None:
                t.admit_start = start
            ctx = len(t.req.prompt) + len(t.req.out_tokens)
            try:
                with TraceAnnotation("bench.admit"):
                    eng.admit(t.req)
            except OutOfPages:
                return  # stays at the head until pages free up
            except ValueError:
                self.queue.popleft()
                t.failed = True
                continue
            self.queue.popleft()
            self.live[t.req.rid] = t
            end = self.clock()
            self._stamp(end)
            self._record("admit", start, end, [ctx])

    def iterate(self) -> None:
        """Feed what is due, then take one decode step (or sleep)."""
        eng = self.engine
        with TraceAnnotation("bench.feed"):
            self._pull_due(self.clock())
        self._admit_due()
        if eng.num_live:
            ctx = [int(p) + 1 for p in eng.slot_pos if p >= 0]
            start = self.clock()
            with TraceAnnotation("bench.step"):
                eng.step()
            end = self.clock()
            with TraceAnnotation("bench.feed"):
                self._stamp(end)
                back = eng.take_preempted()
                for req in reversed(back):
                    t = self.live.pop(req.rid)
                    self.queue.appendleft(t)
            self.steps += 1
            self._record("step", start, end, ctx)
        elif not self.queue and self._next is not None:
            wait = self.origin + self._next.due_s - self.clock()
            if wait > 0:
                with TraceAnnotation("bench.sleep"):
                    time.sleep(wait)

    def stop_arrivals(self) -> None:
        """Drop the queue and draw no more requests; live ones run on."""
        self.queue.clear()
        self._next = None

    def run_until(self, done: Callable[[], bool]) -> None:
        while not done():
            self.iterate()


def in_window(times: List[float], w0: float, w1: float) -> int:
    return sum(1 for x in times if w0 <= x <= w1)


def itl_gaps(tracked: List[Tracked], w0: float, w1: float) -> np.ndarray:
    """Every gap between consecutive output tokens of one request whose
    later token arrived inside ``[w0, w1]``."""
    out = []
    for t in tracked:
        for a, b in zip(t.times, t.times[1:]):
            if w0 <= b <= w1:
                out.append(b - a)
    return np.asarray(out, np.float64)
