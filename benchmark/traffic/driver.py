"""Load driver: a closed loop (``depth`` ops outstanding, each slot
submits its next op when the last one completes) or an open loop (ops
released at seeded due times, whatever is outstanding).

Adapted from the program's ``workload/generator.py`` ``LoadGenerator``
with two repairs: an op's latency runs from its own submit (closed) or
from when it was due (open), so a stalled generator shows in the
latency and not in a separate drift figure; and ops on one key never
overlap in time, so the model knows what every read must return.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from dataclasses import dataclass

from .client import LOST, Client, Result
from .mix import Mix, Op


@dataclass
class Record:
    kind: str
    oid: str
    status: str
    nbytes: int
    t_due: float        # monotonic s: submit (closed) or due (open)
    t_sub: float
    t_done: float | None
    detail: str = ""

    @property
    def latency(self) -> float | None:
        return None if self.t_done is None else self.t_done - self.t_due


class _KeyLocks:
    def __init__(self):
        self._cv = threading.Condition()
        self._busy: set = set()

    def acquire(self, key):
        with self._cv:
            while key in self._busy:
                self._cv.wait()
            self._busy.add(key)

    def release(self, key):
        with self._cv:
            self._busy.discard(key)
            self._cv.notify_all()


class Driver:
    def __init__(self, client: Client, mix: Mix, *, loop: str = "closed",
                 depth: int = 16, rate: float | None = None,
                 arrivals: str = "poisson"):
        if loop not in ("closed", "open"):
            raise ValueError(f"unknown loop {loop!r}")
        if loop == "open" and not rate:
            raise ValueError("an open loop needs a rate")
        self.client = client
        self.mix = mix
        self.loop = loop
        self.depth = int(depth)
        self.rate = rate
        self.arrivals = arrivals
        self._keys = _KeyLocks()
        self._lock = threading.Lock()

    def _one(self, op: Op, t_due: float, deadline: float) -> Record:
        self._keys.acquire(op.key)
        try:
            t_sub = time.monotonic()
            if self.loop == "closed":
                t_due = t_sub
            try:
                comp, data = self.client.submit(op)
                res = self.client.finish(op, comp, data, deadline)
            except Exception as e:   # noqa: BLE001 — recorded as a
                res = Result("error", detail=repr(e))   # failed op
        finally:
            self._keys.release(op.key)
        t_done = None if res.status == LOST else time.monotonic()
        return Record(op.kind, op.oid, res.status, res.nbytes, t_due,
                      t_sub, t_done, res.detail)

    def _threads(self, target, n: int):
        ts = [threading.Thread(target=target, name=f"bench-slot-{i}",
                               daemon=True) for i in range(n)]
        for t in ts:
            t.start()
        return ts

    def run_ops(self, ops: list[Op], timeout: float) -> list[Record]:
        """Every op of a fixed list, `depth` at a time (set-up)."""
        it = iter(ops)
        out: list[Record] = []
        deadline = time.monotonic() + timeout

        def slot():
            while True:
                with self._lock:
                    op = next(it, None)
                if op is None:
                    return
                rec = self._one(op, 0.0, deadline)
                with self._lock:
                    out.append(rec)

        for t in self._threads(slot, self.depth):
            t.join()
        return out

    def run_window(self, first: int, seconds: float, grace: float,
                   t0: float | None = None
                   ) -> tuple[list[Record], float, float]:
        """Ops first, first+1, ... for `seconds` from `t0` (default:
        now); ops still out at the close are waited for up to `grace`
        s more.  → (records, window start, window end), monotonic s."""
        out: list[Record] = []
        idx = itertools.count(first)
        t0 = time.monotonic() if t0 is None else t0
        t_end = t0 + seconds
        deadline = t_end + grace

        def record(rec):
            with self._lock:
                out.append(rec)

        if self.loop == "closed":
            def slot():
                while True:
                    with self._lock:
                        if time.monotonic() >= t_end:
                            return
                        op = self.mix.op(next(idx))
                    record(self._one(op, 0.0, deadline))
            threads = self._threads(slot, self.depth)
        else:
            n = int(self.rate * seconds * 2) + 16
            due = self.mix.arrivals(self.rate, self.arrivals, n)
            q: queue.Queue = queue.Queue()

            def worker():
                while True:
                    item = q.get()
                    if item is None:
                        return
                    record(self._one(*item, deadline))

            threads = self._threads(worker, self.depth)
            for d in due:
                t_due = t0 + float(d)
                if t_due >= t_end:
                    break
                delay = t_due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                q.put((self.mix.op(next(idx)), t_due))
            for _ in threads:
                q.put(None)
        for t in threads:
            t.join()
        return out, t0, t_end
