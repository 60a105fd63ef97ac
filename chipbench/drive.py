"""Open- and closed-loop drivers that stamp every request on the host clock.

Each request gets a :class:`Record`: when it was due, when the driver
actually submitted it, when its answer was delivered back to the caller,
and what came back.  Latency is taken from the due time, so a generator
that falls behind adds its lag to the latency instead of hiding queueing.
"""

from __future__ import annotations

import asyncio
import dataclasses
import math
import time
from typing import Optional

from repro.index.serve import Rejected, Request

now = time.monotonic


@dataclasses.dataclass
class Record:
    query: object                   # traffic.Query
    t_due: float
    t_submit: Optional[float] = None
    t_done: Optional[float] = None
    result: object = None
    error: Optional[str] = None     # "rejected: ...", "failed: ...", None

    @property
    def served(self) -> bool:
        return self.t_done is not None and self.error is None


def _submit(server, rec: Record, done: Optional[asyncio.Event] = None):
    q = rec.query
    rec.t_submit = now()
    fut = server.submit_nowait(Request(list(q.terms), mode=q.mode, k=q.k))

    def finish(f):
        rec.t_done = now()
        exc = f.exception()
        if exc is not None:
            rec.error = f"failed: {exc!r}"
        elif isinstance(f.result(), Rejected):
            rec.error = f"rejected: {f.result()}"
        else:
            rec.result = f.result()
        if done is not None:
            done.set()

    fut.add_done_callback(finish)
    return fut


async def open_loop(server, queries: list, offsets, t0: float) -> tuple:
    """Submit ``queries[i]`` when due, at ``t0 + offsets[i]``, whatever is
    still outstanding.  Returns ``(records, futures)`` once the last one is
    submitted."""
    recs, futs = [], []
    for q, off in zip(queries, offsets):
        rec = Record(q, t0 + float(off))
        delay = rec.t_due - now()
        if delay > 0:
            await asyncio.sleep(delay)
        futs.append(_submit(server, rec))
        recs.append(rec)
    return recs, futs


async def closed_loop(server, queries, clients: int, t0: float,
                      seconds: float, max_requests: float = math.inf) -> tuple:
    """``clients`` callers take the next query from the iterator
    ``queries`` in order; each sends when its previous answer arrives (its
    due time), until ``t0 + seconds`` or until ``max_requests`` were sent.
    Returns ``(records, futures)``."""
    recs, futs = [], []
    t_end = t0 + seconds

    async def client():
        t_due = t0
        while t_due < t_end and len(recs) < max_requests:
            rec = Record(next(queries), t_due)
            if rec.t_due > now():
                await asyncio.sleep(rec.t_due - now())
            fut = _submit(server, rec)
            recs.append(rec)
            futs.append(fut)
            await asyncio.wait([fut])
            t_due = rec.t_done

    await asyncio.gather(*(client() for _ in range(clients)))
    return recs, futs


async def settle(futs: list, timeout: float) -> int:
    """Wait up to ``timeout`` seconds for every future; returns how many
    never resolved."""
    if not futs:
        return 0
    _, pending = await asyncio.wait(futs, timeout=timeout)
    return len(pending)
