"""The clients: they drive ``serve.frontend.OpenLoopServer`` and stamp every
token as it reaches them.

Open loop: each request is due at a fixed time and is sent then, whatever the
server is doing; a late send is recorded as lateness and its latency still
counts from the due time.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import List, Optional

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Served:
    """One request as its client saw it (``time.perf_counter`` seconds)."""
    index: int
    length: int
    horizon: int
    due: float
    offset: int = 0                 # where the prompt starts in the signal
    submit: Optional[float] = None
    recv: List[float] = dataclasses.field(default_factory=list)
    ys: Optional[list] = None       # the served tokens, when kept for the check
    shed: bool = False

    @property
    def done(self) -> bool:
        return len(self.recv) >= self.horizon and not self.shed


class Annotated:
    """The engine as the server sees it, with each call the server makes into
    it wrapped in a host span of the profiler's trace."""

    _SPANS = ("submit", "flush", "decode_closed_loop", "collect_decoded",
              "release")

    def __init__(self, engine):
        object.__setattr__(self, "_engine", engine)
        for name in self._SPANS:
            object.__setattr__(self, name, self._span(name))

    def _span(self, name):
        fn = getattr(self._engine, name)
        label = f"engine.{name}"

        def call(*args, **kwargs):
            with TraceAnnotation(label):
                return fn(*args, **kwargs)
        return call

    def __getattr__(self, name):
        return getattr(self._engine, name)


async def _consume(rec: Served, handle) -> None:
    async for tok in handle:
        rec.recv.append(time.perf_counter())
        if rec.ys is not None:
            rec.ys.append(tok.y)


async def _send(server, rec: Served, prompt, tasks: list) -> None:
    from repro.serve.frontend import AdmissionFull
    rec.submit = time.perf_counter()
    try:
        handle = await server.submit(rec.index, prompt, n_decode=rec.horizon)
    except AdmissionFull:
        rec.shed = True
        return
    tasks.append(asyncio.ensure_future(_consume(rec, handle)))


async def open_loop(server, requests, prompt_of, *, t_zero: float,
                    window, grace: float, keep) -> List[Served]:
    """Send each request at ``t_zero + due``; return the records of those
    due inside ``window`` (``t0, t_end``) once each has finished or ``grace``
    seconds past the window have run out.  Requests due later keep the load
    on meanwhile.  ``keep``: indices whose served tokens are kept."""
    t0, t_end = window
    tasks: list = []
    measured: List[Served] = []

    def settled():
        return all(r.done or r.shed for r in measured)

    for req in requests:
        due = t_zero + req.due
        now = time.perf_counter()
        if due >= t_end and (settled() or now > t_end + grace):
            break
        if due > now:
            await asyncio.sleep(due - now)
        rec = Served(req.index, req.length, req.horizon, due, req.offset,
                     ys=[] if req.index in keep else None)
        if t0 <= due < t_end:
            measured.append(rec)
        await _send(server, rec, prompt_of(req), tasks)
    while not settled() and time.perf_counter() < t_end + grace:
        await asyncio.sleep(0.005)
    await server.abort()
    await asyncio.gather(*tasks)
    return measured
