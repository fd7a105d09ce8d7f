"""The clients: one asyncio task per request, each submitted when it is due
(open loop) and timed from the client's side of its stream.

A traffic generator that holds conversations gives ``next_turn(params,
spec, served)``: once a request has finished, it returns the session's
next request (its ``prompt`` may hold the served tokens) with
``think_s``, the pause before it is sent, or None when the session ends.
The same client then sends it, as a record of its own, if it falls due
inside the window."""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import List, Optional

from repro.serve.service import AdmissionRejected


@dataclasses.dataclass
class Record:
    spec: dict
    due_t: float = 0.0          # perf_counter when it was due
    submit_t: float = 0.0       # perf_counter when submit() was called
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish: Optional[str] = None
    error: Optional[str] = None
    request: object = None      # the program's Request (admission stamps)

    @property
    def queue_wait_s(self) -> Optional[float]:
        r = self.request
        if r is None or not r.admit_t:
            return None
        return r.admit_t - r.submit_t


async def _client(svc, rec: Record) -> None:
    delay = rec.due_t - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    rec.submit_t = time.perf_counter()
    s = rec.spec
    try:
        stream = await svc.submit(s["prompt"], max_tokens=s["max_tokens"],
                                  temperature=s["temperature"],
                                  seed=s["seed"])
    except AdmissionRejected as e:
        rec.error = f"rejected: {e.reason}"
        return
    rec.request = stream.request
    try:
        async for tok in stream:
            rec.times.append(time.perf_counter())
            rec.tokens.append(tok)
        rec.finish = stream.completion.finish_reason
    except asyncio.CancelledError:
        raise
    except Exception as e:                       # the service failed
        rec.error = repr(e)


async def _session(svc, rec: Record, recs: list, t0: float, seconds: float,
                   next_turn) -> None:
    while True:
        await _client(svc, rec)
        if next_turn is None or rec.finish is None:
            return
        nxt = next_turn(rec.spec, rec.tokens)
        if nxt is None:
            return
        due = time.perf_counter() - t0 + nxt["think_s"]
        if due >= seconds:
            return
        rec = Record(spec={**nxt, "due": due}, due_t=t0 + due)
        recs.append(rec)


async def window(svc, specs: list, seconds: float, after: str,
                 finish_s: float, extra=None, next_turn=None):
    """Offer ``specs`` over a window of ``seconds``; returns
    ``(t0, t_end, records)``.  ``after`` is ``"first_token"`` (every
    request due in the window gets up to ``finish_s`` more seconds to
    deliver its first token, then whatever is unfinished is cancelled) or
    ``"cancel"`` (whatever is unfinished at the close is cancelled).
    ``extra(t0, records)`` is a coroutine run beside the clients (the
    tracer); ``next_turn(spec, served)`` continues a session."""
    t0 = time.perf_counter() + 0.05
    recs = [Record(spec=s, due_t=t0 + s["due"]) for s in specs]
    tasks = [asyncio.create_task(_session(svc, r, recs, t0, seconds,
                                          next_turn)) for r in list(recs)]
    side = asyncio.create_task(extra(t0, recs)) if extra is not None else None
    await asyncio.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    t_end = time.perf_counter()
    if after == "first_token":
        while time.perf_counter() < t_end + finish_s and any(
                not (r.times or r.finish or r.error) for r in recs):
            await asyncio.sleep(0.05)
    elif after != "cancel":
        raise ValueError(f"unknown after_window {after!r}")
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    if side is not None:
        await side
    return t0, t_end, recs
