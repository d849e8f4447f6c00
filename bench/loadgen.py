"""Client side of a run: the sessions of a plan, sent over HTTP with SSE.

Started from the program's ``repro.launch.loadgen`` client and changed
where it measured the wrong thing: a request is timed from when it was
*due*, not from when it was sent, so a stall of the client or of the
server shows in every request due during it; every token's arrival time
is kept, so the gaps between tokens can be read; and a request that got
no first token by the end of the window stays in the tail with its wait
so far.

Everything runs on one asyncio loop of this process (a chip belongs to
one process).
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np


@dataclass
class Record:
    """One request, as the client saw it.  Times are ``time.monotonic``."""

    session: int
    turn: int
    due: float
    prompt: list
    max_new: int
    sent: Optional[float] = None
    times: list = field(default_factory=list)   # arrival of each token
    tokens: list = field(default_factory=list)
    status: int = 0
    finish: Optional[str] = None
    error: Optional[str] = None
    ended: Optional[float] = None


async def sse_request(host: str, port: int, rec: Record,
                      timeout_s: float = 600.0) -> None:
    """POST ``rec``'s prompt to ``/v1/generate`` and consume the SSE
    stream into ``rec``.  Never raises for a protocol failure: ``status``
    0 is a failed connect, ``error`` says what happened."""
    body = json.dumps({"prompt": rec.prompt, "max_new_tokens": rec.max_new,
                       "temperature": 0.0}).encode()
    rec.sent = time.monotonic()
    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(host, port), timeout_s)
    except (OSError, asyncio.TimeoutError) as exc:
        rec.error = f"connect: {exc!r}"
        rec.ended = time.monotonic()
        return
    try:
        writer.write(
            f"POST /v1/generate HTTP/1.1\r\nHost: {host}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n"
            f"Connection: close\r\n\r\n".encode() + body)
        await writer.drain()
        line = await asyncio.wait_for(reader.readline(), timeout_s)
        rec.status = int(line.split()[1])
        while True:  # headers
            h = await asyncio.wait_for(reader.readline(), timeout_s)
            if h in (b"\r\n", b"\n", b""):
                break
        if rec.status != 200:
            rec.error = (await reader.read()).decode("utf-8", "replace")
            return
        event = "message"
        while True:
            line = await asyncio.wait_for(reader.readline(), timeout_s)
            if not line:
                rec.error = "stream closed early"
                return
            line = line.strip()
            if not line:
                event = "message"
            elif line.startswith(b"event:"):
                event = line.split(b":", 1)[1].strip().decode()
            elif line.startswith(b"data:"):
                data = json.loads(line.split(b":", 1)[1])
                if event == "done":
                    rec.finish = data["finish_reason"]
                    rec.ended = time.monotonic()
                    return
                rec.times.append(time.monotonic())
                rec.tokens.append(int(data["token"]))
    except (OSError, ValueError, IndexError, asyncio.TimeoutError,
            asyncio.IncompleteReadError) as exc:
        rec.error = repr(exc)
    finally:
        if rec.ended is None and rec.error is not None:
            rec.ended = time.monotonic()
        writer.close()


async def _until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)


async def _session(host, port, plan, session, due: float, end: float,
                   records: list, on_send=None) -> float:
    """Run one session's turns from ``due``; returns when its last turn
    ended (or the window closed)."""
    history = (plan.system_prompts[session.system].tolist()
               if session.system >= 0 else [])
    for k, turn in enumerate(session.turns):
        if k:
            due += turn.think_s
        if due >= end:
            break
        prompt = history + turn.user.tolist()
        rec = Record(session=session.index, turn=k, due=due, prompt=prompt,
                     max_new=turn.answer)
        records.append(rec)
        await _until(due)
        if on_send is not None:
            on_send(rec)
        await sse_request(host, port, rec)
        if rec.finish not in ("length", "stop"):
            break
        history = prompt + rec.tokens
        due = rec.ended
    return due


async def drive(host: str, port: int, plan, t0: float, seconds: float,
                on_send=None) -> list:
    """Send the plan's sessions from ``t0`` for ``seconds`` and return
    the records of every request made due in the window.  At the close
    every request still open is dropped (the server cancels it)."""
    end = t0 + seconds
    records: list = []
    tasks = [asyncio.ensure_future(_session(
        host, port, plan, s, t0 + s.arrival, end, records, on_send))
        for s in plan.sessions]
    await _until(end)
    for t in tasks:
        t.cancel()
    for res in await asyncio.gather(*tasks, return_exceptions=True):
        if not isinstance(res, (float, type(None), asyncio.CancelledError)):
            raise res
    return records


# -- reductions ---------------------------------------------------------------


def ttfts(records: list, end: float) -> np.ndarray:
    """Seconds from due to first token of every request due before
    ``end``; one with no first token by ``end`` (or failed) enters with
    its wait until ``end``."""
    out = []
    for r in records:
        if r.due >= end:
            continue
        first = r.times[0] if r.times and r.times[0] <= end else end
        out.append(first - r.due)
    return np.asarray(out)


def token_gaps(records: list, end: float) -> np.ndarray:
    """Every gap between consecutive tokens of a request whose later
    token arrived by ``end``."""
    gaps = [np.diff([t for t in r.times if t <= end]) for r in records]
    return np.concatenate(gaps) if gaps else np.zeros(0)


def tokens_in(records: list, start: float, end: float) -> int:
    """Tokens delivered to clients within ``[start, end]``."""
    return sum(sum(start <= t <= end for t in r.times) for r in records)


def lateness(records: list, end: float) -> np.ndarray:
    """Seconds by which each send ran behind its due time."""
    return np.asarray([r.sent - r.due for r in records
                       if r.due < end and r.sent is not None])


def failed(records: list, end: float) -> int:
    """Requests due before ``end`` that failed: refused, broken off, or
    finished by the server as cancelled.  Requests the client dropped at
    the close are not failures."""
    return sum(1 for r in records if r.due < end and (
        r.error is not None or r.status not in (0, 200)
        or r.finish == "cancelled"))
