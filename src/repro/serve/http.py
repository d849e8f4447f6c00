"""Async HTTP front door for :class:`~repro.serve.engine.ContinuousEngine`.

Pure stdlib (asyncio + json): the serving container needs no web
framework.  One event loop owns three things:

* **The pump** — a background task that runs the engine's blocking
  ``step()`` in the default thread-pool executor (the loop stays
  responsive while the device computes) and routes the step's
  ``(uid, token)`` events and :class:`Completion`s to per-request asyncio
  queues.  ``ContinuousEngine.submit`` / ``cancel`` are thread-safe
  against a concurrently running ``step()``, which is exactly the
  property this split leans on.
* **The HTTP server** — ``asyncio.start_server`` with a minimal
  HTTP/1.1 parser (request line, headers, ``Content-Length`` body; every
  response closes the connection).  Endpoints:

  - ``POST /v1/generate`` — body ``{"prompt": [ids...],
    "max_new_tokens": N, "temperature": T, "stop_ids": [...],
    "priority": P, "timeout_s": S, "stream": true|false}``.
    ``priority`` (0 = most urgent, default 1) and ``timeout_s`` ride
    into the engine, so admission is priority-class aware and a request
    still QUEUED past its deadline is dropped engine-side (the route
    deadline below covers it once running).  Streams tokens as
    Server-Sent Events (``data: {"id": uid, "token": t}`` per token,
    then ``event: done`` with the finish reason and counts), or — with
    ``"stream": false`` — returns one JSON object after the request
    finishes.  A full admission queue (``max_pending``) answers **429**
    with ``Retry-After`` before touching the engine: backpressure, not
    unbounded buffering.
  - ``GET /metrics`` — Prometheus text exposition of the server counters
    plus the engine's ``kv_stats()`` / ``prefill_stats()`` /
    ``spec_stats()`` (TTFT/latency quantiles, prefix-hit rate, blocks in
    use — see :class:`ServeMetrics`).
  - ``GET /healthz`` — liveness + a small JSON status.

* **Cancellation** — the server is the reason
  :meth:`ContinuousEngine.cancel` exists.  A client that disconnects
  mid-stream (detected by a concurrent read on the socket) and a request
  that overruns its deadline (``timeout_s``, default
  ``default_timeout_s``) are both cancelled *into* the engine, which
  releases the slot, parked write frontier, and refcounted paged blocks
  and returns a ``finish_reason="cancelled"`` completion through the
  normal path.  Deadline expiry is fired by the pump between steps, so an
  expired request is reported ``cancelled`` even if its token budget
  would have ended it the same step.

:class:`BackgroundServer` wraps the whole thing in a context manager
running the event loop on a daemon thread, for synchronous callers
(benchmarks, tests)::

    with BackgroundServer(engine, max_pending=32) as bg:
        r = requests_like_client(bg.host, bg.port)  # e.g. launch.loadgen

``repro.launch.serve --http`` boots the blocking variant (:func:`serve`),
and ``repro.launch.loadgen`` is the matching closed-/open-loop client.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from collections import deque
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation


def _quantile(values, q: float) -> float:
    vals = list(values)
    return float(np.percentile(np.asarray(vals), q * 100)) if vals else 0.0


class ServeMetrics:
    """Server-side counters + latency reservoirs, rendered as Prometheus
    text exposition (the ``repro_serve_*`` family).

    TTFT/latency are bounded reservoirs (last ``maxlen`` completions), so
    the quantiles are over recent traffic and a long-lived server never
    grows.  Completions cancelled before their first token carry no TTFT
    sample (``first_token_at == 0``), and cancelled completions land in
    their OWN latency reservoir (``repro_serve_cancelled_latency_seconds``)
    — a storm of instantly-cancelled requests must not drag the served
    p50/p95 down.  TTFT is additionally bucketed per priority class."""

    def __init__(self, maxlen: int = 4096):
        self.maxlen = maxlen
        self.http_requests: dict = {}   # (route, code) -> count
        self.completions: dict = {}     # finish_reason -> count
        self.tokens_streamed = 0
        self.rejected_backpressure = 0
        self.cancelled = {"disconnect": 0, "deadline": 0}
        self.ttft_s: deque = deque(maxlen=maxlen)
        self.ttft_by_priority: dict = {}  # priority -> deque of ttfts
        self.latency_s: deque = deque(maxlen=maxlen)          # served only
        self.cancelled_latency_s: deque = deque(maxlen=maxlen)

    def count_request(self, route: str, code: int) -> None:
        key = (route, code)
        self.http_requests[key] = self.http_requests.get(key, 0) + 1

    def observe(self, completion) -> None:
        r = completion.finish_reason
        self.completions[r] = self.completions.get(r, 0) + 1
        if completion.first_token_at > 0:
            self.ttft_s.append(completion.ttft)
            prio = getattr(completion, "priority", 1)
            self.ttft_by_priority.setdefault(
                prio, deque(maxlen=self.maxlen)).append(completion.ttft)
        if r == "cancelled":
            self.cancelled_latency_s.append(completion.latency)
        else:
            self.latency_s.append(completion.latency)

    def render(self, engine) -> str:
        """Prometheus text format; merges the engine's own stats so one
        scrape covers the whole serving stack."""
        lines = []

        def metric(name, value, help_=None, type_="gauge", labels=""):
            if help_ is not None:
                lines.append(f"# HELP {name} {help_}")
                lines.append(f"# TYPE {name} {type_}")
            lines.append(f"{name}{labels} {value}")

        for (route, code), n in sorted(self.http_requests.items()):
            lines.append(
                f'repro_serve_http_requests_total'
                f'{{route="{route}",code="{code}"}} {n}')
        for reason, n in sorted(self.completions.items()):
            lines.append(
                f'repro_serve_completions_total{{reason="{reason}"}} {n}')
        for cause, n in sorted(self.cancelled.items()):
            lines.append(
                f'repro_serve_cancelled_total{{cause="{cause}"}} {n}')
        metric("repro_serve_tokens_streamed_total", self.tokens_streamed,
               "Tokens written to SSE streams", "counter")
        metric("repro_serve_rejected_backpressure_total",
               self.rejected_backpressure,
               "Requests answered 429 by the bounded admission queue",
               "counter")
        for q in (0.5, 0.95):
            metric("repro_serve_ttft_seconds", _quantile(self.ttft_s, q),
                   labels=f'{{quantile="{q}"}}')
            metric("repro_serve_latency_seconds",
                   _quantile(self.latency_s, q),
                   labels=f'{{quantile="{q}"}}')
            metric("repro_serve_cancelled_latency_seconds",
                   _quantile(self.cancelled_latency_s, q),
                   labels=f'{{quantile="{q}"}}')
        for prio in sorted(self.ttft_by_priority):
            for q in (0.5, 0.95):
                metric("repro_serve_ttft_seconds",
                       _quantile(self.ttft_by_priority[prio], q),
                       labels=f'{{quantile="{q}",priority="{prio}"}}')

        pe = engine.preempt_stats()
        metric("repro_serve_preemptions_total", pe["preemptions"],
               "Running decodes preempted for a higher-priority admission",
               "counter")
        metric("repro_serve_preempt_resumes_total", pe["resumes"],
               "Preempted requests whose resume re-bound", "counter")
        metric("repro_serve_preempt_violations_total",
               pe["preempt_violations"],
               "Preemptions whose victim did not outrank the preemptor "
               "(must be 0)", "counter")

        sched = engine.scheduler
        metric("repro_serve_queue_pending", sched.n_pending,
               "Requests waiting for a slot")
        metric("repro_serve_slots_running", sched.n_running)
        metric("repro_serve_slots_prefilling", sched.n_prefilling)

        kv = engine.kv_stats()
        metric("repro_serve_kv_allocated_bytes", kv["kv_allocated_bytes"])
        metric("repro_serve_kv_peak_resident_bytes",
               kv["kv_peak_resident_bytes"])
        if "blocks_in_use" in kv:
            metric("repro_serve_kv_blocks_in_use", kv["blocks_in_use"],
                   "Paged KV blocks referenced by live requests")
            metric("repro_serve_kv_blocks_peak", kv["peak_blocks_in_use"])
            metric("repro_serve_kv_blocks_total", kv["n_blocks"])
        if "draft_kv_allocated_bytes" in kv:
            metric("repro_serve_draft_kv_allocated_bytes",
                   kv["draft_kv_allocated_bytes"])

        pf = engine.prefill_stats()
        metric("repro_serve_prefix_hit_rate", pf["prefix_hit_rate"],
               "Fraction of admitted prompt tokens served from the "
               "prefix cache")
        metric("repro_serve_prefill_tokens_computed_total",
               pf["prefill_tokens_computed"], type_="counter")
        metric("repro_serve_prompt_tokens_admitted_total",
               pf["prompt_tokens_admitted"], type_="counter")
        for name, key, help_ in (
                ("repro_serve_queue_wait_seconds", "queue_wait",
                 "Submit to first slot, first life of each request"),
                ("repro_serve_prefill_wait_seconds", "prefill_wait",
                 "First slot to first token, first life of each request")):
            metric(name, pf[f"{key}_p50_s"], help_,
                   labels='{quantile="0.5"}')
            metric(name, pf[f"{key}_p95_s"], labels='{quantile="0.95"}')
        metric("repro_serve_steps_total", pf["decode_steps"],
               "Engine steps that decoded (kind=decode), and those of them "
               "that also ran a prefill chunk (kind=mixed)", "counter",
               labels='{kind="decode"}')
        metric("repro_serve_steps_total", pf["mixed_steps"],
               labels='{kind="mixed"}')
        metric("repro_serve_programs_compiled_total",
               pf["programs_compiled"],
               "Programs compiled or loaded from the compile cache while "
               "the engine stepped", "counter")

        if engine.spec_k:
            sp = engine.spec_stats()
            metric("repro_serve_spec_acceptance_rate",
                   sp["spec_acceptance_rate"])
        return "\n".join(lines) + "\n"


class _Route:
    """Per-request delivery: a queue the pump feeds, plus the deadline."""

    __slots__ = ("queue", "deadline", "expired")

    def __init__(self, deadline: Optional[float]):
        self.queue: asyncio.Queue = asyncio.Queue()
        self.deadline = deadline
        self.expired = False


class HttpServer:
    """The asyncio server; see the module docstring for the protocol.

    ``port=0`` binds an ephemeral port (read ``self.port`` after
    :meth:`start`).  ``max_pending`` bounds the engine's admission queue:
    a POST arriving with ``scheduler.n_pending >= max_pending`` is
    rejected 429 without submitting.  ``default_timeout_s`` is the
    per-request deadline when the body names none (``None`` disables)."""

    def __init__(self, engine, *, host: str = "127.0.0.1", port: int = 0,
                 max_pending: int = 64,
                 default_timeout_s: Optional[float] = None):
        if max_pending < 1:
            raise ValueError("need max_pending >= 1")
        self.engine = engine
        self.host, self.port = host, port
        self.max_pending = max_pending
        self.default_timeout_s = default_timeout_s
        self.metrics = ServeMetrics()
        self._routes: dict = {}  # uid -> _Route
        self._wake = asyncio.Event()
        self._stopping = False
        self._server = None
        self._pump_task = None

    # -- lifecycle -----------------------------------------------------------

    async def start(self) -> None:
        self._server = await asyncio.start_server(self._handle, self.host,
                                                  self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        self._pump_task = asyncio.ensure_future(self._pump())

    async def stop(self) -> None:
        self._stopping = True
        self._wake.set()
        self._server.close()
        await self._server.wait_closed()
        if self._pump_task is not None:
            await self._pump_task

    # -- the pump ------------------------------------------------------------

    def _fire_deadlines(self) -> None:
        now = time.monotonic()
        for uid, route in list(self._routes.items()):
            if (route.deadline is not None and now >= route.deadline
                    and not route.expired):
                route.expired = True
                self.metrics.cancelled["deadline"] += 1
                self.engine.cancel(uid)

    async def _pump(self) -> None:
        """Drive ``engine.step()`` in the executor while work exists and
        fan its events out to the per-request routes.  Everything that
        mutates the engine beyond thread-safe ``submit``/``cancel``
        happens here, on one task — handlers only enqueue."""
        loop = asyncio.get_running_loop()
        while not self._stopping:
            self._fire_deadlines()
            if self.engine.scheduler.idle:
                self._wake.clear()
                if self.engine.scheduler.idle:  # re-check: lost-wakeup guard
                    try:
                        await asyncio.wait_for(self._wake.wait(),
                                               timeout=0.05)
                    except asyncio.TimeoutError:
                        pass
                    continue
            done = await loop.run_in_executor(None, self.engine.step)
            with TraceAnnotation("serve.fanout"):
                for uid, tok in self.engine.step_events:
                    route = self._routes.get(uid)
                    if route is not None:
                        route.queue.put_nowait(("token", tok))
                for comp in done:
                    self.metrics.observe(comp)
                    route = self._routes.pop(comp.uid, None)
                    if route is not None:
                        route.queue.put_nowait(("done", comp))

    # -- HTTP ----------------------------------------------------------------

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        try:
            line = await reader.readline()
            if not line:
                return
            parts = line.decode("latin1").split()
            if len(parts) < 2:
                return
            method, path = parts[0].upper(), parts[1]
            headers = {}
            while True:
                h = await reader.readline()
                if h in (b"\r\n", b"\n", b""):
                    break
                k, _, v = h.decode("latin1").partition(":")
                headers[k.strip().lower()] = v.strip()
            body = b""
            n = int(headers.get("content-length", "0") or 0)
            if n:
                body = await reader.readexactly(n)

            if method == "POST" and path == "/v1/generate":
                await self._generate(reader, writer, body)
            elif method == "GET" and path == "/metrics":
                self._respond(writer, path, 200,
                              self.metrics.render(self.engine).encode(),
                              ctype="text/plain; version=0.0.4")
            elif method == "GET" and path == "/healthz":
                sched = self.engine.scheduler
                self._respond(writer, path, 200, json.dumps({
                    "status": "ok",
                    "pending": sched.n_pending,
                    "running": sched.n_running,
                    "prefilling": sched.n_prefilling,
                }).encode())
            else:
                self._respond(writer, path, 404,
                              json.dumps({"error": "not found"}).encode())
            await writer.drain()
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass

    def _respond(self, writer, route: str, code: int, body: bytes, *,
                 ctype: str = "application/json",
                 extra_headers: str = "") -> None:
        reason = {200: "OK", 400: "Bad Request", 404: "Not Found",
                  429: "Too Many Requests"}.get(code, "OK")
        writer.write(
            f"HTTP/1.1 {code} {reason}\r\n"
            f"Content-Type: {ctype}\r\n"
            f"Content-Length: {len(body)}\r\n"
            f"{extra_headers}"
            f"Connection: close\r\n\r\n".encode() + body)
        self.metrics.count_request(route, code)

    async def _generate(self, reader, writer, body: bytes) -> None:
        route = "/v1/generate"
        try:
            payload = json.loads(body or b"{}")
            prompt = payload["prompt"]
        except (ValueError, KeyError) as exc:
            self._respond(writer, route, 400,
                          json.dumps({"error": f"bad request: {exc}"}
                                     ).encode())
            return
        # backpressure BEFORE the engine sees the request: the queue is a
        # hard bound, the client owns the retry
        if self.engine.scheduler.n_pending >= self.max_pending:
            self.metrics.rejected_backpressure += 1
            self._respond(writer, route, 429,
                          json.dumps({"error": "admission queue full",
                                      "pending": self.engine.scheduler
                                      .n_pending}).encode(),
                          extra_headers="Retry-After: 1\r\n")
            return
        timeout_s = payload.get("timeout_s", self.default_timeout_s)
        try:
            uid = self.engine.submit(
                np.asarray(prompt, np.int32),
                max_new_tokens=int(payload.get("max_new_tokens", 16)),
                temperature=float(payload.get("temperature", 0.0)),
                stop_ids=tuple(payload.get("stop_ids", ())),
                priority=int(payload.get("priority", 1)),
                # the engine enforces this while the request is QUEUED;
                # the route deadline below covers it once running (and
                # owns non-positive timeouts = already expired, which
                # the engine's Request validation does not admit)
                timeout_s=(float(timeout_s)
                           if timeout_s is not None and float(timeout_s) > 0
                           else None))
        except (ValueError, TypeError) as exc:
            self._respond(writer, route, 400,
                          json.dumps({"error": str(exc)}).encode())
            return
        deadline = (time.monotonic() + float(timeout_s)
                    if timeout_s is not None else None)
        rt = self._routes[uid] = _Route(deadline)
        self._wake.set()
        if payload.get("stream", True):
            await self._stream_sse(reader, writer, uid, rt)
        else:
            await self._respond_json(writer, uid, rt)

    async def _stream_sse(self, reader, writer, uid: int,
                          rt: _Route) -> None:
        route = "/v1/generate"
        writer.write(
            b"HTTP/1.1 200 OK\r\n"
            b"Content-Type: text/event-stream\r\n"
            b"Cache-Control: no-cache\r\n"
            b"Connection: close\r\n\r\n")
        self.metrics.count_request(route, 200)
        # the only bytes a well-behaved client sends after the body is
        # EOF on disconnect — a concurrent read is our disconnect signal
        disc = asyncio.ensure_future(reader.read(1))
        try:
            while True:
                get = asyncio.ensure_future(rt.queue.get())
                await asyncio.wait({get, disc},
                                   return_when=asyncio.FIRST_COMPLETED)
                if not get.done():
                    get.cancel()
                    # client went away: cancel into the engine and stop
                    # streaming; the pump still observes the completion
                    self._routes.pop(uid, None)
                    self.metrics.cancelled["disconnect"] += 1
                    self.engine.cancel(uid)
                    return
                kind, val = get.result()
                if kind == "token":
                    self.metrics.tokens_streamed += 1
                    writer.write(
                        f'data: {{"id": {uid}, "token": {int(val)}}}\n\n'
                        .encode())
                    await writer.drain()
                else:  # done
                    comp = val
                    writer.write(
                        b"event: done\ndata: " + json.dumps({
                            "id": uid,
                            "finish_reason": comp.finish_reason,
                            "n_tokens": len(comp.tokens),
                            "prompt_len": comp.prompt_len,
                        }).encode() + b"\n\n")
                    await writer.drain()
                    return
        except (ConnectionError, OSError):
            self._routes.pop(uid, None)
            self.metrics.cancelled["disconnect"] += 1
            self.engine.cancel(uid)
        finally:
            disc.cancel()

    async def _respond_json(self, writer, uid: int, rt: _Route) -> None:
        tokens = []
        while True:
            kind, val = await rt.queue.get()
            if kind == "token":
                tokens.append(int(val))
            else:
                comp = val
                self._respond(writer, "/v1/generate", 200, json.dumps({
                    "id": uid,
                    "tokens": tokens,
                    "finish_reason": comp.finish_reason,
                    "prompt_len": comp.prompt_len,
                }).encode())
                return


def serve(engine, *, host: str = "127.0.0.1", port: int = 8000,
          max_pending: int = 64,
          default_timeout_s: Optional[float] = None) -> None:
    """Blocking entry point (``repro.launch.serve --http``): boot the
    server and run until interrupted."""

    async def main():
        srv = HttpServer(engine, host=host, port=port,
                         max_pending=max_pending,
                         default_timeout_s=default_timeout_s)
        await srv.start()
        print(f"serving on http://{srv.host}:{srv.port}  "
              f"(POST /v1/generate, GET /metrics, GET /healthz)",
              flush=True)
        try:
            await asyncio.Event().wait()
        finally:
            await srv.stop()

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        pass


class BackgroundServer:
    """Run :class:`HttpServer` on a daemon thread — the synchronous
    harness for benchmarks and tests::

        with BackgroundServer(engine, max_pending=8) as bg:
            ...drive http://{bg.host}:{bg.port} from this thread...

    The engine must not be stepped by anyone else while the server owns
    it (the pump is the single driver)."""

    def __init__(self, engine, **kwargs):
        self.engine = engine
        self.kwargs = kwargs
        self.server: Optional[HttpServer] = None
        self._loop = None
        self._thread = None

    @property
    def host(self) -> str:
        return self.server.host

    @property
    def port(self) -> int:
        return self.server.port

    def __enter__(self) -> "BackgroundServer":
        self._loop = asyncio.new_event_loop()
        ready = threading.Event()
        err: list = []

        def run():
            asyncio.set_event_loop(self._loop)
            self.server = HttpServer(self.engine, **self.kwargs)
            try:
                self._loop.run_until_complete(self.server.start())
            except Exception as exc:  # surface bind errors to the caller
                err.append(exc)
                ready.set()
                return
            ready.set()
            self._loop.run_forever()

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="repro-http-serve")
        self._thread.start()
        if not ready.wait(timeout=30):
            raise RuntimeError("HTTP server failed to start in 30s")
        if err:
            raise err[0]
        return self

    def __exit__(self, *exc) -> None:
        asyncio.run_coroutine_threadsafe(self.server.stop(),
                                         self._loop).result(timeout=30)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=30)
        self._loop.close()


__all__ = ["HttpServer", "BackgroundServer", "ServeMetrics", "serve"]
