"""Asyncio serving front end: HTTP/SSE token streaming over the engine.

Two layers, both stdlib-only (no aiohttp — the CI image has none):

  * :class:`AsyncServeEngine` — drives ``ContinuousBatchingEngine.step()``
    as a cooperative asyncio task and turns the scheduler's ``on_token``
    hook into per-request ``asyncio.Queue`` deliveries, so any number of
    concurrent coroutines each ``async for`` their own request's tokens
    the moment the step that sampled them finishes. Submission applies
    the engine's overload gate (:class:`~.overload.ShedError` propagates
    to the caller — the HTTP layer maps it to 429) and a draining server
    rejects new work while resident requests run to completion.
  * :class:`ServeHTTPServer` — a minimal HTTP/1.1 server on
    ``asyncio.start_server`` exposing

      - ``POST /v1/generate`` — body ``{"prompt": [ids...],
        "max_new_tokens": n, "temperature": t, "top_p": p, "top_k": k,
        "seed": s}`` (sampling fields optional → engine defaults);
        responds with an SSE stream: one ``data: {"token": id,
        "index": i}`` event per token as it is sampled, then a final
        ``data: {"done": true, ...}`` event. 429 + Retry-After when the
        overload controller sheds, 503 while draining.
      - ``POST /v1/cancel`` — body ``{"request_id": id}``; releases the
        request's slot/pages/prefix retains mid-flight.
      - ``GET /v1/health`` — engine + overload stats as JSON.
      - ``POST /v1/drain`` — stop admitting, wait for resident requests
        to finish, then respond (graceful-shutdown hook).

    Client disconnects are detected two ways — the socket reaching EOF
    while the stream waits for its next token, and a failed SSE write —
    and both route to ``engine.cancel``: an abandoned request frees its
    pages and prefix-cache retains the same engine step instead of
    decoding to max_new_tokens for nobody.

The engine step is synchronous device compute, so the step loop runs it
inline and yields to the event loop between steps: token delivery,
admission, and disconnect handling all interleave at step granularity.
That is the right trade for a single-device engine — a thread pool would
add latency jitter without adding parallelism (steps serialize on the
device anyway).
"""
from __future__ import annotations

import asyncio
import json
import logging
import time
from typing import Dict, Optional

import numpy as np

from .overload import ShedError
from .sampling import SamplingParams

log = logging.getLogger("repro.serve.server")

#: sentinel queue item: the request was cancelled, end the stream
_CANCELLED = object()


class DrainingError(RuntimeError):
    """Submission rejected because the server is draining (HTTP 503)."""


class AsyncServeEngine:
    """Async facade over ``ContinuousBatchingEngine`` for many clients.

    One instance owns the engine: all submissions, cancels, and steps go
    through it, on one event loop. ``submit`` returns a request id whose
    tokens arrive on :meth:`stream`; the internal step task starts on
    first submission and parks when the engine drains idle.
    """

    def __init__(self, engine):
        self.engine = engine
        engine.scheduler.on_token = self._on_token
        self._queues: Dict[int, asyncio.Queue] = {}
        self._step_task: Optional[asyncio.Task] = None
        self.draining = False
        self._idle = asyncio.Event()
        self._idle.set()

    # -- engine-side callbacks (sync, inside step()) ------------------------

    def _on_token(self, req, token: int, finished: bool) -> None:
        q = self._queues.get(req.id)
        if q is not None:
            q.put_nowait((token, finished))

    # -- submission / delivery ----------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               sampling_params: Optional[SamplingParams] = None,
               arrival: Optional[float] = None) -> int:
        """Queue one request; returns its id (tokens via :meth:`stream`).
        ``arrival`` (``time.perf_counter`` seconds, default now) is when
        the request was received; admission latency counts from it.

        Raises :class:`DrainingError` while draining and propagates the
        engine's :class:`~.overload.ShedError` under overload.
        """
        if self.draining:
            raise DrainingError("server is draining, not accepting work")
        rid = self.engine.submit(np.asarray(prompt, np.int32),
                                 max_new_tokens,
                                 sampling_params=sampling_params,
                                 arrival=arrival)
        self._queues[rid] = asyncio.Queue()
        self._kick()
        return rid

    async def stream(self, request_id: int):
        """Async-iterate ``(index, token, finished)`` for one request.

        Ends after the ``finished`` token, or immediately (no further
        items) if the request is cancelled mid-stream.
        """
        q = self._queues.get(request_id)
        if q is None:
            raise KeyError(f"unknown request id {request_id}")
        index = 0
        try:
            while True:
                item = await q.get()
                if item is _CANCELLED:
                    return
                token, finished = item
                yield index, token, finished
                index += 1
                if finished:
                    return
        finally:
            self._queues.pop(request_id, None)

    def cancel(self, request_id: int) -> bool:
        """Release a request's slot/pages/prefix retains mid-flight and
        terminate its stream. True if it was still live."""
        found = self.engine.cancel(request_id)
        # pop the map entry now (a disconnected client's stream may never
        # resume to clean up); a live stream still holds the queue object
        # and sees the sentinel
        q = self._queues.pop(request_id, None)
        if q is not None:
            q.put_nowait(_CANCELLED)
        return found

    async def drain(self) -> None:
        """Stop admitting new requests, then wait until every resident
        request has run to completion (graceful shutdown)."""
        self.draining = True
        await self._idle.wait()

    # -- the step loop -------------------------------------------------------

    def _kick(self) -> None:
        if self._step_task is None or self._step_task.done():
            self._idle.clear()
            self._step_task = asyncio.get_running_loop().create_task(
                self._run_steps())

    async def _run_steps(self) -> None:
        engine = self.engine
        try:
            while engine.scheduler.has_work:
                engine.step()
                # streamed requests' results live in their queues; don't
                # let the batch-API result list grow without bound
                engine.scheduler.finished.clear()
                # one cooperative yield per step: token writes, new
                # submissions, cancels, and disconnects interleave here
                await asyncio.sleep(0)
        finally:
            self._idle.set()


# -- the HTTP/SSE layer ------------------------------------------------------

_SSE_HEADERS = (b"HTTP/1.1 200 OK\r\n"
                b"Content-Type: text/event-stream\r\n"
                b"Cache-Control: no-cache\r\n"
                b"Connection: close\r\n\r\n")


def _json_response(status: str, payload: dict,
                   extra_headers: str = "") -> bytes:
    body = json.dumps(payload).encode()
    return (f"HTTP/1.1 {status}\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n{extra_headers}"
            f"Connection: close\r\n\r\n").encode() + body


def _sse_event(payload: dict) -> bytes:
    return b"data: " + json.dumps(payload).encode() + b"\n\n"


def _parse_sampling(body: dict) -> Optional[SamplingParams]:
    keys = ("temperature", "top_p", "top_k", "seed")
    if not any(k in body for k in keys):
        return None
    return SamplingParams(
        temperature=float(body.get("temperature", 0.0)),
        top_p=float(body.get("top_p", 1.0)),
        top_k=int(body.get("top_k", 0)),
        seed=(int(body["seed"]) if body.get("seed") is not None
              else None)).validate()


class ServeHTTPServer:
    """Minimal stdlib HTTP/1.1 + SSE front end over AsyncServeEngine."""

    def __init__(self, async_engine: AsyncServeEngine, host: str =
                 "127.0.0.1", port: int = 8000):
        self.engine = async_engine
        self.host = host
        self.port = port
        self._server: Optional[asyncio.AbstractServer] = None

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port)
        # port 0 resolves to an ephemeral port at bind time
        self.port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()

    async def serve_forever(self) -> None:
        assert self._server is not None, "call start() first"
        async with self._server:
            await self._server.serve_forever()

    # -- request handling ---------------------------------------------------

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        received = time.perf_counter()
        try:
            method, path, body = await self._read_request(reader)
            if method == "POST" and path == "/v1/generate":
                await self._generate(reader, writer, body, received)
            elif method == "POST" and path == "/v1/cancel":
                found = self.engine.cancel(int(body["request_id"]))
                writer.write(_json_response(
                    "200 OK", {"cancelled": bool(found)}))
            elif method == "GET" and path == "/v1/health":
                stats = dict(self.engine.engine.overload.stats())
                stats["draining"] = self.engine.draining
                stats["queue_depth"] = len(
                    self.engine.engine.scheduler.queue)
                writer.write(_json_response("200 OK", stats))
            elif method == "POST" and path == "/v1/drain":
                await self.engine.drain()
                writer.write(_json_response("200 OK", {"drained": True}))
            else:
                writer.write(_json_response(
                    "404 Not Found", {"error": f"no route {method} {path}"}))
            await writer.drain()
        except (ConnectionResetError, BrokenPipeError, asyncio.IncompleteReadError):
            pass
        except Exception as e:  # malformed request: answer, don't crash
            try:
                writer.write(_json_response("400 Bad Request",
                                            {"error": str(e)}))
                await writer.drain()
            except (ConnectionResetError, BrokenPipeError):
                pass
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

    async def _read_request(self, reader: asyncio.StreamReader):
        request_line = (await reader.readline()).decode()
        if not request_line.strip():
            raise ValueError("empty request")
        method, path, _ = request_line.split(" ", 2)
        content_length = 0
        while True:
            line = (await reader.readline()).decode()
            if line in ("\r\n", "\n", ""):
                break
            name, _, value = line.partition(":")
            if name.strip().lower() == "content-length":
                content_length = int(value.strip())
        body = {}
        if content_length:
            body = json.loads(await reader.readexactly(content_length))
        return method, path.strip(), body

    async def _generate(self, reader: asyncio.StreamReader,
                        writer: asyncio.StreamWriter, body: dict,
                        received: float) -> None:
        try:
            rid = self.engine.submit(
                body["prompt"], int(body.get("max_new_tokens", 16)),
                sampling_params=_parse_sampling(body), arrival=received)
        except DrainingError as e:
            writer.write(_json_response("503 Service Unavailable",
                                        {"error": str(e)}))
            return
        except ShedError as e:
            writer.write(_json_response(
                "429 Too Many Requests", {"error": str(e)},
                extra_headers=f"Retry-After: {e.retry_after_s:.3f}\r\n"))
            return
        except (ValueError, KeyError) as e:
            writer.write(_json_response("400 Bad Request",
                                        {"error": str(e)}))
            return
        writer.write(_SSE_HEADERS)
        writer.write(_sse_event({"request_id": rid}))
        await writer.drain()
        # half-open detection: the POST body is fully consumed, so any
        # EOF from here on means the client hung up — reap the request
        # instead of decoding to max_new_tokens for nobody
        eof_task = asyncio.ensure_future(reader.read(1))
        tokens = []
        cancelled = False
        try:
            stream = self.engine.stream(rid)
            stream_iter = stream.__aiter__()
            while True:
                next_task = asyncio.ensure_future(stream_iter.__anext__())
                done, _ = await asyncio.wait(
                    {eof_task, next_task},
                    return_when=asyncio.FIRST_COMPLETED)
                if eof_task in done:
                    next_task.cancel()
                    self.engine.cancel(rid)
                    cancelled = True
                    log.info("client disconnected, cancelled request %d",
                             rid)
                    return
                try:
                    index, token, finished = next_task.result()
                except StopAsyncIteration:
                    cancelled = True  # cancelled via /v1/cancel
                    break
                tokens.append(int(token))
                try:
                    writer.write(_sse_event(
                        {"token": int(token), "index": index}))
                    await writer.drain()
                except (ConnectionResetError, BrokenPipeError):
                    self.engine.cancel(rid)
                    cancelled = True
                    return
                if finished:
                    break
            if not cancelled:
                writer.write(_sse_event(
                    {"done": True, "request_id": rid, "tokens": tokens}))
            else:
                writer.write(_sse_event(
                    {"done": True, "request_id": rid, "cancelled": True}))
            await writer.drain()
        finally:
            eof_task.cancel()


async def sse_generate(host: str, port: int, payload: dict):
    """Minimal stdlib SSE client: POST /v1/generate, yield parsed events.

    The benchmark's and tests' closed-loop clients use this — it speaks
    exactly the wire format ``ServeHTTPServer`` emits. Raises
    ``RuntimeError`` carrying the status line on non-200 responses (429
    sheds land here).
    """
    body = json.dumps(payload).encode()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(
            (f"POST /v1/generate HTTP/1.1\r\nHost: {host}\r\n"
             f"Content-Type: application/json\r\n"
             f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await writer.drain()
        status = (await reader.readline()).decode()
        if "200" not in status:
            rest = await reader.read()
            raise RuntimeError(f"{status.strip()} {rest.decode()!r}")
        while True:  # skip response headers
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
        while True:
            line = await reader.readline()
            if not line:
                return
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            event = json.loads(line[len(b"data: "):])
            yield event
            if event.get("done"):
                return
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionResetError, BrokenPipeError):
            pass
