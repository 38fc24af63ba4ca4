"""Streaming generation endpoint over stdlib ``http.server``.

Same opt-in localhost pattern as ``observability.start_metrics_server``:
nothing listens unless :func:`start_serving_server` is called; with no
explicit port it reads ``FLAGS_serving_port`` (0 = disabled).

Routes:

- ``POST /v1/generate`` — body ``{"prompt": [ids...], "max_new_tokens": n,
  "priority": "interactive"|"standard"|"best_effort"|int, "tenant": str,
  "ttl_s": seconds, "eos_token_id": id, "stream": bool}``. With
  ``stream`` (default true) the response is ``application/x-ndjson``: one
  ``{"token": id}`` line per generated token AS IT IS PRODUCED, then a final
  ``{"done": true, "outcome": ..., "tokens": n}`` line; without it, one JSON
  object after the request finishes.
- ``GET /healthz`` — the frontend's :meth:`snapshot` (overload level, queue
  depth, pool utilization). In multi-replica mode with a
  :class:`~paddle_tpu.observability.aggregate.ClusterObserver` attached to
  the router, this is the observer's fleet view instead (router state,
  per-replica lifecycle + tp_degree + kv-tier + spec acceptance, the SLO
  burn-rate block).
- ``GET /metrics`` — the same replica-labeled Prometheus text exposition
  ``observability.start_metrics_server`` serves (one shared renderer,
  ``render_exposition`` — single- and multi-replica formats agree by
  construction).

Tracing: a ``traceparent`` request header (W3C shape, see
``observability.tracing``) continues the caller's trace through this hop;
the response carries a ``traceparent`` header naming the request's root
span so the client can link its own spans. With ``FLAGS_trace_sample_rate``
at 0 the header is ignored at the cost of one cached-bool read.

Status mapping: malformed body / intake validation → **400** (typed
``IntakeError``, no message string-matching), unknown route → **404**,
shedding → **429** with a ``Retry-After`` header from the
:class:`Overloaded` hint, engine failure mid-request → **500**. A client
that disconnects mid-stream gets its request cancelled — the engine slot is
evicted and its KV blocks reclaimed — so an impatient client cannot leak
pool capacity. Each response counts into
``serving_http_responses_total{code}``.

**Multi-replica mode**: pass a
:class:`~paddle_tpu.serving.router.ReplicaRouter` instead of a frontend —
it exposes the same ``submit``/``cancel``/``snapshot``/``start``/``stop``
surface, so the endpoint serves the whole cluster through one port:
``/healthz`` returns per-replica states plus routing counters, and a
replica death mid-stream fails over transparently (the handler keeps
streaming from the same handle).
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

from paddle_tpu.flags import GLOBAL_FLAGS
from paddle_tpu.observability.serving import serving_metrics
from paddle_tpu.serving.errors import IntakeError, Overloaded
from paddle_tpu.serving.frontend import Priority, ServingFrontend
from paddle_tpu.testing.faults import InjectedFault, fault_point

__all__ = ["start_serving_server", "stop_serving_server"]

# cached once: families are permanent registry objects; re-resolving all of
# them through the registry lock on every response would be pure waste
_RESPONSES = serving_metrics()["responses"]


class _BadRequest(ValueError):
    pass


def _parse_body(raw: bytes) -> Dict[str, Any]:
    """Validate the request body; returns ``submit()`` kwargs plus
    ``stream``. Anything wrong raises :class:`_BadRequest` → 400."""
    try:
        body = json.loads(raw.decode("utf-8") if raw else "{}")
    except (ValueError, UnicodeDecodeError) as exc:
        raise _BadRequest(f"body is not valid JSON: {exc}") from exc
    if not isinstance(body, dict):
        raise _BadRequest("body must be a JSON object")
    prompt = body.get("prompt")
    if not isinstance(prompt, list) or not all(isinstance(t, int) for t in prompt):
        raise _BadRequest("'prompt' must be a list of token ids (integers)")
    out: Dict[str, Any] = {"prompt_ids": prompt}
    if "max_new_tokens" in body:
        if not isinstance(body["max_new_tokens"], int):
            raise _BadRequest("'max_new_tokens' must be an integer")
        out["max_new_tokens"] = body["max_new_tokens"]
    if "priority" in body:
        try:
            out["priority"] = Priority.parse(body["priority"])
        except ValueError as exc:
            raise _BadRequest(str(exc)) from exc
    if "tenant" in body:
        if (
            not isinstance(body["tenant"], str)
            or not body["tenant"]
            or len(body["tenant"]) > 128
        ):
            raise _BadRequest("'tenant' must be a non-empty string (<= 128 chars)")
        out["tenant"] = body["tenant"]
    if "ttl_s" in body and body["ttl_s"] is not None:
        if not isinstance(body["ttl_s"], (int, float)) or body["ttl_s"] <= 0:
            raise _BadRequest("'ttl_s' must be a positive number of seconds")
        out["ttl_s"] = float(body["ttl_s"])
    if "eos_token_id" in body and body["eos_token_id"] is not None:
        if not isinstance(body["eos_token_id"], int):
            raise _BadRequest("'eos_token_id' must be an integer")
        out["eos_token_id"] = body["eos_token_id"]
    out["stream"] = bool(body.get("stream", True))
    return out


class _ServingHandler(BaseHTTPRequestHandler):
    # set by start_serving_server on the handler subclass: a ServingFrontend
    # or a ReplicaRouter (duck-typed: same submit/cancel/snapshot surface)
    frontend: ServingFrontend = None  # type: ignore[assignment]
    stream_timeout_s: float = 60.0

    # -- plumbing ------------------------------------------------------------
    def log_message(self, *args: Any) -> None:  # silence per-request stderr
        pass

    def _count(self, code: int) -> None:
        _RESPONSES.labels(code=str(code)).inc()

    def _send_json(self, code: int, payload: Dict[str, Any],
                   headers: Optional[Dict[str, str]] = None) -> None:
        body = (json.dumps(payload) + "\n").encode()
        self._count(code)  # BEFORE the write: a client that reads the body
        # and immediately asserts on the counter must never race the handler
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    # -- routes --------------------------------------------------------------
    def do_GET(self) -> None:  # noqa: N802 - http.server API
        path = self.path.split("?", 1)[0]
        if path == "/healthz":
            # multi-replica mode with a ClusterObserver attached: the fleet
            # view (router state, per-replica lifecycle + capability blocks,
            # the SLO monitor); otherwise the frontend/router snapshot
            observer = getattr(self.frontend, "observer", None)
            self._send_json(
                200,
                observer.healthz() if observer is not None
                else self.frontend.snapshot(),
            )
            return
        if path == "/metrics":
            # the SAME replica-labeled exposition as start_metrics_server:
            # one renderer, so single- and multi-replica formats agree. An
            # attached observer may carry a non-default registry — honor it.
            from paddle_tpu.observability.exporters import render_exposition

            observer = getattr(self.frontend, "observer", None)
            body = (
                observer.render_metrics()
                if observer is not None
                else render_exposition()
            ).encode()
            self._count(200)
            self.send_response(200)
            self.send_header(
                "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
            )
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        self._send_json(
            404,
            {"error": "try POST /v1/generate, GET /healthz or GET /metrics"},
        )

    def do_POST(self) -> None:  # noqa: N802 - http.server API
        if self.path.split("?", 1)[0] != "/v1/generate":
            self._send_json(404, {"error": "try POST /v1/generate"})
            return
        try:
            length = int(self.headers.get("Content-Length", "0"))
            kwargs = _parse_body(self.rfile.read(length))
            stream = kwargs.pop("stream")
        except _BadRequest as exc:
            self._send_json(400, {"error": str(exc)})
            return
        # distributed tracing: continue the caller's trace when the header
        # is present (malformed headers are ignored, never a 4xx)
        kwargs["traceparent"] = self.headers.get("traceparent")
        try:
            handle = self.frontend.submit(**kwargs)
        except Overloaded as exc:
            self._send_json(
                429,
                {"error": str(exc), "reason": exc.reason,
                 "retry_after_s": exc.retry_after},
                headers={"Retry-After": f"{exc.retry_after:.3f}"},
            )
            return
        except IntakeError as exc:
            # the typed taxonomy is the whole point: no message matching
            self._send_json(400, {"error": str(exc), "type": type(exc).__name__})
            return
        except RuntimeError as exc:  # engine permanently failed
            self._send_json(500, {"error": str(exc)})
            return
        if stream:
            self._stream_response(handle)
        else:
            self._blocking_response(handle)

    # -- response modes ------------------------------------------------------
    def _stream_response(self, handle) -> None:
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Cache-Control", "no-store")
        if handle.traceparent:
            # the root span's identity: the client can link its own spans
            self.send_header("traceparent", handle.traceparent)
        # no Content-Length: HTTP/1.0 semantics — connection close ends the
        # body; each line is flushed as its token is produced
        self.end_headers()
        n = 0
        try:
            for tok in handle.stream(timeout=self.stream_timeout_s):
                fault_point("serving.respond")
                self.wfile.write((json.dumps({"token": int(tok)}) + "\n").encode())
                self.wfile.flush()
                n += 1
            self.wfile.write(
                (json.dumps(
                    {"done": True, "outcome": handle.outcome, "tokens": n}
                ) + "\n").encode()
            )
            self.wfile.flush()
            self._count(200)
        except TimeoutError:
            # server-side stall (pump stopped?) — not the client's fault,
            # but the slot must still be reclaimed
            self.frontend.cancel(handle.id, reason="stream_timeout")
            self.close_connection = True
        except (BrokenPipeError, ConnectionResetError, OSError, InjectedFault):
            # client went away — or a serving.respond fault modelling it: a
            # sampled campaign's default InjectedFault must take the same
            # cancel path as a real torn connection, so overload x fault
            # interplay reaches the eviction code. Either way the request is
            # evicted and its slot + KV blocks return to the pool.
            self.frontend.cancel(handle.id, reason="client_disconnect")
            self.close_connection = True

    def _blocking_response(self, handle) -> None:
        try:
            inner = handle.result(timeout=self.stream_timeout_s)
        except TimeoutError as exc:
            self.frontend.cancel(handle.id, reason="stream_timeout")
            self._send_json(500, {"error": str(exc)})
            return
        try:
            fault_point("serving.respond")
            self._send_json(
                200,
                {
                    "outcome": handle.outcome,
                    # a router handle shed before any replica accepted it
                    # has no engine-side request to read a reason from
                    "finish_reason": None if inner is None else inner.finish_reason,
                    "tokens": handle.tokens(),
                    "degraded": handle.degraded,
                },
                headers=(
                    {"traceparent": handle.traceparent}
                    if handle.traceparent else None
                ),
            )
        except (BrokenPipeError, ConnectionResetError, OSError, InjectedFault):
            # the request already finished (nothing to evict) — just don't
            # let a torn connection / injected respond fault kill the
            # handler thread loudly
            self.close_connection = True


_server: Optional[ThreadingHTTPServer] = None
_server_lock = threading.Lock()


def start_serving_server(
    frontend: ServingFrontend,
    port: Optional[int] = None,
    stream_timeout_s: float = 60.0,
) -> Optional[ThreadingHTTPServer]:
    """Serve the generation endpoint on 127.0.0.1 and start the frontend's
    pump thread. ``frontend`` may also be a
    :class:`~paddle_tpu.serving.router.ReplicaRouter` (multi-replica mode:
    per-replica pumps plus the router supervisor are started instead).
    ``port=None`` reads ``FLAGS_serving_port`` (<= 0 → disabled,
    returns None); an explicit ``port=0`` binds an ephemeral port
    (``server.server_address[1]`` has it). Idempotent for the same port;
    raises when a different port is requested while one is bound."""
    global _server
    with _server_lock:
        if _server is not None:
            bound = _server.server_address[1]
            if port not in (None, 0) and int(port) != bound:
                raise RuntimeError(
                    f"serving server already bound to port {bound}; "
                    f"stop_serving_server() before requesting port {port}"
                )
            return _server
        if port is None:
            port = int(GLOBAL_FLAGS.get("serving_port"))
            if port <= 0:
                return None
        from paddle_tpu.core.compile_cache import enable_compile_cache

        enable_compile_cache()  # before the pump's first (step) compile
        handler = type(
            "_BoundServingHandler",
            (_ServingHandler,),
            {"frontend": frontend, "stream_timeout_s": float(stream_timeout_s)},
        )
        srv = ThreadingHTTPServer(("127.0.0.1", int(port)), handler)
        srv.daemon_threads = True
        frontend.start()
        t = threading.Thread(target=srv.serve_forever, daemon=True, name="serving-http")
        t.start()
        _server = srv
        return srv


def stop_serving_server(frontend: Optional[ServingFrontend] = None) -> None:
    """Shut the endpoint down; also stops ``frontend``'s pump when given."""
    global _server
    with _server_lock:
        if _server is not None:
            _server.shutdown()
            _server.server_close()
            _server = None
    if frontend is not None:
        frontend.stop()
