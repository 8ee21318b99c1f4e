"""ModelServer — the HTTP front end of the serving tier.

A stdlib ``ThreadingHTTPServer`` (the ui_server pattern: no web framework,
no egress) over a :class:`~deeplearning4j_tpu.serving.router.ModelRouter`:

    POST /v1/models/<id>/infer     {"inputs": [[...], ...]}      → outputs
    POST /v1/models/<id>/generate  {"prompt_tokens"|"prompts": [[...], ...],
                                    "max_new_tokens": N,
                                    "temperature": T}            → tokens
    GET  /v1/models                                              → registry
    GET  /v1/models/<id>/debug/requests[?last=N]   flight-recorder dump
    GET  /metrics                  Prometheus text (ui_server collectors)
    GET  /healthz                  health JSON incl. serving + slo sections
    GET  /slo                      SLO evaluation JSON (util/slo.py)
    GET  /admin/status             worker identity: pid, worker_id, drain
    POST /admin/drain              begin graceful drain (idempotent, 200)

Connections are persistent: the handler speaks HTTP/1.1 with explicit
``Content-Length`` on every response, so a front tier (serving/fleet.py)
keeps one pooled connection per worker instead of paying a TCP handshake
per request. That is also why every POST path reads the full request body
*before* answering — an unread body would desynchronize the keep-alive
stream and corrupt the next request on the socket.

Request scope: every POST honors an inbound ``X-Request-Id`` header (or
mints one) and echoes it on the response — success AND error — so a caller
can correlate its 429 with the scheduler's flight-recorder record and the
sampled trace spans (docs/OBSERVABILITY.md#request-tracing--slos).

Request headers/body knobs: ``lane`` ("interactive"|"batch") and
``deadline_ms`` ride in the JSON body. The load-shed contract
(docs/SERVING.md): admission rejection and deadline misses answer **429**
with a ``Retry-After`` header; a draining server answers **503**; an
unknown model **404**; a malformed body **400**. Shedding is queue-depth
driven in the scheduler — the HTTP layer only translates.

Graceful drain reuses the r11 elastic seam: ``drain_signals`` (default
SIGTERM — what every preemption notice delivers) are trapped; on signal the
server stops admitting (503), finishes everything queued, counts
``serving.drains_total``, flips the ``serving.drained`` health check, and
``drained`` reads True — the same finish-in-flight → leave contract
``ElasticTrainer`` gives training (docs/FAULT_TOLERANCE.md).
"""

from __future__ import annotations

import json
import signal
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from deeplearning4j_tpu.serving.resilience import (ModelLoadError,
                                                   ReloadRejectedError)
from deeplearning4j_tpu.serving.router import ModelRouter, UnknownModelError
from deeplearning4j_tpu.serving.scheduler import ShedError
from deeplearning4j_tpu.util import telemetry as tm


class _ServingHTTPServer(ThreadingHTTPServer):
    # a connection burst wider than the stdlib default accept backlog (5)
    # must queue in the kernel, not get RST — admission control lives in
    # the scheduler's queue_limit, never in the TCP accept queue
    request_queue_size = 128
    daemon_threads = True


class ModelServer:
    """HTTP model server over a router (see module doc)."""

    def __init__(self, router: ModelRouter, port: int = 0,
                 host: str = "127.0.0.1",
                 drain_signals=(signal.SIGTERM,),
                 request_timeout_s: float = 60.0,
                 worker_id: Optional[str] = None):
        self.router = router
        self.host = host
        self.port = port
        self.drain_signals = tuple(drain_signals)
        self.request_timeout_s = float(request_timeout_s)
        #: fleet identity (serving/fleet.py spawns workers with one);
        #: surfaced on GET /admin/status so a supervisor can verify it is
        #: talking to the process it thinks it is after a respawn
        self.worker_id = worker_id
        self.drained = False
        self._draining = False
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._old_handlers: dict = {}

    # ----------------------------------------------------------- lifecycle
    def start(self, warmup: bool = True) -> "ModelServer":
        if warmup:
            self.router.warmup()
        server = self
        handler = _make_handler(self)
        self._httpd = _ServingHTTPServer((self.host, self.port), handler)
        self.port = self._httpd.server_address[1]  # resolves port 0
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True, name="model-server")
        self._thread.start()
        self._install_signal_handlers()
        tm.set_health("serving.accepting", True,
                      f"listening on {self.host}:{self.port}")
        return server

    def _install_signal_handlers(self):
        try:
            for sig in self.drain_signals:
                self._old_handlers[sig] = signal.signal(
                    sig, self._on_drain_signal)
        except ValueError:
            # not the main thread (tests, embedded servers): drain stays
            # available through request_drain()
            self._old_handlers = {}

    def _restore_signal_handlers(self):
        for sig, old in self._old_handlers.items():
            try:
                signal.signal(sig, old)
            except ValueError:
                pass
        self._old_handlers = {}

    def _on_drain_signal(self, signum, frame):
        tm.counter("serving.drain_signals_total")
        self.request_drain()

    def request_drain(self, timeout: float = 30.0) -> "ModelServer":
        """Begin graceful drain (idempotent): stop admitting, finish queued
        work in the background, then report drained. Returns immediately;
        poll ``drained`` or join ``wait_drained()``."""
        if self._draining:
            return self
        self._draining = True
        tm.set_health("serving.accepting", False, "draining")

        def _drain():
            clean = self.router.drain(timeout=timeout)
            self.drained = True
            tm.set_health("serving.drained", True,
                          f"drained clean={clean}")

        threading.Thread(target=_drain, daemon=True,
                         name="serving-drain").start()
        return self

    def wait_drained(self, timeout: float = 30.0) -> bool:
        import time

        deadline = time.monotonic() + timeout
        while not self.drained and time.monotonic() < deadline:
            time.sleep(0.01)
        return self.drained

    @property
    def draining(self) -> bool:
        return self._draining

    def stop(self):
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        self._restore_signal_handlers()
        self.router.shutdown()

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    # ------------------------------------------------------------ handlers
    def _handle_infer(self, model_id: str, body: dict,
                      request_id: Optional[str] = None) -> dict:
        x = np.asarray(body["inputs"], np.float32)
        if x.ndim < 2:
            x = x[None]
        fut = self.router.submit(
            model_id, x, lane=body.get("lane", "interactive"),
            deadline_ms=body.get("deadline_ms"), request_id=request_id)
        out = fut.result(timeout=self.request_timeout_s)
        return {"model": model_id, "outputs": np.asarray(out).tolist()}

    def _handle_generate(self, model_id: str, body: dict,
                         request_id: Optional[str] = None) -> dict:
        prompts = body.get("prompt_tokens", body.get("prompts"))
        if prompts is None:
            raise ValueError("generate needs prompt_tokens")
        if prompts and isinstance(prompts[0], (int, float)):
            prompts = [prompts]  # single prompt shorthand
        opts = {"max_new_tokens": int(body.get("max_new_tokens", 16))}
        if body.get("temperature"):
            opts["temperature"] = float(body["temperature"])
        if body.get("eos_id") is not None:
            opts["eos_id"] = int(body["eos_id"])
        futs = []
        try:
            for i, p in enumerate(prompts):
                # multi-prompt bodies fan out to N scheduler requests: each
                # keeps the caller's id with a /row suffix, so all of them
                # correlate back to one HTTP request in the flight recorder
                rid = None if request_id is None else (
                    request_id if len(prompts) == 1
                    else f"{request_id}/{i}")
                futs.append(self.router.submit(
                    model_id, np.asarray(p, np.int32),
                    lane=body.get("lane", "batch"),
                    deadline_ms=body.get("deadline_ms"),
                    request_id=rid, **opts))
            toks = [f.result(timeout=self.request_timeout_s) for f in futs]
        except Exception:
            # a shed/timeout mid-list must not abandon live work: cancel
            # whatever is still queued (a no-op on finished futures) so an
            # overloaded model is not decoded-into for a 429'd request
            for f in futs:
                f.cancel()
            raise
        return {"model": model_id, "tokens": toks}


def _make_handler(server: ModelServer):
    from deeplearning4j_tpu.util.ui_server import UIServer

    class Handler(BaseHTTPRequestHandler):
        # HTTP/1.1 so connections persist across requests: the fleet front
        # tier (serving/fleet.py) pools one connection per worker. Every
        # response sets Content-Length (see _send), which 1.1 requires for
        # keep-alive framing.
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):  # quiet
            pass

        def _send(self, status: int, body: bytes,
                  ctype: str = "application/json", headers=()):
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for k, v in headers:
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _send_json(self, status: int, obj, headers=()):
            self._send(status, json.dumps(obj).encode(), headers=headers)

        def do_GET(self):
            from urllib.parse import parse_qs, urlparse

            u = urlparse(self.path)
            parts = u.path.strip("/").split("/")
            if u.path == "/metrics":
                self._send(200, UIServer._metrics_text().encode(),
                           "text/plain; version=0.0.4; charset=utf-8")
            elif u.path == "/healthz":
                body, ok = UIServer._healthz()
                self._send(200 if ok else 503, body.encode())
            elif u.path == "/slo":
                self._send(200, UIServer._slo_json().encode())
            elif u.path == "/admin/status":
                # worker identity for a fleet supervisor: cheap, never
                # touches the scheduler (a wedged model must not hide
                # the process's identity from its supervisor)
                import os

                self._send_json(200, {
                    "pid": os.getpid(),
                    "worker_id": server.worker_id,
                    "draining": server.draining,
                    "drained": server.drained,
                    "models": server.router.model_ids(),
                })
            elif u.path in ("/v1/models", "/v1/models/"):
                self._send_json(200, server.router.status())
            elif len(parts) == 5 and parts[:2] == ["v1", "models"] \
                    and parts[3:] == ["debug", "requests"]:
                # flight-recorder dump: the last-N completed/shed/error
                # request records for one model (docs/OBSERVABILITY.md)
                try:
                    last = int(parse_qs(u.query).get("last", [0])[0]) or None
                except ValueError:
                    last = None
                try:
                    records = server.router.debug_requests(parts[2],
                                                           last=last)
                except UnknownModelError as e:
                    self._send_json(404, {"error": f"unknown model {e}"})
                    return
                self._send_json(200, {"model": parts[2],
                                      "requests": records})
            else:
                self._send_json(404, {"error": f"no route {self.path}"})

        def do_POST(self):
            # read the body FIRST, on every path — an unread body would
            # desynchronize the persistent (HTTP/1.1) connection and the
            # next request on the socket would parse garbage. A live span
            # (and, under a profile, a TraceAnnotation beside the worker's
            # phases: a phase stretched by a handler holding the GIL shows
            # it); a body that is no JSON is answered 400 below
            with tm.span("serving.http.parse"):
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n) if n else b""
                try:
                    body = json.loads(raw or b"{}")
                except ValueError as e:
                    body = e
            parts = self.path.strip("/").split("/")
            if parts == ["admin", "drain"]:
                # admin verb for a front tier / orchestrator that cannot
                # signal the process (adopted workers): same graceful
                # drain as SIGTERM, idempotent
                server.request_drain()
                self._send_json(200, {"draining": True})
                return
            # /v1/models/<id>/infer|generate|reload
            if len(parts) != 4 or parts[:2] != ["v1", "models"] \
                    or parts[3] not in ("infer", "generate", "reload"):
                self._send_json(404, {"error": f"no route {self.path}"})
                return
            model_id, verb = parts[2], parts[3]
            # honor the caller's X-Request-Id (mint one otherwise) and echo
            # it on EVERY response — 200s and sheds alike — so the caller,
            # the trace spans, and the flight recorder share one id
            from deeplearning4j_tpu.serving.scheduler import new_request_id

            rid = self.headers.get("X-Request-Id") or new_request_id()
            rid_hdr = [("X-Request-Id", rid)]
            if server.draining:
                self._send_json(
                    503, {"error": "draining", "model": model_id},
                    headers=[("Retry-After", "10")] + rid_hdr)
                return
            try:
                if isinstance(body, ValueError):
                    raise body
                if verb == "infer":
                    resp = server._handle_infer(model_id, body,
                                                request_id=rid)
                elif verb == "reload":
                    # rolling-reload admin verb (docs/SERVING.md#resilience)
                    resp = {"model": model_id,
                            "version": server.router.reload(
                                model_id, body["path"])}
                else:
                    resp = server._handle_generate(model_id, body,
                                                   request_id=rid)
                with tm.span("serving.http.write"):
                    resp["request_id"] = rid
                    self._send_json(200, resp, headers=rid_hdr)
            except UnknownModelError as e:
                self._send_json(404, {"error": f"unknown model {e}"},
                                headers=rid_hdr)
            except (ModelLoadError, ReloadRejectedError) as e:
                # a rejected reload is a CONFLICT with the live version,
                # which keeps serving — never a 5xx, the tier is healthy
                self._send_json(409, {"error": type(e).__name__,
                                      "detail": str(e)},
                                headers=rid_hdr)
            except ShedError as e:
                # the load-shed contract: 429 (or 503 while draining) with
                # Retry-After, body says why — docs/SERVING.md
                self._send_json(
                    e.http_status,
                    {"error": type(e).__name__, "detail": str(e),
                     "request_id": rid},
                    headers=[("Retry-After",
                              str(int(max(1, e.retry_after_s))))] + rid_hdr)
            except (KeyError, ValueError, TypeError) as e:
                self._send_json(400, {"error": f"bad request: {e!r}"},
                                headers=rid_hdr)
            except Exception as e:  # noqa: BLE001 — a broken batch must
                self._send_json(500, {"error": repr(e)},  # not kill the srv
                                headers=rid_hdr)

    return Handler
