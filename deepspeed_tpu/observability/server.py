"""Live telemetry & control plane: per-engine HTTP ops surface.

Every observability layer before this one was file-based — ``.prom``
textfiles, JSONL logs, flight-record directories read after the fact.
A fleet needs engines that are *live* targets: scrapeable metrics,
machine-readable probes, and remote drain/dump control. This module is
that surface, dependency-free on the stdlib ``http.server``:

Read endpoints (GET):

- ``/metrics``  — Prometheus exposition, byte-compatible with the
  textfile sink (both render through ``expfmt.render_exposition``);
- ``/healthz``  — liveness JSON (200 while the process serves requests);
- ``/readyz``   — readiness JSON, **503** when not ready (draining /
  queue full) — the k8s-style probe contract;
- ``/requests`` — live in-flight table (rid, state, slot, tokens,
  deadlines) straight from the scheduler;
- ``/capacity`` — the capacity report (PR 6); ``?census=1`` adds the
  AOT program census (expensive — off by default per scrape);
- ``/goodput``  — the goodput/badput decomposition (``goodput.py``);
- ``/tenants``  — per-tenant cost/fairness breakdown (``tenantscope.py``
  report: attribution rows, Jain index, noisy-neighbor state);
- ``/flight``   — newest flight-record summary (manifest + why-marker
  names), the live analog of the doctor's file-mode flight section;
- ``/trace``    — the engine's span ring as a Chrome/Perfetto trace
  (save and load at ui.perfetto.dev); ``?rid=N`` returns that request's
  hop-latency decomposition (queue_wait/prefill/handoff_wait/import/
  decode/e2e) instead.

Control endpoints (POST, token-gated — see below):

- ``/drain``       — begin a graceful drain (body ``{"end": true}``
  reopens intake);
- ``/flight/dump`` — freeze the flight recorder now, why-marker
  ``manual``;
- ``/slo/reload``  — swap the SLO config live (JSON body = the new
  ``SLOConfig`` dict).

Security posture: the server binds **loopback by default**; exposing it
beyond localhost is an explicit config/call-site decision. Control
POSTs additionally require the configured bearer token
(``Authorization: Bearer <token>`` or ``X-DSTPU-Token``) when one is
set; without a token they are accepted from loopback peers only.

Cost discipline: config-gated, off by default — a disabled engine
builds no server object, spawns **zero threads**, compiles zero
programs, and adds zero host syncs (``tests/unit/test_telemetry.py``
compares compile counts with the plane on and off). Enabled, request handling runs on
daemon threads and only ever touches host-side Python state (registry
snapshots under their own locks, scheduler tables copied defensively).
"""

from __future__ import annotations

import dataclasses
import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional
from urllib.parse import parse_qs, urlparse

from ..utils.logging import log_dist
from .expfmt import exposition_from_events

_JSON = "application/json; charset=utf-8"
# the content type Prometheus' scraper advertises/expects for text format
_PROM = "text/plain; version=0.0.4; charset=utf-8"


@dataclasses.dataclass
class TelemetryConfig:
    """Config block gating the per-engine telemetry server (serving:
    ``serving.telemetry``, training: ``observability.telemetry``). Off
    (``enabled=False`` / block absent) builds nothing — zero threads."""

    enabled: bool = False
    port: int = 0                  # 0 = ephemeral (bound port returned)
    host: str = "127.0.0.1"        # loopback-bound by default
    token: str = ""                # control-POST bearer token ("" = only
                                   # loopback peers may POST)

    def __post_init__(self):
        if not 0 <= int(self.port) <= 65535:
            raise ValueError(f"telemetry port must be in [0, 65535], "
                             f"got {self.port}")

    @classmethod
    def from_any(cls, cfg) -> "Optional[TelemetryConfig]":
        if cfg is None or isinstance(cfg, cls):
            return cfg
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(
                f"unknown telemetry config keys: {sorted(unknown)}")
        return cls(**cfg)


@dataclasses.dataclass
class TelemetryHooks:
    """What an engine exposes to its telemetry server. Every hook is
    optional except the registry: an absent hook makes its endpoint a
    clean 404 (the doctor's ``--url`` mode degrades on exactly that),
    so one server class fronts both engine types."""

    registry: object                              # MetricsRegistry
    prefix: str = "dstpu"
    step_fn: Optional[Callable[[], int]] = None
    # called before every /metrics render: refresh derived gauges
    # (health mirror, goodput export) so scrapes are always current
    refresh_fn: Optional[Callable[[], None]] = None
    health_fn: Optional[Callable[[], dict]] = None
    requests_fn: Optional[Callable[[], list]] = None
    capacity_fn: Optional[Callable[[bool], dict]] = None   # (census) ->
    goodput_fn: Optional[Callable[[], dict]] = None
    flight_fn: Optional[Callable[[], dict]] = None
    # (rid | None) -> chrome trace dict / hop decomposition / None(→404)
    trace_fn: Optional[Callable[[Optional[int]], object]] = None
    drain_fn: Optional[Callable[[bool], dict]] = None      # (end) ->
    dump_fn: Optional[Callable[[], Optional[str]]] = None
    slo_reload_fn: Optional[Callable[[dict], dict]] = None
    # arrival & scaling observatory readout (loadscope.py): the scaling
    # report JSON — unmeasured inputs arrive as nulls with reasons, the
    # endpoint stays 200 (degraded-null contract); absent hook → 404
    scaling_fn: Optional[Callable[[], dict]] = None
    # per-tenant observatory readout (tenantscope.py): the per-tenant
    # breakdown — cost attribution rows, fairness block, noisy-neighbor
    # state (the doctor's --url [tenants] section); absent hook → 404
    tenants_fn: Optional[Callable[[], dict]] = None
    # autoscaler control loop (serving/autoscaler.py): GET status +
    # decision audit tail; POST freeze/pin override (token-gated like
    # every control POST; ValueError → 400)
    autoscale_fn: Optional[Callable[[], dict]] = None
    autoscale_control_fn: Optional[Callable[[dict], dict]] = None


def flight_summary(flight) -> dict:
    """Live flight-record summary for ``GET /flight`` and the doctor's
    ``--url`` gate: the newest dump's manifest plus the why-marker names
    it contains — the same facts the file-mode doctor derives from the
    dump directory."""
    from .flight import newest_flight_record, read_flight_record

    out: dict = {"dump_dir": str(flight.dump_dir),
                 "dumps": [str(p) for p in flight.dumps],
                 "max_dumps": flight.max_dumps,
                 "newest": None, "markers": []}
    rec_dir = newest_flight_record(flight.dump_dir)
    if rec_dir is not None:
        rec = read_flight_record(rec_dir)
        names = sorted({str(dict(m.get("meta", {})).get("name", "?"))
                        for m in rec["events"]
                        if m.get("kind") == "marker"})
        out["newest"] = {"path": str(rec_dir), "manifest": rec["manifest"],
                         "markers": names}
        out["markers"] = names
    return out


class TelemetryServer:
    """One engine's HTTP ops surface; start with :meth:`start`, stop
    with :meth:`close`. ``port`` holds the bound port after start (pass
    0 for an ephemeral one — the bench and tests do)."""

    def __init__(self, hooks: TelemetryHooks, host: str = "127.0.0.1",
                 port: int = 0, token: str = ""):
        self.hooks = hooks
        self.host = host
        self.port = int(port)
        self.token = token or ""
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ lifecycle
    def start(self) -> int:
        """Bind + serve on a daemon thread; returns the bound port."""
        if self._httpd is not None:
            return self.port
        handler = _make_handler(self)
        httpd = ThreadingHTTPServer((self.host, self.port), handler)
        httpd.daemon_threads = True
        self._httpd = httpd
        self.port = int(httpd.server_address[1])
        self._thread = threading.Thread(
            target=httpd.serve_forever, name="dstpu-telemetry",
            daemon=True)
        self._thread.start()
        log_dist(f"telemetry server listening on "
                 f"http://{self.host}:{self.port}", ranks=[0])
        return self.port

    def close(self) -> None:
        """Shut the listener down (idempotent). Worker threads are
        daemonic; in-flight handlers finish or die with the process."""
        httpd, self._httpd = self._httpd, None
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    # ------------------------------------------------------------- renders
    def metrics_text(self) -> str:
        """The /metrics body — also the byte-compat oracle the tests
        compare against the textfile sink."""
        h = self.hooks
        if h.refresh_fn is not None:
            h.refresh_fn()
        step = int(h.step_fn()) if h.step_fn is not None else 0
        return exposition_from_events(h.registry.to_events(step), h.prefix)


def _make_handler(server: TelemetryServer):
    """Handler class closed over the server (BaseHTTPRequestHandler is
    instantiated per request by the socket server — state lives on the
    TelemetryServer)."""

    class Handler(BaseHTTPRequestHandler):
        # keep noisy per-request lines out of stderr; failures surface
        # through status codes and the engine's own logging
        def log_message(self, fmt, *args):   # noqa: D102
            pass

        # ------------------------------------------------------- plumbing
        def _send(self, code: int, body: bytes, ctype: str) -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _json(self, code: int, obj) -> None:
            self._send(code, json.dumps(obj, indent=2, default=str)
                       .encode("utf-8") + b"\n", _JSON)

        def _authorized(self) -> bool:
            """Control-POST gate: bearer token when configured, else
            loopback peers only (the server binds loopback by default;
            a re-bound server without a token still refuses remote
            control)."""
            if server.token:
                auth = self.headers.get("Authorization", "")
                tok = auth[len("Bearer "):] if auth.startswith("Bearer ") \
                    else self.headers.get("X-DSTPU-Token", "")
                return tok == server.token
            return self.client_address[0] in ("127.0.0.1", "::1")

        def _body_json(self) -> Optional[dict]:
            """POST body → dict; an EMPTY body is a valid {} (bare
            ``POST /drain`` / ``/flight/dump``), but a NON-EMPTY body
            that fails to parse returns None → 400. A garbled
            ``/slo/reload`` must not silently read as "disable SLOs",
            nor a garbled ``/drain {"end": true}`` as "begin"."""
            try:
                n = int(self.headers.get("Content-Length", 0) or 0)
            except ValueError:
                return None
            if n <= 0:
                return {}
            try:
                obj = json.loads(self.rfile.read(n).decode("utf-8"))
                return obj if isinstance(obj, dict) else None
            except (ValueError, UnicodeDecodeError):
                return None

        # ------------------------------------------------------------- GET
        def do_GET(self):   # noqa: N802 (http.server API)
            try:
                self._get()
            except BrokenPipeError:
                pass        # client went away mid-response; nothing to do
            except Exception as e:   # a handler bug must not kill the
                # listener thread — degrade to a 500 the scraper sees
                try:
                    self._json(500, {"error": repr(e)})
                except Exception:
                    return

        def _get(self):
            h = server.hooks
            parsed = urlparse(self.path)
            path = parsed.path.rstrip("/") or "/"
            if path == "/metrics":
                self._send(200, server.metrics_text().encode("utf-8"),
                           _PROM)
            elif path == "/healthz":
                health = h.health_fn() if h.health_fn is not None \
                    else {"alive": True}
                # liveness: the process is up and answering — 200 even
                # while degraded/draining (that's /readyz's business)
                self._json(200, {"alive": True, **health})
            elif path == "/readyz":
                health = h.health_fn() if h.health_fn is not None \
                    else {"ready": True}
                ready = bool(health.get("ready", True))
                self._json(200 if ready else 503, health)
            elif path == "/requests":
                if h.requests_fn is None:
                    self._json(404, {"error": "no request table "
                                              "(training engine?)"})
                else:
                    rows = h.requests_fn()
                    self._json(200, {"requests": rows,
                                     "in_flight": len(rows)})
            elif path == "/capacity":
                if h.capacity_fn is None:
                    self._json(404, {"error": "no capacity hook"})
                else:
                    q = parse_qs(parsed.query)
                    census = q.get("census", ["0"])[0] in ("1", "true")
                    self._json(200, h.capacity_fn(census))
            elif path == "/goodput":
                if h.goodput_fn is None:
                    self._json(404, {"error": "goodput ledger disabled "
                                              "(set goodput=True)"})
                else:
                    self._json(200, h.goodput_fn())
            elif path == "/flight":
                if h.flight_fn is None:
                    self._json(404, {"error": "no flight recorder "
                                              "configured"})
                else:
                    self._json(200, h.flight_fn())
            elif path == "/scaling":
                if h.scaling_fn is None:
                    self._json(404, {"error": "loadscope disabled "
                                              "(set serving.loadscope)"})
                else:
                    self._json(200, h.scaling_fn())
            elif path == "/tenants":
                if h.tenants_fn is None:
                    self._json(404, {"error": "tenantscope disabled "
                                              "(set serving.tenantscope)"})
                else:
                    self._json(200, h.tenants_fn())
            elif path == "/autoscale":
                if h.autoscale_fn is None:
                    self._json(404, {"error": "no autoscaler "
                                              "(set serving.autoscale)"})
                else:
                    self._json(200, h.autoscale_fn())
            elif path == "/trace":
                if h.trace_fn is None:
                    self._json(404, {"error": "no trace hook"})
                    return
                q = parse_qs(parsed.query)
                rid_s = q.get("rid", [None])[0]
                try:
                    rid = None if rid_s is None else int(rid_s)
                except ValueError:
                    self._json(400, {"error": f"bad rid {rid_s!r}"})
                    return
                obj = h.trace_fn(rid)
                if obj is None:
                    self._json(404, {"error":
                                     f"unknown rid {rid}" if rid is not None
                                     else "span ring disabled "
                                          "(set serving.spans)"})
                else:
                    self._json(200, obj)
            elif path == "/":
                eps = {"/metrics": h.registry is not None,
                       "/healthz": True, "/readyz": True,
                       "/requests": h.requests_fn is not None,
                       "/capacity": h.capacity_fn is not None,
                       "/goodput": h.goodput_fn is not None,
                       "/flight": h.flight_fn is not None,
                       "/scaling": h.scaling_fn is not None,
                       "/tenants": h.tenants_fn is not None,
                       "/autoscale": h.autoscale_fn is not None,
                       "/trace": h.trace_fn is not None,
                       "POST /drain": h.drain_fn is not None,
                       "POST /flight/dump": h.dump_fn is not None,
                       "POST /slo/reload": h.slo_reload_fn is not None,
                       "POST /autoscale":
                           h.autoscale_control_fn is not None}
                self._json(200, {"endpoints": {k: v for k, v in eps.items()
                                               if v}})
            else:
                self._json(404, {"error": f"unknown endpoint {path!r}"})

        # ------------------------------------------------------------ POST
        def do_POST(self):   # noqa: N802
            try:
                self._post()
            except BrokenPipeError:
                pass        # client went away mid-response; nothing to do
            except Exception as e:
                try:
                    self._json(500, {"error": repr(e)})
                except Exception:
                    return

        def _post(self):
            h = server.hooks
            path = urlparse(self.path).path.rstrip("/")
            if path not in ("/drain", "/flight/dump", "/slo/reload",
                            "/autoscale"):
                self._json(404, {"error": f"unknown endpoint {path!r}"})
                return
            if not self._authorized():
                self._json(403, {"error": "control endpoint: missing or "
                                          "wrong token (Authorization: "
                                          "Bearer <token>)"})
                return
            body = self._body_json()
            if body is None:
                self._json(400, {"error": "request body is not a JSON "
                                          "object (send {} or omit the "
                                          "body)"})
                return
            if path == "/drain":
                if h.drain_fn is None:
                    self._json(404, {"error": "no drain hook "
                                              "(training engine?)"})
                    return
                self._json(200, h.drain_fn(bool(body.get("end", False))))
            elif path == "/flight/dump":
                if h.dump_fn is None:
                    self._json(404, {"error": "no flight recorder "
                                              "configured"})
                    return
                d = h.dump_fn()
                self._json(200 if d is not None else 409,
                           {"dumped": d is not None,
                            "dir": None if d is None else str(d),
                            "why": None if d is not None else
                            "max_dumps reached (or recorder refused)"})
            elif path == "/slo/reload":
                if h.slo_reload_fn is None:
                    self._json(404, {"error": "no SLO machinery on this "
                                              "engine"})
                    return
                try:
                    self._json(200, h.slo_reload_fn(body))
                except (ValueError, TypeError) as e:
                    self._json(400, {"error": str(e)})
            elif path == "/autoscale":
                if h.autoscale_control_fn is None:
                    self._json(404, {"error": "no autoscaler "
                                              "(set serving.autoscale)"})
                    return
                try:
                    self._json(200, h.autoscale_control_fn(body))
                except (ValueError, TypeError) as e:
                    self._json(400, {"error": str(e)})

    return Handler
