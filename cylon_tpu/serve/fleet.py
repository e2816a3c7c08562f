"""Replicated serve fleet: N engine processes, one router, zero lost acks.

ROADMAP item 5(c): "millions of users" needs more than one
:class:`~cylon_tpu.serve.ServeEngine` — it needs N engine *processes*
behind a router that keeps serving when one of them dies. Every
prerequisite already exists: PR 7 made a single engine crash-safe
(fsync'd write-ahead journal, snapshot tables, exactly-once replay with
idempotency keys) and PR 14 shipped the router contract (the
``/health`` composite verdict, ``/metrics/window``, the cursored
``/events?since=`` journal). This module is the missing assembly — the
fleet — and its chaos proof: kill one engine mid-run, lose nothing.

Topology (see ``docs/serving.md`` → "A replicated serve fleet")::

                         FleetRouter (this module)
                  poll: /health + /events?since=<cursor>
                  submit: POST /submit → GET /result/<rid>
                 ┌────────────┴────────────┐
           engine process e0         engine process e1
           ServeEngine + gateway     ServeEngine + gateway
                 │                          │
            <root>/engines/e0/        <root>/engines/e1/
              journal.jsonl             journal.jsonl
              journal.lock              journal.lock
                 └────────── <root>/catalog-store ───────┘
                          (shared snapshot store)

The moving parts:

* **One durable dir tree** (:class:`FleetLayout`): per-engine journal
  subdirs (each fenced by its own
  :class:`~cylon_tpu.serve.durability.JournalLock` — a second live
  engine can never append to an owned journal) over ONE shared
  snapshot store (every engine registers the same resident tables, so
  the snapshots are content-identical and either engine's store
  recovers them).

* **An engine gateway** (:class:`EngineGateway`): the *write* half of
  the per-engine HTTP surface — ``POST /submit`` admits a registered
  named query (the replayable submission surface), ``GET
  /result/<rid>`` long-polls its outcome. The read half stays the PR 14
  introspection endpoint (``/health``, ``/events``, ``/metrics/window``
  — still statically read-only-linted); the gateway is a separate
  port so the diagnostic plane never grows a control surface.

* **The router** (:class:`FleetRouter`): admits requests with
  fleet-scoped idempotency keys, routes by tenant affinity over each
  engine's latest ``/health`` verdict, and polls every engine on a
  cursor loop (``/health`` + ``/events?since=`` + ``/metrics/window``)
  under the ``router_poll`` watchdog section with
  :func:`~cylon_tpu.resilience.retrying` backoff — transport failures
  classify as ``Code.Unavailable`` (:class:`EngineUnavailable`), i.e.
  retryable, until they aren't.

* **Failover**: an engine that fails ``CYLON_TPU_FLEET_FAIL_THRESHOLD``
  consecutive polls (or answers unhealthy/closing past
  ``CYLON_TPU_FLEET_DWELL`` seconds) is declared dead. The router then
  (1) **fences** its journal
  (:func:`~cylon_tpu.serve.durability.fence_journal` — a zombie's next
  append raises instead of racing the replay), (2) reads the dead
  journal's admitted-but-unresolved entries and **replays** them on a
  surviving peer with their ORIGINAL idempotency keys — exactly once,
  because keys dedup through both the router's ack cache and the
  peer's journal — and (3) re-points every affected
  :class:`RouterTicket` at its replacement, so a client blocked in
  ``result()`` just... gets its result. An acknowledged request is
  never lost (``fleet.lost_acks`` MUST stay 0); a retried one never
  double-executes.

Telemetry: ``fleet.routed{engine,tenant}``, ``fleet.failovers``,
``fleet.replayed``, ``fleet.lost_acks``, ``fleet.deduped``,
``fleet.events_gap{engine}`` counters plus ``failover``/``fence``/
``events_gap`` entries in the structured event journal.

Fleet tracing (ISSUE 20, armed by ``CYLON_TPU_TRACE`` like the local
flight recorder — the unarmed router mints nothing and pulls nothing):
every admitted request gets a ``trace_id`` minted at
:meth:`FleetRouter.submit` (the outermost entry), carried to the
engine as ``X-Cylon-Trace-Id``/``X-Cylon-Parent-Span`` headers on
``POST /submit`` — headers, not body kwargs, so the journaled replay
entry keeps the query's own arguments — and kept by a failover replay
(the journal entry records the id; the survivor re-runs under it with
a ``fleet.replay_hop`` marker). The poll loop additionally drains each
engine's cursored ``/trace?since=`` segments and estimates per-engine
clock offsets from the ``/ping`` wall stamp (midpoint method), so
:meth:`FleetRouter.fleet_trace_buffers` hands
:func:`cylon_tpu.telemetry.trace.merge_timelines` one aligned
router+engines timeline per run (the ``--fleet-trace`` bench leg's
Chrome trace artifact).

Knobs (``docs/serving.md`` knob table):

================================  =================================  =======
env                               meaning                            default
================================  =================================  =======
``CYLON_TPU_FLEET_POLL``          router poll interval (s)           ``0.5``
``CYLON_TPU_FLEET_FAIL_THRESHOLD``consecutive failed polls → dead    ``3``
``CYLON_TPU_FLEET_DWELL``         unhealthy/closing dwell (s) → dead ``5``
``CYLON_TPU_FLEET_PROBE_TIMEOUT`` per-probe HTTP timeout (s; a busy
                                  engine is not a dead engine)       ``30``
``CYLON_TPU_FLEET_LOCK_TTL``      journal-lock heartbeat TTL (s;
                                  ``0`` = pid-liveness only)         ``0``
================================  =================================  =======

Run one engine process (the fleet bench / chaos harness spawns these)::

    python -m cylon_tpu.serve.fleet --root /tmp/fleet --name e0 \\
        --sf 0.002 --mix q1,q3,q5,q6

The measured acceptance is ``python -m cylon_tpu.serve.bench --fleet
--clients 16``: two engine processes, SIGKILL one mid-run, and the
record (``BENCH_r09.json``) pins failovers ≥ 1, lost_acks == 0,
double_executions == 0 and the windowed p99 before/during/after the
kill.
"""

import argparse
import base64
import hashlib
import http.client
import itertools
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

from cylon_tpu import plan, resilience, telemetry, watchdog
from cylon_tpu.errors import (Code, CylonError, DataLossError,
                              DeadlineExceeded, InvalidArgument,
                              ResourceExhausted)
from cylon_tpu.serve.durability import RequestJournal, fence_journal
from cylon_tpu.serve.result_cache import (ResultCache,
                                          cache_bytes_from_env,
                                          hook_on_append)
from cylon_tpu.telemetry import events as _events
from cylon_tpu.telemetry import trace as _trace
from cylon_tpu.utils.logging import get_logger

__all__ = [
    "EngineUnavailable", "RemoteRequestFailed", "FleetLayout",
    "EngineGateway", "HttpEngineClient", "LocalEngineClient",
    "RouterTicket", "FleetRouter", "spawn_engine", "EngineProc",
    "run_fleet_bench", "encode_value", "decode_value",
]

#: default mixed workload for fleet engine processes (mirrors
#: serve.bench.DEFAULT_MIX without importing the bench at module load)
DEFAULT_MIX = ("q1", "q3", "q5", "q6", "q14")

#: per-query TPC-H read sets — the version-vector half of the result-
#: cache key each fleet engine declares at register_query time (ISSUE
#: 19). Precise sets mean precise invalidation: an orders append must
#: not evict a cached q1 (lineitem-only). A query not listed here
#: falls back to the FULL resident set — over-invalidation is merely
#: slower, under-invalidation would serve stale bytes.
QUERY_READ_SETS = {
    "q1": ("lineitem",),
    "q3": ("customer", "orders", "lineitem"),
    "q4": ("orders", "lineitem"),
    "q5": ("customer", "orders", "lineitem", "supplier", "nation",
           "region"),
    "q6": ("lineitem",),
    "q7": ("supplier", "lineitem", "orders", "customer", "nation"),
    "q10": ("customer", "orders", "lineitem", "nation"),
    "q12": ("orders", "lineitem"),
    "q14": ("lineitem", "part"),
    "q18": ("customer", "orders", "lineitem"),
    "q19": ("lineitem", "part"),
}


def _poll_interval() -> float:
    try:
        return float(os.environ.get("CYLON_TPU_FLEET_POLL", "0.5"))
    except ValueError:
        return 0.5


def _fail_threshold() -> int:
    try:
        return max(int(os.environ.get(
            "CYLON_TPU_FLEET_FAIL_THRESHOLD", "3")), 1)
    except ValueError:
        return 3


def _dwell() -> float:
    try:
        return float(os.environ.get("CYLON_TPU_FLEET_DWELL", "5"))
    except ValueError:
        return 5.0


class EngineUnavailable(CylonError):
    """An engine's HTTP surface could not be reached (connection
    refused, reset, timeout, or a 5xx from a dying process). Carries
    ``Code.Unavailable`` so :func:`cylon_tpu.resilience.is_retryable`
    classifies it retryable — the router retries with backoff, and only
    a run of consecutive exhausted retries declares the engine dead.

    ``refused`` is True when the transport failure was a connection
    REFUSAL — no listener, so the request provably never reached an
    admission path. That is the one transport failure a submit may
    re-route on unconditionally (a timeout/reset is ambiguous: the
    engine may have admitted the request before the connection died,
    so re-routing is only safe once the engine is declared dead and
    the failover replay dedups the key)."""

    code = Code.Unavailable
    refused = False


class RemoteRequestFailed(CylonError):
    """A fleet-routed request FAILED on its engine (the error is the
    request's outcome — the answer was delivered, just not the happy
    one). ``kind`` preserves the engine-side error class name."""

    def __init__(self, msg: str = "", kind: "str | None" = None):
        super().__init__(msg)
        self.kind = kind


# --------------------------------------------------------- value codec
def encode_value(v) -> dict:
    """JSON-able envelope for a query result crossing the gateway:
    pandas DataFrames (column-wise, dtype-tagged, datetimes as int64
    ns, bytes base64), numpy arrays, numpy/python scalars. Floats ride
    native JSON (repr round-trips exactly); non-finite floats encode as
    None."""
    import numpy as np

    try:
        import pandas as pd
    except ImportError:  # pragma: no cover - pandas is a hard dep here
        pd = None

    def _enc_float(x):
        # strict JSON has no Infinity/NaN tokens: tag non-finite
        # floats so decode restores them EXACTLY (inf must not come
        # back as NaN — or worse, None)
        if x != x:
            return {"__f__": "nan"}
        if x == float("inf"):
            return {"__f__": "inf"}
        if x == float("-inf"):
            return {"__f__": "-inf"}
        return x

    def _enc_item(x):
        if x is None:
            return None
        if isinstance(x, bytes):
            return {"__b64__": base64.b64encode(x).decode("ascii")}
        if isinstance(x, (str, bool, int)):
            return x
        if isinstance(x, float):
            return _enc_float(x)
        if isinstance(x, np.generic):
            return _enc_item(x.item())
        raise InvalidArgument(
            f"fleet result codec cannot encode {type(x).__name__}")

    def _enc_col(arr):
        if pd is not None and isinstance(arr, pd.Series):
            if isinstance(arr.dtype, pd.StringDtype):
                # a missing string is None on the wire, whatever NA
                # marker the pandas string dtype uses
                arr = arr.astype(object).where(arr.notna(), None)
            arr = arr.to_numpy()
        arr = np.asarray(arr)
        if np.issubdtype(arr.dtype, np.datetime64):
            return {"dtype": str(arr.dtype), "kind": "datetime",
                    "data": arr.astype("int64").tolist()}
        if arr.dtype != object and (
                np.issubdtype(arr.dtype, np.number)
                or arr.dtype == bool):
            data = arr.tolist()
            if np.issubdtype(arr.dtype, np.floating):
                data = [_enc_float(x) for x in data]
            return {"dtype": str(arr.dtype), "kind": "num",
                    "data": data}
        return {"dtype": "object", "kind": "obj",
                "data": [_enc_item(x) for x in arr.tolist()]}

    if pd is not None and isinstance(v, pd.DataFrame):
        return {"__fleet__": "frame", "columns": list(map(str, v.columns)),
                "cols": {str(c): _enc_col(v[c])
                         for c in v.columns}}
    if isinstance(v, np.ndarray):
        return {"__fleet__": "ndarray", "col": _enc_col(v)}
    return {"__fleet__": "scalar", "data": _enc_item(v)}


def decode_value(env: "dict | None"):
    """Inverse of :func:`encode_value`."""
    import numpy as np
    import pandas as pd

    if env is None:
        return None

    _SPECIALS = {"nan": float("nan"), "inf": float("inf"),
                 "-inf": float("-inf")}

    def _dec_item(x):
        if isinstance(x, dict):
            if "__b64__" in x:
                return base64.b64decode(x["__b64__"])
            if "__f__" in x:
                return _SPECIALS[x["__f__"]]
        return x

    def _dec_col(c):
        if c["kind"] == "datetime":
            return np.asarray(c["data"],
                              dtype="int64").astype(c["dtype"])
        if c["kind"] == "num":
            data = [_dec_item(x) for x in c["data"]]
            return np.asarray(data, dtype=np.dtype(c["dtype"]))
        # a Series keeps object dtype: pandas would otherwise infer its
        # string dtype and turn a missing string (None) into NaN
        return pd.Series([_dec_item(x) for x in c["data"]], dtype=object)

    kind = env.get("__fleet__")
    if kind == "frame":
        return pd.DataFrame({c: _dec_col(env["cols"][c])
                             for c in env["columns"]},
                            columns=env["columns"])
    if kind == "ndarray":
        return np.asarray(_dec_col(env["col"]))
    if kind == "scalar":
        return _dec_item(env.get("data"))
    raise InvalidArgument(f"unknown fleet value envelope {kind!r}")


# --------------------------------------------------------- layout
class FleetLayout:
    """The shared durable dir tree: per-engine journal subdirs under
    ``<root>/engines/<name>/`` (each with its own lockfile fence) plus
    ONE shared snapshot store at ``<root>/catalog-store`` — every
    engine registers the same resident tables, so the snapshots are
    content-identical and dedup on disk."""

    def __init__(self, root: str):
        self.root = str(root)

    @property
    def engines_root(self) -> str:
        return os.path.join(self.root, "engines")

    def engine_dir(self, name: str) -> str:
        return os.path.join(self.engines_root, str(name))

    @property
    def snapshot_dir(self) -> str:
        return os.path.join(self.root, "catalog-store")

    def engine_names(self) -> "list[str]":
        try:
            return sorted(os.listdir(self.engines_root))
        except OSError:
            return []


def snapshot_generations(root: str) -> "dict[str, int]":
    """Per-table generation stamps in the fleet's SHARED snapshot
    store (ISSUE 18 fix): every engine's ``register_table``/
    ``append_table`` writes its catalog generation into the snapshot
    map, so a failover target's :meth:`ServeEngine.recover` — and this
    router-side audit — sees the POST-append generation, not a
    silently stale one. Reads the store at ``<root>/catalog-store``;
    tables snapshotted before the versioning era are absent."""
    from cylon_tpu.serve.durability import CatalogSnapshot

    return CatalogSnapshot(FleetLayout(root).snapshot_dir).generations()


# --------------------------------------------------------- gateway
class EngineGateway:
    """The per-engine-process submission surface the router talks to.

    Deliberately separate from the read-only introspection endpoint
    (``serve/introspect.py`` stays statically linted as having no
    mutating calls): ``POST /submit`` admits one REGISTERED named query
    through the engine's public :meth:`~ServeEngine.submit_named` —
    which means every gateway admission is write-ahead journaled,
    idempotency-key deduped and SLO-stamped exactly like a local one —
    and ``GET /result/<rid>`` long-polls the ticket's outcome. Loopback
    only, like the introspection port."""

    def __init__(self, engine, port: int = 0):
        import http.server

        self._engine = engine
        outer = self

        class _Handler(http.server.BaseHTTPRequestHandler):
            server_version = "cylon-tpu-fleet-gateway"
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                get_logger().debug("gateway: " + fmt, *args)

            def _reply(self, code: int, payload: dict) -> None:
                body = json.dumps(telemetry.json_safe(payload),
                                  allow_nan=False).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 - stdlib handler name
                try:
                    outer._get(self)
                except BrokenPipeError:
                    pass
                except Exception as e:  # never kill the server thread
                    try:
                        self._reply(500, {
                            "error": f"{type(e).__name__}: {e}",
                            "kind": type(e).__name__})
                    except Exception:
                        pass

            def do_POST(self):  # noqa: N802 - stdlib handler name
                try:
                    outer._post(self)
                except BrokenPipeError:
                    pass
                except Exception as e:
                    try:
                        self._reply(500, {
                            "error": f"{type(e).__name__}: {e}",
                            "kind": type(e).__name__})
                    except Exception:
                        pass

        self._httpd = http.server.ThreadingHTTPServer(
            ("127.0.0.1", int(port)), _Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="cylon-fleet-gateway", daemon=True)
        self._thread.start()

    @property
    def address(self) -> "tuple[str, int]":
        return self._httpd.server_address[:2]

    @property
    def url(self) -> str:
        host, port = self.address
        return f"http://{host}:{port}"

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)

    # ------------------------------------------------------- handlers
    def _get(self, h) -> None:
        import urllib.parse

        path, _, query = h.path.partition("?")
        qs = urllib.parse.parse_qs(query)
        eng = self._engine
        if path == "/ping":
            h._reply(503 if eng.closing else 200,
                     {"ok": not eng.closing, "closing": eng.closing,
                      "live": eng.live,
                      # wall-clock stamp for the router's clock-offset
                      # handshake (midpoint method): offset =
                      # ts - (t0 + t1)/2 around the probe
                      "ts": time.time()})
            return
        if path.startswith("/result/"):
            rid = path.rsplit("/", 1)[1]
            ticket = eng.ticket(int(rid)) if rid.isdigit() else None
            if ticket is None:
                h._reply(404, {"error": f"unknown rid {rid!r}",
                               "kind": "NotFound"})
                return
            try:
                wait_s = min(float(qs.get("timeout", ["0"])[0]), 60.0)
            except ValueError:
                wait_s = 0.0
            if wait_s > 0:
                ticket.wait(wait_s)
            if not ticket.done:
                h._reply(200, {"state": "running",
                               "rid": ticket.rid})
                return
            if ticket.error is not None:
                h._reply(200, {
                    "state": "failed", "rid": ticket.rid,
                    "error": str(ticket.error),
                    "kind": type(ticket.error).__name__})
                return
            h._reply(200, {"state": "done", "rid": ticket.rid,
                           "value": encode_value(ticket.value),
                           # the (fingerprint, version-vector) the
                           # engine published this result under —
                           # None when uncacheable; the router's
                           # fleet-scoped cache keys on it verbatim
                           "cache_key": getattr(ticket, "cache_key",
                                                None)})
            return
        h._reply(404, {"error": f"unknown path {path!r}",
                       "kind": "NotFound"})

    def _post(self, h) -> None:
        eng = self._engine
        if h.path.partition("?")[0] != "/submit":
            h._reply(404, {"error": f"unknown path {h.path!r}",
                           "kind": "NotFound"})
            return
        if eng.closing:
            h._reply(503, {"error": "engine closing",
                           "kind": "Unavailable"})
            return
        try:
            n = int(h.headers.get("Content-Length", "0"))
            body = json.loads(h.rfile.read(n) or b"{}")
        except ValueError as e:
            h._reply(400, {"error": f"malformed submit body: {e}",
                           "kind": "InvalidArgument"})
            return
        # the fleet trace context crosses the process hop as HTTP
        # headers, never as body kwargs — the journal must record the
        # query's OWN kwargs so a replay's fingerprint still matches.
        # submit_named strips the _trace_* control keywords before
        # fingerprinting for the same reason.
        tid = h.headers.get("X-Cylon-Trace-Id")
        parent = h.headers.get("X-Cylon-Parent-Span")
        if parent is not None and parent.isdigit():
            parent = int(parent)
        try:
            ticket = eng.submit_named(
                str(body["name"]), *body.get("args", ()),
                idempotency_key=body.get("key"),
                tenant=body.get("tenant", "default"),
                priority=int(body.get("priority", 1)),
                slo=body.get("slo"),
                tables=body.get("tables", ()),
                _trace_id=tid, _parent_span=parent,
                **body.get("kwargs", {}))
        except ResourceExhausted as e:
            h._reply(429, {"error": str(e),
                           "kind": "ResourceExhausted"})
            return
        except (InvalidArgument, KeyError) as e:
            h._reply(400, {"error": str(e),
                           "kind": type(e).__name__})
            return
        h._reply(200, {"rid": ticket.rid, "state": ticket.state,
                       "tenant": ticket.tenant})


# --------------------------------------------------------- clients
class HttpEngineClient:
    """The router's handle on one engine PROCESS: the gateway port for
    submit/result, the introspection port for /health, /events and
    /metrics/window. Every transport failure maps to
    :class:`EngineUnavailable` (``Code.Unavailable`` — retryable)."""

    def __init__(self, name: str, gateway_url: str,
                 introspect_url: "str | None" = None,
                 durable_dir: "str | None" = None,
                 pid: "int | None" = None,
                 probe_timeout: "float | None" = None):
        self.name = str(name)
        self.gateway_url = gateway_url.rstrip("/")
        self.introspect_url = (introspect_url.rstrip("/")
                               if introspect_url else None)
        self.durable_dir = durable_dir
        self.pid = pid
        # a BUSY engine is not a dead engine: on a saturated host the
        # GIL can starve the HTTP threads for seconds, so probes get a
        # generous timeout — a real kill still detects instantly
        # (connection refused), and a wedged-but-listening engine is
        # the unhealthy-dwell / lock-TTL path's job, not this one's
        if probe_timeout is None:
            try:
                probe_timeout = float(os.environ.get(
                    "CYLON_TPU_FLEET_PROBE_TIMEOUT", "30"))
            except ValueError:
                probe_timeout = 30.0
        self.probe_timeout = probe_timeout

    def _request(self, url: str, data: "bytes | None" = None,
                 timeout: float = 10.0,
                 headers: "dict | None" = None) -> dict:
        hdrs = {"Content-Type": "application/json"} if data else {}
        if headers:
            hdrs.update(headers)
        req = urllib.request.Request(url, data=data, headers=hdrs)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                payload = json.loads(e.read())
            except Exception:
                payload = {"error": str(e), "kind": "HTTPError"}
            if e.code == 503:
                # a clean "closing"/unavailable verdict, not a crash
                payload.setdefault("status", "closing")
                payload["http_status"] = 503
                return payload
            if e.code == 429:
                raise ResourceExhausted(payload.get("error", str(e)))
            if e.code in (400, 404, 409):
                raise InvalidArgument(payload.get("error", str(e)))
            if "kind" in payload:
                # the GATEWAY's error envelope: the engine is alive
                # and answered — an application-level failure (e.g. a
                # result the codec cannot encode) must not read as
                # engine death and trip a failover
                raise RemoteRequestFailed(
                    f"engine {self.name!r} request failed: "
                    f"{payload.get('error', '')}",
                    kind=payload.get("kind"))
            raise EngineUnavailable(
                f"engine {self.name!r} answered HTTP {e.code}: "
                f"{payload.get('error', '')}")
        except (urllib.error.URLError, ConnectionError, OSError,
                TimeoutError, http.client.HTTPException) as e:
            # includes IncompleteRead/RemoteDisconnected: the process
            # died (or was SIGKILLed) mid-response — Unavailable, the
            # retryable transport class
            reason = getattr(e, "reason", e)
            exc = EngineUnavailable(
                f"engine {self.name!r} unreachable at {url}: "
                f"{type(e).__name__}: {e}")
            exc.refused = isinstance(reason, ConnectionRefusedError)
            raise exc

    # ------------------------------------------------- router surface
    def submit(self, name: str, args=(), kwargs=None,
               tenant: str = "default", priority: int = 1,
               slo=None, key: "str | None" = None,
               tables=(), trace_id: "str | None" = None,
               parent_span=None) -> int:
        body = {"name": name, "args": list(args),
                "kwargs": dict(kwargs or {}), "tenant": tenant,
                "priority": priority, "slo": slo, "key": key,
                "tables": list(tables)}
        headers = {}
        if trace_id is not None:
            headers["X-Cylon-Trace-Id"] = str(trace_id)
            if parent_span is not None:
                headers["X-Cylon-Parent-Span"] = str(parent_span)
        out = self._request(self.gateway_url + "/submit",
                            data=json.dumps(body).encode(),
                            timeout=max(self.probe_timeout, 10.0),
                            headers=headers or None)
        if "rid" not in out:
            raise EngineUnavailable(
                f"engine {self.name!r} refused submit: {out}")
        return int(out["rid"])

    def result(self, rid: int, timeout: float = 5.0) -> dict:
        return self._request(
            f"{self.gateway_url}/result/{int(rid)}?timeout={timeout}",
            timeout=timeout + max(self.probe_timeout, 10.0))

    def health(self) -> dict:
        base = self.introspect_url or self.gateway_url
        path = "/health" if self.introspect_url else "/ping"
        return self._request(base + path, timeout=self.probe_timeout)

    def events_since(self, cursor: int = 0) -> dict:
        if self.introspect_url is None:
            return {"events": [], "cursor": int(cursor), "dropped": 0,
                    "armed": False}
        return self._request(
            f"{self.introspect_url}/events?since={int(cursor)}",
            timeout=self.probe_timeout)

    def trace_since(self, cursor: int = 0) -> dict:
        """The engine's cursored ``/trace?since=`` span segment (same
        payload discipline as :meth:`events_since`)."""
        if self.introspect_url is None:
            return {"events": [], "cursor": int(cursor), "dropped": 0,
                    "armed": False}
        return self._request(
            f"{self.introspect_url}/trace?since={int(cursor)}",
            timeout=self.probe_timeout)

    def ping(self) -> dict:
        """Raw gateway liveness reply — carries the engine's wall
        ``ts`` for the router's clock-offset handshake."""
        return self._request(self.gateway_url + "/ping",
                             timeout=self.probe_timeout)

    def metrics_window(self, window: "float | None" = None) -> dict:
        if self.introspect_url is None:
            return {}
        q = f"?window={window}" if window else ""
        return self._request(
            self.introspect_url + "/metrics/window" + q,
            timeout=self.probe_timeout)


class LocalEngineClient:
    """The same client interface over an IN-PROCESS engine — the fleet
    logic is identical whether the engine is a process or an object,
    which is what lets the router's routing/failover machinery unit-
    test without interpreter spawns. Talks only through the engine's
    public API (the bench-guard lint pins that for this whole
    module)."""

    def __init__(self, engine, name: str,
                 durable_dir: "str | None" = None):
        self.engine = engine
        self.name = str(name)
        self.durable_dir = durable_dir or engine.durable_dir
        self.pid = os.getpid()

    def submit(self, name: str, args=(), kwargs=None,
               tenant: str = "default", priority: int = 1,
               slo=None, key: "str | None" = None,
               tables=(), trace_id: "str | None" = None,
               parent_span=None) -> int:
        if self.engine.closing:
            e = EngineUnavailable(
                f"engine {self.name!r} is closing")
            e.refused = True  # nothing admitted: safe to re-route
            raise e
        t = self.engine.submit_named(
            name, *args, idempotency_key=key, tenant=tenant,
            priority=priority, slo=slo, tables=tables,
            _trace_id=trace_id, _parent_span=parent_span,
            **(kwargs or {}))
        return t.rid

    def result(self, rid: int, timeout: float = 5.0) -> dict:
        t = self.engine.ticket(rid)
        if t is None:
            raise EngineUnavailable(
                f"engine {self.name!r} lost rid {rid}")
        t.wait(timeout)
        if not t.done:
            return {"state": "running", "rid": rid}
        if t.error is not None:
            return {"state": "failed", "rid": rid,
                    "error": str(t.error),
                    "kind": type(t.error).__name__}
        return {"state": "done", "rid": rid,
                "value": encode_value(t.value),
                "cache_key": getattr(t, "cache_key", None)}

    def health(self) -> dict:
        if self.engine.closing:
            return {"status": "closing"}
        return self.engine.health()

    def events_since(self, cursor: int = 0) -> dict:
        return _events.since(cursor)

    def trace_since(self, cursor: int = 0) -> dict:
        return _trace.since(cursor)

    def ping(self) -> dict:
        # in-process: same clock as the router, so the handshake's
        # midpoint estimate converges on ~0 offset
        return {"ok": not self.engine.closing,
                "closing": self.engine.closing, "ts": time.time()}

    def metrics_window(self, window: "float | None" = None) -> dict:
        from cylon_tpu.telemetry import timeseries

        return timeseries.window_view(window)


# --------------------------------------------------------- router
def _affinity_order(tenant: str, names: "list[str]") -> "list[str]":
    """Deterministic tenant-affinity ring: the tenant's md5 picks a
    starting engine, failures walk the ring. Stable across processes
    (no PYTHONHASHSEED dependence) so a router restart keeps the same
    placement."""
    names = sorted(names)
    if not names:
        return []
    h = int.from_bytes(
        hashlib.md5(str(tenant).encode()).digest()[:4], "big")
    k = h % len(names)
    return names[k:] + names[:k]


class _EngineState:
    """Router-side view of one engine."""

    def __init__(self, client):
        self.client = client
        self.name = client.name
        self.verdict: "dict | None" = None
        self.status = "unknown"
        self.failures = 0          # consecutive failed polls
        self.unhealthy_since: "float | None" = None
        self.dead = False
        self.last_window: "dict | None" = None
        self.events_seen = 0
        # fleet tracing (ISSUE 20): the engine's pulled /trace segment
        # stream (cursored, bounded like the source ring) plus the
        # ping-handshake clock estimate — all idle until the router's
        # recorder is armed
        self.trace_cursor = 0
        self.trace_events: list = []
        self.trace_dropped = 0
        self.clock_offset: "float | None" = None
        self.offset_jitter: "float | None" = None

    def snapshot(self) -> dict:
        return {"name": self.name, "status": self.status,
                "dead": self.dead, "failures": self.failures,
                "events_seen": self.events_seen}


class RouterTicket:
    """The fleet-level future: survives the engine it was first routed
    to. ``result()`` long-polls the current assignment and, when a
    failover re-points the ticket at a peer, simply keeps polling
    there — the client never sees the swap."""

    def __init__(self, router: "FleetRouter", key: str, name: str,
                 tenant: str):
        self._router = router
        self.key = key
        self.name = name
        self.tenant = tenant
        self._cv = threading.Condition()
        self._client = None
        self.rid: "int | None" = None
        self._lost: "str | None" = None
        self.submitted = time.monotonic()
        #: the fleet trace id minted for this request (None unarmed) —
        #: one id names the whole causal chain, failover hops included
        self.trace_id: "str | None" = None

    @property
    def engine(self) -> "str | None":
        with self._cv:
            return None if self._client is None else self._client.name

    def _assign(self, client, rid: int) -> None:
        # dead-ness checked OUTSIDE _cv (router lock ordering: never
        # _cv → _mu): a failover replay may have already re-pointed
        # this ticket at a live peer while our submit thread was
        # descheduled — the stale assignment to the now-dead engine
        # must not overwrite it (result() would poll a corpse forever)
        new_dead = self._router._is_dead(getattr(client, "name", None))
        with self._cv:
            if self._client is not None and new_dead:
                return
            self._client, self.rid = client, int(rid)
            self._cv.notify_all()

    def _mark_lost(self, why: str) -> None:
        """Declare this acknowledged request LOST. The ONE place the
        per-ticket ``fleet.lost_acks`` count happens (once per ticket,
        however many threads observe the loss)."""
        with self._cv:
            if self._lost is not None:
                return
            self._lost = why
            self._cv.notify_all()
        telemetry.counter("fleet.lost_acks",
                          tenant=self.tenant).inc()

    def result(self, timeout: "float | None" = None):
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while True:
            done, value = self._router._acked(self.key)
            if done:
                return value
            failed = self._router._failure(self.key)
            if failed is not None:
                raise RemoteRequestFailed(
                    f"request {self.key!r} failed on engine "
                    f"{failed['engine']}: {failed['error']}",
                    kind=failed["kind"])
            with self._cv:
                if self._lost is not None:
                    # counted once at _mark_lost time, not per waiter
                    raise DataLossError(
                        f"acknowledged request {self.key!r} was LOST: "
                        f"{self._lost}")
                client, rid = self._client, self.rid
            remaining = (None if deadline is None
                         else deadline - time.monotonic())
            if remaining is not None and remaining <= 0:
                raise DeadlineExceeded(
                    f"result({timeout=}) timed out waiting on fleet "
                    f"request {self.key!r}", section="router_poll",
                    retryable=True)
            chunk = 5.0 if remaining is None else min(remaining, 5.0)
            if client is None:  # awaiting failover reassignment
                with self._cv:
                    if self._client is None and self._lost is None:
                        self._cv.wait(min(chunk, 0.25))
                continue
            try:
                res = client.result(rid, timeout=chunk)
            except EngineUnavailable:
                # the engine died under us: tell the router (counts
                # toward its failure threshold) and wait for either a
                # reassignment or a lost verdict
                self._router._note_failure(client.name,
                                           reason="result_poll")
                with self._cv:
                    if self._client is client and self._lost is None:
                        self._cv.wait(0.25)
                continue
            state = res.get("state")
            if state == "done":
                value = decode_value(res.get("value"))
                self._router._store_result(res.get("cache_key"),
                                           res.get("value"))
                self._router._record_ack(self.key, value)
                return value
            if state == "failed":
                self._router._record_failure(
                    self.key, engine=client.name,
                    error=res.get("error", ""),
                    kind=res.get("kind", "Error"))
                raise RemoteRequestFailed(
                    f"request {self.key!r} failed on engine "
                    f"{client.name}: {res.get('error', '')}",
                    kind=res.get("kind"))
            # running (or a 503 "closing" envelope): poll again


class FleetRouter:
    """Tenant-affinity + health-verdict routing over N engines, with
    journal-replay failover (module docstring). ``clients`` is any mix
    of :class:`HttpEngineClient` (engine processes) and
    :class:`LocalEngineClient` (in-process engines — tests)."""

    _ids = itertools.count(1)

    def __init__(self, clients, poll_interval: "float | None" = None,
                 fail_threshold: "int | None" = None,
                 unhealthy_dwell: "float | None" = None,
                 retry_policy=None, start: bool = True):
        clients = list(clients)
        if len({c.name for c in clients}) != len(clients):
            raise InvalidArgument("engine names must be unique")
        self._mu = threading.RLock()
        self._states = {c.name: _EngineState(c) for c in clients}
        self._cursors = {c.name: 0 for c in clients}
        # fleet tracing arms off the SAME env as the local recorder:
        # one check at construction — an unarmed router never mints
        # ids, opens spans, handshakes clocks or pulls /trace
        self._trace_armed = _trace.enabled()
        self.poll_interval = (poll_interval if poll_interval is not None
                              else _poll_interval())
        self.fail_threshold = (fail_threshold
                               if fail_threshold is not None
                               else _fail_threshold())
        self.unhealthy_dwell = (unhealthy_dwell
                                if unhealthy_dwell is not None
                                else _dwell())
        self._retry_policy = retry_policy
        # the FLEET-scoped versioned result cache (ISSUE 19): keyed
        # exactly like the engine-side cache — (query fingerprint,
        # table-version vector) — but holding the ENCODED value
        # envelopes the gateways reply with, so a hit on any engine
        # serves every engine, and the cache survives the engine the
        # result first ran on. The router only learns a key from a
        # done reply's ``cache_key`` (it cannot version remote
        # tables itself), so ``_vv_by_fp`` maps fingerprint -> the
        # last vector an engine answered with; a stale mapping can
        # only cause a MISS (the entry under the old vector was
        # already invalidated), never a stale hit.
        self._result_cache = hook_on_append(ResultCache(
            cache_bytes_from_env("CYLON_TPU_FLEET_RESULT_CACHE_BYTES"),
            metric_prefix="fleet"))
        self._vv_by_fp: "dict[str, tuple]" = {}
        self._tickets: "dict[str, RouterTicket]" = {}
        self._acks: "dict[str, object]" = {}
        self._failures: "dict[str, dict]" = {}
        self._replayed_keys: "list[str]" = []
        self._failovers: "list[dict]" = []
        self._kseq = itertools.count(1)
        self._stop = threading.Event()
        #: ONE poll thread per engine: a hung-but-listening engine
        #: (probe timeouts eat retries × probe_timeout per tick) must
        #: not head-of-line-block the detection of every OTHER
        #: engine's death
        self._pollers: "dict[str, threading.Thread]" = {}
        if start:
            self.start()

    # ------------------------------------------------------- lifecycle
    def start(self) -> None:
        with self._mu:
            for name in self._states:
                th = self._pollers.get(name)
                if th is not None and th.is_alive():
                    continue
                th = threading.Thread(
                    target=self._poll_loop, args=(name,),
                    name=f"cylon-fleet-poll-{name}", daemon=True)
                self._pollers[name] = th
                th.start()

    def close(self) -> None:
        """Stop the poll loops (the engines belong to their owner)."""
        self._stop.set()
        for th in list(self._pollers.values()):
            th.join(timeout=5)

    def __enter__(self) -> "FleetRouter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------- routing
    def engines(self) -> "list[dict]":
        with self._mu:
            return [s.snapshot() for s in self._states.values()]

    def _eligible_locked(self) -> "list[_EngineState]":
        """Routable engines, best verdict first: ``ok`` engines, then
        ``degraded``, then never-polled ``unknown`` (optimistic — a
        just-started fleet must route before the first poll lands).
        ``unhealthy``/``closing``/dead engines never route."""
        rank = {"ok": 0, "degraded": 1, "unknown": 2}
        out = [s for s in self._states.values()
               if not s.dead and s.status in rank]
        out.sort(key=lambda s: (rank[s.status], s.name))
        return out

    def _pick_locked(self, tenant: str,
                     exclude=frozenset()) -> "_EngineState":
        eligible = [s for s in self._eligible_locked()
                    if s.name not in exclude]
        if not eligible:
            raise EngineUnavailable(
                f"no routable engine in the fleet (states: "
                f"{[s.snapshot() for s in self._states.values()]})")
        # route within the best-status tier only (an ok engine always
        # beats a degraded one); the tenant's affinity ring breaks ties
        order = ("ok", "degraded", "unknown")
        best_rank = min(order.index(s.status) for s in eligible)
        tier = {s.name: s for s in eligible
                if order.index(s.status) == best_rank}
        name = _affinity_order(tenant, list(tier))[0]
        return tier[name]

    def submit(self, name: str, *args, tenant: str = "default",
               idempotency_key: "str | None" = None,
               priority: int = 1, slo=None, tables=(),
               **kwargs) -> RouterTicket:
        """Admit one named query into the fleet. ``idempotency_key``
        is FLEET-scoped: a key the router has already acked returns
        the cached result's ticket (no engine is touched — the dedup
        survives the engine the original ran on); an unknown key is
        stamped on the engine-side journal, so a failover replay and a
        client retry can never both execute. Keys are generated when
        the client brings none (the replay path needs one).

        When tracing is armed this is the request's OUTERMOST entry:
        it mints the ``trace_id``, opens the router-side
        ``fleet.submit`` span, and hands both across the HTTP hop —
        the engine's first span links back here via ``parent_span``."""
        if not self._trace_armed:
            return self._submit_routed(
                name, args, kwargs, tenant=tenant,
                idempotency_key=idempotency_key, priority=priority,
                slo=slo, tables=tables, trace_id=None,
                parent_span=None)
        trace_id = _trace.new_trace_id()
        with _trace.trace_context(trace_id):
            tok = _trace.begin("fleet.submit", cat="fleet",
                               query=str(name), tenant=str(tenant))
            try:
                return self._submit_routed(
                    name, args, kwargs, tenant=tenant,
                    idempotency_key=idempotency_key,
                    priority=priority, slo=slo, tables=tables,
                    trace_id=trace_id,
                    parent_span=tok[0] if tok else None)
            finally:
                _trace.end(tok)

    def _submit_routed(self, name, args, kwargs, *, tenant,
                       idempotency_key, priority, slo, tables,
                       trace_id, parent_span) -> RouterTicket:
        key = idempotency_key or \
            f"fleet-{os.getpid()}-{next(self._kseq)}"
        with self._mu:
            existing = self._tickets.get(key)
            if existing is not None:
                telemetry.counter("fleet.deduped",
                                  tenant=tenant).inc()
                return existing
            ticket = RouterTicket(self, key, name, tenant)
            ticket.trace_id = trace_id
            self._tickets[key] = ticket
        # fleet-scoped cache check BEFORE any engine is touched: the
        # fingerprint is computed router-side (same canonical JSON the
        # engines hash), the version vector is the one an engine last
        # answered this fingerprint with — an append anywhere in the
        # fleet invalidated the entry under it, so a hit is provably
        # current. The hit resolves through the ack ledger, exactly
        # like a delivered result (0 engine round-trips).
        if self._result_cache.enabled:
            fp = plan.query_fingerprint(name, args, kwargs)
            if fp is not None:
                with self._mu:
                    vv = self._vv_by_fp.get(fp)
                hit, env = self._result_cache.lookup(fp, vv)
                if hit:
                    self._record_ack(key, decode_value(env))
                    return ticket
        # a submit that lands in an engine's death window (killed but
        # not yet declared dead — _pick_locked can still select it)
        # walks the affinity ring to the next peer instead of erroring
        # the client. Re-routing with the SAME key is safe ONLY when
        # the first attempt provably did not execute: a connection
        # REFUSAL (no listener — nothing was admitted), or an engine
        # since declared DEAD (if it did journal the admit, the
        # failover replay dedups the key). An ambiguous failure
        # against a live engine (timeout while it grinds) must raise
        # instead — the engine may be executing the request, and a
        # same-key resubmission to a peer would genuinely run twice.
        tried: set = set()
        while True:
            try:
                with self._mu:
                    st = self._pick_locked(tenant, exclude=tried)
            except EngineUnavailable:
                with self._mu:
                    self._tickets.pop(key, None)
                raise
            try:
                rid = st.client.submit(
                    name, args=args, kwargs=kwargs, tenant=tenant,
                    priority=priority, slo=slo, key=key,
                    tables=tables, trace_id=trace_id,
                    parent_span=parent_span)
            except EngineUnavailable as e:
                self._note_failure(st.name, reason="submit")
                if not (getattr(e, "refused", False)
                        or self._is_dead(st.name)):
                    with self._mu:
                        self._tickets.pop(key, None)
                    raise
                tried.add(st.name)
                get_logger().warning(
                    "fleet: submit of %r to %r failed (%s); "
                    "re-routing", key, st.name, e)
                continue
            except BaseException:
                with self._mu:
                    self._tickets.pop(key, None)
                raise
            break
        ticket._assign(st.client, rid)
        telemetry.counter("fleet.routed", engine=st.name,
                          tenant=tenant).inc()
        return ticket

    # ------------------------------------------------------- acks
    def _record_ack(self, key: str, value) -> None:
        with self._mu:
            self._acks[key] = value

    def _store_result(self, cache_key: "dict | None", env) -> None:
        """Publish one delivered result envelope into the fleet cache
        under the ``(fingerprint, version-vector)`` the ENGINE stamped
        on it (an engine only stamps a key when its read set was still
        at the admitted versions at retirement — the staleness guard
        already ran there). Local-fleet belt-and-braces: when the
        router process itself holds a vector table (in-process
        engines share the catalog), a version that moved since the
        stamp drops the store instead of publishing a dead entry."""
        if not cache_key or not self._result_cache.enabled:
            return
        fp = cache_key.get("fingerprint")
        vv = tuple(tuple(v) for v in cache_key.get("versions", ()))
        if fp is None or not vv:
            return
        from cylon_tpu import catalog
        from cylon_tpu.errors import KeyError_

        for tid, gen, dig in vv:
            try:
                cur = catalog.table_version(str(tid))
            except (KeyError, KeyError_):
                continue  # remote table: /events invalidation governs
            if (int(cur["generation"]) != int(gen)
                    or str(cur["digest"]) != str(dig)):
                return
        self._result_cache.store(fp, vv, env)
        with self._mu:
            self._vv_by_fp[fp] = vv

    def _acked(self, key: str) -> "tuple[bool, object]":
        with self._mu:
            if key in self._acks:
                return True, self._acks[key]
        return False, None

    def _record_failure(self, key: str, engine: str, error: str,
                        kind: str) -> None:
        with self._mu:
            self._failures[key] = {"engine": engine, "error": error,
                                   "kind": kind}

    def _failure(self, key: str) -> "dict | None":
        with self._mu:
            return self._failures.get(key)

    # ------------------------------------------------------- polling
    def _poll_loop(self, name: str) -> None:
        st = self._states[name]
        while not self._stop.is_set():
            if st.dead:
                return  # DEAD is terminal; nothing left to watch
            self._poll_one(st)
            self._stop.wait(self.poll_interval)

    def _poll_one(self, st: "_EngineState") -> None:
        """One cursor-loop tick against one engine: the /health
        verdict (with retry/backoff — transport errors are
        ``Code.Unavailable``), the /events cursor advance, and the
        windowed metrics view, all inside the ``router_poll`` watchdog
        section."""
        with watchdog.watched_section("router_poll", detail=st.name):
            try:
                verdict = resilience.retrying(
                    st.client.health, self._retry_policy,
                    label=f"router_poll[{st.name}]")
            except Exception:
                self._note_failure(st.name, reason="health_poll")
                return
            try:
                ev = st.client.events_since(self._cursors[st.name])
                self._cursors[st.name] = ev.get(
                    "cursor", self._cursors[st.name])
                st.events_seen += len(ev.get("events", ()))
                gap = int(ev.get("dropped", 0) or 0)
                if gap:
                    # the engine's journal ring evicted entries before
                    # this poll read them — the router fell behind.
                    # Counted per engine and journaled, never silent:
                    # a gap can hide an append (stale fleet cache) or
                    # a replayed admit.
                    telemetry.counter("fleet.events_gap",
                                      engine=st.name).inc(gap)
                    _events.emit("events_gap", engine=st.name,
                                 dropped=gap)
                # fleet-cache invalidation rides the same cursor: an
                # append ANY engine journals evicts exactly the cached
                # results whose version vector read that table (for
                # in-process fleets the catalog hook already fired —
                # re-invalidating an evicted table is a no-op)
                for e in ev.get("events", ()):
                    if e.get("kind") == "append" and e.get("table"):
                        self._result_cache.invalidate_table(
                            e["table"])
                if self._trace_armed:
                    self._pull_trace(st)
                st.last_window = st.client.metrics_window()
            except Exception:
                # the health verdict landed; a flaky events/window read
                # alone is not a liveness failure
                pass
        now = time.monotonic()
        with self._mu:
            st.verdict = verdict
            st.status = verdict.get("status", "unknown")
            st.failures = 0
            if st.status in ("unhealthy", "closing"):
                if st.unhealthy_since is None:
                    st.unhealthy_since = now
                dwell = now - st.unhealthy_since
            else:
                st.unhealthy_since = None
                dwell = 0.0
        if dwell > self.unhealthy_dwell:
            self._fail_over(st.name,
                            reason=f"{st.status}_past_dwell")

    def _pull_trace(self, st: "_EngineState") -> None:
        """Advance one engine's ``/trace`` cursor: append its new span
        segment to the router-side buffer (bounded like the source
        ring, same eviction-means-gap accounting) and, once per
        engine, estimate the clock offset from a ping handshake."""
        if st.clock_offset is None:
            st.clock_offset, st.offset_jitter = \
                self._clock_handshake(st.client)
        tr = st.client.trace_since(st.trace_cursor)
        st.trace_cursor = tr.get("cursor", st.trace_cursor)
        st.trace_dropped += int(tr.get("dropped", 0) or 0)
        st.trace_events.extend(tr.get("events", ()))
        del st.trace_events[:-_trace.DEFAULT_CAPACITY]

    @staticmethod
    def _clock_handshake(client,
                         probes: int = 5) -> "tuple[float, float]":
        """Estimate ``engine_clock - router_clock`` by the midpoint
        method: each ping reads the engine's wall ``ts`` between local
        stamps t0/t1, giving ``offset = ts - (t0 + t1)/2``; the probe
        with the smallest round trip wins and its half-RTT bounds the
        asymmetry error (the recorded jitter). A reply with no ``ts``
        (an older gateway) contributes nothing; all-failed probes fall
        back to (0, 0) — same-host fleets, the bench topology, are
        near-0 anyway and the jitter says how much to trust it."""
        best = None
        for _ in range(max(int(probes), 1)):
            t0 = time.time()
            try:
                pong = client.ping()
            except Exception:
                continue
            t1 = time.time()
            ts = pong.get("ts")
            if not isinstance(ts, (int, float)):
                continue
            rtt = max(t1 - t0, 0.0)
            off = float(ts) - (t0 + t1) / 2.0
            if best is None or rtt < best[0]:
                best = (rtt, off)
        if best is None:
            return 0.0, 0.0
        return best[1], best[0] / 2.0

    def _is_dead(self, name: "str | None") -> bool:
        with self._mu:
            st = self._states.get(name)
            return st is not None and st.dead

    def _note_failure(self, name: str, reason: str) -> None:
        with self._mu:
            st = self._states.get(name)
            if st is None or st.dead:
                return
            st.failures += 1
            tripped = st.failures >= self.fail_threshold
        if tripped:
            self._fail_over(name, reason=f"unreachable ({reason})")

    # ------------------------------------------------------- failover
    def _fail_over(self, name: str, reason: str) -> None:
        """Declare ``name`` dead and move its work: fence the journal,
        replay admitted-but-unresolved entries on a surviving peer
        (original idempotency keys — exactly once), re-point affected
        tickets. Idempotent: the first caller wins."""
        with self._mu:
            st = self._states.get(name)
            if st is None or st.dead:
                return
            st.dead = True
            st.status = "dead"
        telemetry.counter("fleet.failovers").inc()
        log = get_logger()
        log.warning("fleet: engine %r declared DEAD (%s); failing "
                    "over", name, reason)
        durable = st.client.durable_dir
        if durable:
            try:
                fence_journal(durable, owner=f"router:{os.getpid()}")
                _events.emit("fence", engine=name,
                             owner=f"router:{os.getpid()}")
                # mark the barrier on the router's trace track too:
                # the stitched timeline shows the victim go quiet,
                # THE FENCE, then the survivor's replay hops
                _trace.instant("fleet.fence", cat="fleet",
                               engine=name, reason=reason)
            except OSError as e:  # pragma: no cover - fs failure
                log.error("fleet: could not fence %s: %s", durable, e)
        replayed, lost = self._replay_journal(st, durable)
        done_at = time.monotonic()
        with self._mu:
            self._failovers.append({
                "engine": name, "reason": reason,
                "replayed": replayed, "lost": lost,
                "completed_ts": done_at})
        _events.emit("failover", engine=name, reason=reason,
                     replayed=replayed, lost=lost)
        log.warning("fleet: failover of %r complete — %d request(s) "
                    "replayed, %d lost", name, replayed, lost)

    def _unresolved_entries(self, durable: "str | None") -> \
            "tuple[list[dict], list[dict]]":
        """(replayable, unreplayable) journal entries the fleet still
        owes an answer for. Beyond the journal's own incomplete set
        (no ``done`` line), an entry that journaled done but whose
        result the ROUTER never delivered is also unresolved — the
        value died with the engine's memory, so exactly-once yields to
        never-lost and the entry re-executes under its original key."""
        if not durable:
            return [], []
        replayable, unreplayable = RequestJournal.incomplete(durable)
        have = {e.get("key") for e in replayable}
        with self._mu:
            undelivered = {
                k for k, t in self._tickets.items()
                if k not in self._acks and k not in self._failures}
        for e in RequestJournal.read(durable):
            if e.get("kind") != "admit" or e.get("key") in have:
                continue
            if e.get("key") in undelivered:
                (replayable if e.get("replayable") and e.get("name")
                 else unreplayable).append(e)
                have.add(e.get("key"))
        return replayable, unreplayable

    def _replay_journal(self, dead: "_EngineState",
                        durable: "str | None") -> "tuple[int, int]":
        replayable, unreplayable = self._unresolved_entries(durable)
        replayed = lost = 0
        for e in unreplayable:
            # admitted (= acknowledged) but not expressible as a named
            # query: nothing can re-run it. This is the one genuinely
            # lossy shape — counted, never silent.
            lost += 1
            telemetry.counter("fleet.lost_acks",
                              tenant=e.get("tenant", "default")).inc()
            log = get_logger()
            log.error("fleet: journal entry rid=%s on dead engine %r "
                      "is unreplayable (bare callable / non-JSON "
                      "args) — the acknowledged request is lost",
                      e.get("rid"), dead.name)
        for e in replayable:
            key = e.get("key")
            with self._mu:
                if key is not None and (key in self._acks
                                        or key in self._failures):
                    continue  # outcome already delivered via router
            tenant = e.get("tenant", "default")
            # the replayed request keeps its ORIGINAL trace id (the
            # dead engine's journal recorded it at admission): one id
            # names router admission, the dead engine's partial run,
            # the fence, and the survivor's re-run — with this hop
            # marker stitching the two engine tracks together
            tid = e.get("trace_id")
            try:
                with self._mu:
                    peer = self._pick_locked(tenant)
                with _trace.trace_context(tid):
                    rid = peer.client.submit(
                        e["name"], args=e.get("args", ()),
                        kwargs=e.get("kwargs", {}), tenant=tenant,
                        priority=e.get("priority", 1),
                        slo=e.get("slo"), key=key,
                        tables=e.get("tables", ()), trace_id=tid)
                    if tid is not None:
                        _trace.instant("fleet.replay_hop",
                                       cat="fleet", engine=peer.name,
                                       key=key)
            except Exception as exc:
                lost += 1
                get_logger().error(
                    "fleet: replay of %r from dead engine %r failed: "
                    "%s", key or e.get("rid"), dead.name, exc)
                t = (self._tickets.get(key)
                     if key is not None else None)
                if t is not None:
                    # _mark_lost owns the lost_acks count (once)
                    t._mark_lost(
                        f"engine {dead.name!r} died and the "
                        f"replay on a peer failed: {exc}")
                else:  # journal-only entry: no ticket to carry it
                    telemetry.counter("fleet.lost_acks",
                                      tenant=tenant).inc()
                continue
            replayed += 1
            telemetry.counter("fleet.replayed", tenant=tenant).inc()
            with self._mu:
                self._replayed_keys.append(key)
                ticket = self._tickets.get(key)
            if ticket is not None:
                ticket._assign(peer.client, rid)
        # any router ticket still pointing at the dead engine with no
        # journal entry cannot exist (submit acks only after the
        # write-ahead line) — but belt-and-braces: mark them lost
        # rather than letting result() spin forever
        with self._mu:
            stranded = [
                t for k, t in self._tickets.items()
                if k not in self._acks and k not in self._failures
                and t.engine == dead.name]
        for t in stranded:
            lost += 1
            t._mark_lost(f"engine {dead.name!r} died with no "
                         "replayable journal entry for this key")
        return replayed, lost

    # ------------------------------------------------------- report
    def report(self) -> dict:
        with self._mu:
            return {
                "engines": [s.snapshot()
                            for s in self._states.values()],
                "tickets": len(self._tickets),
                "acked": len(self._acks),
                "failed": len(self._failures),
                "failovers": list(self._failovers),
                "replayed_keys": list(self._replayed_keys),
                "routed": telemetry.total("fleet.routed"),
                "deduped": telemetry.total("fleet.deduped"),
                "lost_acks": telemetry.total("fleet.lost_acks"),
            }

    def fleet_trace_buffers(self, drain: bool = True) -> "list[dict]":
        """Per-PROCESS trace buffers for
        :func:`cylon_tpu.telemetry.trace.merge_timelines`: the
        router's own recorder as the reference track (offset 0) plus
        every engine's pulled ``/trace`` segments on its
        handshake-estimated clock offset. ``drain`` pulls each
        engine's cursor once more first, so spans emitted after the
        last poll tick are included — call BEFORE :meth:`close` while
        survivors still answer (a dead engine's tail was pulled when
        it still lived, or is part of the gap accounting)."""
        with self._mu:
            states = list(self._states.values())
        if drain and self._trace_armed:
            for st in states:
                try:
                    self._pull_trace(st)
                except Exception:
                    pass  # dead engine: keep what the polls got
        bufs = [{"proc": "router", "pid": os.getpid(),
                 "clock_offset": 0.0, "offset_jitter": 0.0,
                 "dropped": _trace.dropped(),
                 "events": _trace.events()}]
        for st in states:
            bufs.append({
                "proc": st.name,
                "pid": getattr(st.client, "pid", None),
                "clock_offset": st.clock_offset or 0.0,
                "offset_jitter": st.offset_jitter,
                "dropped": st.trace_dropped,
                "events": list(st.trace_events)})
        return bufs


# ----------------------------------------------------- engine process
def _mk_fleet_query(cq, resident, env):
    """A registered named query for one fleet engine: step 1 dispatches
    the compiled program, step 2 materialises to the host (the same
    staged shape serve.bench uses, so requests interleave)."""
    from cylon_tpu.serve.bench import _materialize

    def run():
        out = cq(resident, env=env)
        yield
        return _materialize(out)

    return run


def _mk_fleet_fallback(query: str, data):
    """The registered spill path for one fleet query: the partitioned
    host fallback (the two-phase plan for global-aggregate queries
    like q14). Registered — not per-submit — so a journal REPLAY after
    a failover re-arms it automatically: a replayed request that OOMs
    on the survivor recomputes its merge scalar there instead of
    trusting anything from the dead engine's journal."""
    from cylon_tpu import fallback

    def run():
        # eager per-partition execution: the spill path must not
        # re-enter the compiled-dispatch layer that just exhausted
        # memory (it would OOM again under the same pressure). The
        # result is already HOST-shaped (pandas frame / float) — the
        # same client-visible shape _materialize gives the compiled
        # path.
        out = fallback.tpch_fallback(query, data, compiled=False)
        return out if hasattr(out, "columns") else float(out)

    return run


def _engine_main(args) -> int:
    """One fleet engine process: resident TPC-H tables on its own
    mesh, named queries registered for the gateway, durable dir at
    ``<root>/engines/<name>`` with the shared snapshot store. Prints
    one ``FLEET_ENGINE_READY {json}`` line, then serves until
    SIGTERM/SIGINT (clean close — journal lock released)."""
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    os.environ.setdefault("CYLON_TPU_SERVE_HTTP_PORT", "0")

    import cylon_tpu as ct
    from cylon_tpu import tpch
    from cylon_tpu.serve import ServeEngine
    from cylon_tpu.serve.bench import _mk_resident
    from cylon_tpu.tpch import dbgen

    # chaos harness hooks (same env contract as tests/test_chaos.py):
    # CHAOS_KILL=point:nth installs a process-wide FaultRule.kill so
    # the engine hard-dies (rc 43) at a seeded mid-query instant;
    # CHAOS_OOM=point:nth makes every hit from nth on raise
    # MemoryError — each dispatch exhausts memory, so every request
    # completes through its registered spill fallback (the degraded
    # path, including replayed requests after a failover)
    rules = []
    kill = os.environ.get("CHAOS_KILL")
    if kill:
        point, nth = kill.rsplit(":", 1)
        rules.append(resilience.FaultRule.kill(point, nth=int(nth)))
    oom = os.environ.get("CHAOS_OOM")
    if oom:
        point, nth = oom.rsplit(":", 1)
        rules.append(resilience.FaultRule(
            point, nth=int(nth), times=0,
            error=MemoryError("injected OOM (CHAOS_OOM)")))
    if rules:
        resilience.install(resilience.FaultPlan(rules))

    layout = FleetLayout(args.root)
    env = ct.CylonEnv(ct.TPUConfig())
    data = dbgen.generate(args.sf, args.seed)
    resident = _mk_resident(env, data)
    engine = ServeEngine(env,
                         durable_dir=layout.engine_dir(args.name),
                         snapshot_dir=layout.snapshot_dir)
    for nm, df in resident.items():
        engine.register_table(f"tpch/{nm}", df)
    mix = tuple(q.strip() for q in args.mix.split(",") if q.strip())
    for q in mix:
        reads = QUERY_READ_SETS.get(q, tuple(resident))
        engine.register_query(q, _mk_fleet_query(tpch.compiled(q),
                                                 resident, env),
                              fallback=_mk_fleet_fallback(q, data),
                              tables=[f"tpch/{nm}" for nm in reads
                                      if nm in resident])
    gateway = EngineGateway(engine, port=args.gateway_port)
    ready = {"name": args.name, "pid": os.getpid(),
             "gateway": list(gateway.address),
             "introspect": (list(engine.http_address)
                            if engine.http_address else None),
             "durable_dir": engine.durable_dir, "mix": list(mix)}
    print("FLEET_ENGINE_READY " + json.dumps(ready), flush=True)

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *a: stop.set())
    while not stop.is_set():
        stop.wait(0.5)
    engine.close(wait=True)
    gateway.close()
    return 0


class EngineProc:
    """A spawned fleet engine process + its router-side client."""

    def __init__(self, name: str, proc, client: HttpEngineClient,
                 log_path: str):
        self.name = name
        self.proc = proc
        self.client = client
        self.log_path = log_path

    @property
    def pid(self) -> int:
        return self.proc.pid

    def kill(self, sig=signal.SIGKILL) -> None:
        """The chaos hammer: SIGKILL by default — no cleanup, no lock
        release, exactly like a preemption."""
        os.kill(self.proc.pid, sig)

    def terminate(self, timeout: float = 60.0) -> "int | None":
        if self.proc.poll() is None:
            self.proc.terminate()
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            return self.proc.wait(10)


#: chip index -> the live engine process pinned to it (this parent's)
_PINNED: dict = {}


def _host_chips() -> int:
    """TPU chips on this host, counted from their device nodes: the
    router must not touch JAX, since a process that has holds the chip
    its engines need."""
    import glob

    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def _pin_chip(child_env: dict) -> "int | None":
    """Pin an engine child to the lowest chip no live engine holds (its
    env gains the one-chip-per-process libtpu bounds); None on a host
    without chips or a child configured off the TPU. More engines than
    chips is an error."""
    platforms = child_env.get("JAX_PLATFORMS", "")
    chips = _host_chips()
    if not chips or (platforms and "tpu" not in platforms.split(",")):
        return None
    free = [i for i in range(chips)
            if i not in _PINNED or _PINNED[i].poll() is not None]
    if not free:
        raise InvalidArgument(
            f"{chips} chip(s) on this host, each held by a live engine: "
            f"one engine per chip")
    i = free[0]
    port = 8476 + i
    child_env.update({
        "TPU_VISIBLE_CHIPS": str(i),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
        "TPU_MESH_CONTROLLER_PORT": str(port)})
    return i


def spawn_engine(root: str, name: str, sf: float = 0.002,
                 seed: int = 0, mix=DEFAULT_MIX,
                 env_extra: "dict | None" = None,
                 ready_timeout: float = 300.0) -> EngineProc:
    """Spawn ``python -m cylon_tpu.serve.fleet`` as one engine process
    under ``root`` and wait for its READY line. The child's stderr
    streams to ``<root>/<name>.log`` (post-mortem evidence); stdout is
    drained by a daemon thread after the handshake. On a chip host
    each engine is pinned to a chip of its own (:func:`_pin_chip`)."""
    os.makedirs(root, exist_ok=True)
    log_path = os.path.join(root, f"{name}.log")
    cmd = [sys.executable, "-m", "cylon_tpu.serve.fleet",
           "--root", str(root), "--name", str(name),
           "--sf", str(sf), "--seed", str(seed),
           "--mix", ",".join(mix)]
    child_env = dict(os.environ)
    child_env.setdefault("CYLON_TPU_SERVE_HTTP_PORT", "0")
    child_env.setdefault("CYLON_TPU_EVENTS", "1")
    # a compiled query's FIRST dispatch traces + compiles for tens of
    # seconds on a small host, holding the single-step scheduler the
    # whole time — /health's stall probe must not read warm-up compile
    # as a wedged scheduler (the router would dwell it to death)
    child_env.setdefault("CYLON_TPU_SERVE_STALL_AGE", "120")
    child_env.pop("CHAOS_KILL", None)
    child_env.pop("CHAOS_OOM", None)
    child_env.update(env_extra or {})
    chip = _pin_chip(child_env)
    logf = open(log_path, "ab")
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=logf,
                            env=child_env, text=True)
    logf.close()  # the child holds its own descriptor now
    if chip is not None:
        _PINNED[chip] = proc

    # the handshake read rides a daemon reader thread so ready_timeout
    # is ENFORCED — a child wedged before printing READY (stuck
    # compile, hung import) must not block the spawner forever; the
    # same thread keeps draining stdout afterwards so the pipe never
    # fills
    import queue as _queue

    lines: "_queue.Queue" = _queue.Queue(maxsize=1024)

    def _reader():
        for line in proc.stdout:
            try:
                lines.put_nowait(line)
            except _queue.Full:  # post-handshake chatter: discard,
                pass             # never let the pipe back up
        try:
            lines.put_nowait(None)  # EOF sentinel
        except _queue.Full:
            pass

    threading.Thread(target=_reader, daemon=True,
                     name=f"fleet-spawn-{name}").start()
    deadline = time.monotonic() + ready_timeout
    ready = None
    while ready is None:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            proc.kill()
            raise EngineUnavailable(
                f"fleet engine {name!r} never reported READY within "
                f"{ready_timeout}s; see {log_path}")
        try:
            line = lines.get(timeout=min(remaining, 1.0))
        except _queue.Empty:
            continue
        if line is None:
            raise EngineUnavailable(
                f"fleet engine {name!r} died before READY "
                f"(rc={proc.poll()}); see {log_path}")
        if line.startswith("FLEET_ENGINE_READY "):
            ready = json.loads(line.split(" ", 1)[1])
    client = HttpEngineClient(
        name, gateway_url="http://%s:%d" % tuple(ready["gateway"]),
        introspect_url=("http://%s:%d" % tuple(ready["introspect"])
                        if ready.get("introspect") else None),
        durable_dir=ready["durable_dir"], pid=ready["pid"])
    return EngineProc(name, proc, client, log_path)


# ----------------------------------------------------- fleet bench
def _phase_p99s(samples: "list[tuple[float, float, float]]",
                kill_ts: "float | None",
                recovered_ts: "float | None") -> dict:
    """p99 request walls by phase relative to the outage window
    ``[kill_ts, recovered_ts]``: *before* = completed before the kill,
    *during* = the request's lifetime OVERLAPPED the outage (it was in
    flight when the engine died, or started before the failover
    finished — the set the kill could actually hurt), *after* =
    submitted after the failover completed. ``samples`` are
    (start, end, wall) triples; phases with no population report
    None."""
    import numpy as np

    def p99(walls):
        if not walls:
            return None
        return float(np.quantile(np.asarray(walls), 0.99))

    if kill_ts is None:
        return {"before": p99([w for _, _, w in samples]),
                "during": None, "after": None}
    hi = recovered_ts if recovered_ts is not None else kill_ts
    return {
        "before": p99([w for s, e, w in samples if e < kill_ts]),
        "during": p99([w for s, e, w in samples
                       if e >= kill_ts and s <= hi]),
        "after": p99([w for s, e, w in samples if s > hi]),
    }


def audit_double_executions(layout: FleetLayout,
                            replayed_keys) -> "tuple[int, dict]":
    """Cross-journal exactly-once audit: a key with more than one
    ``done(state=done)`` line across the fleet's journals executed
    more than once. Keys the router knowingly re-executed (a completed
    result that died undelivered — never-lost beats exactly-once
    there) are excluded; everything else is a real double-execution."""
    done_counts: "dict[str, int]" = {}
    for name in layout.engine_names():
        for e in RequestJournal.read(layout.engine_dir(name)):
            if e.get("kind") == "done" and e.get("state") == "done" \
                    and e.get("key"):
                done_counts[e["key"]] = done_counts.get(e["key"],
                                                        0) + 1
    allowed = set(k for k in (replayed_keys or ()) if k)
    doubles = {k: n for k, n in done_counts.items()
               if n > 1 and k not in allowed}
    return len(doubles), doubles


def _fleet_trace_artifact(router: "FleetRouter", root: str) -> dict:
    """Collect and stitch the fleet's per-process trace buffers (call
    BEFORE the router closes — the final cursor drain wants live
    survivors) and write the Chrome Trace artifact under ``root``.
    Returns the ``--fleet-trace`` record fields
    (:data:`cylon_tpu.serve.bench.REQUIRED_FLEET_TRACE_FIELDS`) plus
    the stitched report of the headline request — when the chaos kill
    produced a failover replay, that request's SINGLE trace id spans
    router admission, the dead engine's partial run, and the
    survivor's replay hop."""
    from cylon_tpu.telemetry.export import write_chrome_trace

    bufs = router.fleet_trace_buffers()
    merged = _trace.merge_timelines(bufs)
    path = write_chrome_trace(
        os.path.join(root, "fleet_trace.trace.json"), bufs)
    jitters = [b.get("offset_jitter") for b in bufs[1:]
               if isinstance(b.get("offset_jitter"), (int, float))]
    hops = [e for e in merged if e.get("name") == "fleet.replay_hop"]
    hop_tids = sorted({e.get("trace_id") for e in hops
                       if e.get("trace_id")})
    stitched = None
    if hop_tids:
        # several requests may have replayed; headline the one whose
        # events survive on the MOST process tracks (a dead engine's
        # unpulled ring segments die with it — some replayed traces
        # keep the victim's partial run, some don't), ties broken by
        # event count then tid for determinism
        def _coverage(tid):
            evs = [e for e in merged if e.get("trace_id") == tid]
            return (len({e.get("proc") for e in evs}), len(evs))

        best = max(sorted(hop_tids), key=_coverage)
        stitched = _trace.fleet_request_report(merged, best)
    else:  # no failover this run: report the busiest trace instead
        by_tid: "dict[str, int]" = {}
        for e in merged:
            t = e.get("trace_id")
            if t:
                by_tid[t] = by_tid.get(t, 0) + 1
        if by_tid:
            top = max(sorted(by_tid), key=lambda t: by_tid[t])
            stitched = _trace.fleet_request_report(merged, top)
    return {
        "trace_path": path,
        "spans": sum(1 for e in merged if e.get("kind") == "begin"),
        "engines_stitched": sum(1 for b in bufs[1:]
                                if b.get("events")),
        "offset_jitter_s": (round(max(jitters), 6) if jitters
                            else None),
        "replay_hops": len(hops),
        "trace_dropped": sum(int(b.get("dropped", 0) or 0)
                             for b in bufs),
        "stitched_request": stitched,
    }


def _fleet_history_check(layout: FleetLayout, mix) -> dict:
    """Audit the query-profile cost model against the run it just
    learned from: merge the engines' persisted histories (each engine
    saved ``profile_history.json`` at clean close; a SIGKILLed one
    never did — the merge reads what survived) and compare each mix
    query's ``predicted_wall_s`` against the mean of its own executed
    walls. The ISSUE 20 acceptance gates the prediction within 2x of
    actual — measured against real executions, not against a probe
    request that would resolve from the result cache."""
    from cylon_tpu.telemetry import profile as _profile

    paths = [os.path.join(layout.engine_dir(n), _profile.HISTORY_FILE)
             for n in layout.engine_names()]
    paths = [p for p in paths if os.path.exists(p)]
    hist = _profile.merged_history(paths)
    checks: "dict[str, dict | None]" = {}
    for q in mix:
        # fleet queries take no arguments: the engine-side fingerprint
        # at record time is the same canonical hash over (name, (), {})
        fp = plan.query_fingerprint(q, (), {})
        est = hist.predict(fp) if fp is not None else None
        if est is None or not est.get("samples"):
            checks[q] = None
            continue
        mean = float(est["mean_wall_s"])
        pred = float(est["predicted_wall_s"])
        checks[q] = {
            "predicted_wall_s": round(pred, 4),
            "actual_mean_wall_s": round(mean, 4),
            "samples": est["samples"],
            "within_2x": bool(mean > 0
                              and 0.5 <= pred / mean <= 2.0),
        }
    return {"history_files": len(paths), "queries": checks}


def run_fleet_bench(clients: int = 16, requests: int = 3,
                    sf: float = 0.002, seed: int = 0,
                    mix=DEFAULT_MIX, engines: int = 2,
                    kill_mid_run: bool = True,
                    root: "str | None" = None,
                    result_timeout: float = 600.0,
                    fleet_trace: bool = False) -> dict:
    """The ISSUE 15 measured acceptance: ≥2 engine processes over one
    durable tree, N concurrent clients replaying the TPC-H mix through
    the router, one engine SIGKILLed mid-run. Every ticket the router
    acknowledged must complete oracle-exact (0 lost acks), nothing may
    double-execute, and the record carries the windowed p99 before /
    during / after the kill. Returns the record
    (:data:`cylon_tpu.serve.bench.REQUIRED_FLEET_FIELDS`)."""
    import tempfile

    import numpy as np  # noqa: F401  (quantiles in _phase_p99)

    import cylon_tpu as ct
    from cylon_tpu import tpch
    from cylon_tpu.serve.bench import (_materialize, _mk_resident,
                                       _results_match)
    from cylon_tpu.tpch import dbgen

    if engines < 2:
        raise InvalidArgument(
            f"a fleet needs >= 2 engines, got {engines}")
    if fleet_trace:
        # arm the flight recorder fleet-wide: this (router) process
        # plus — via env inheritance and the explicit extra below —
        # every spawned engine. The leg is opt-in, so mutating the
        # env here mirrors how the storm leg arms CYLON_TPU_EVENTS.
        os.environ["CYLON_TPU_TRACE"] = "1"
    root = root or os.environ.get("CYLON_BENCH_FLEET_DIR") \
        or tempfile.mkdtemp(prefix="cylon_fleet_")
    layout = FleetLayout(root)
    mix = tuple(mix)

    # oracles: each mix query once, alone, in THIS process — every
    # fleet-routed result must reproduce them exactly
    env = ct.CylonEnv(ct.TPUConfig())
    data = dbgen.generate(sf, seed)
    resident = _mk_resident(env, data)
    oracles = {q: _materialize(tpch.compiled(q)(resident, env=env))
               for q in mix}

    # every spawned engine is terminated on ANY exit path — a
    # mid-bench exception must not leak live engine processes (ports,
    # journal locks, resident meshes) onto the host
    procs: "list[EngineProc]" = []
    router = None
    try:
        for i in range(engines):
            procs.append(spawn_engine(
                root, f"e{i}", sf=sf, seed=seed, mix=mix,
                env_extra={"CYLON_TPU_TRACE": "1"} if fleet_trace
                else None))
        # SIGKILL detection rides connection-refused polls (threshold
        # 3 at 0.25s — ~1s to DEAD); the dwell only governs
        # verdict-based failover and is deliberately generous so a
        # host saturated by 16 concurrent compiles is not misread as
        # an outage
        router = FleetRouter([p.client for p in procs],
                             poll_interval=0.25, fail_threshold=3,
                             unhealthy_dwell=45.0)
        return _drive_fleet_bench(
            router, procs, layout, oracles, clients=clients,
            requests=requests, sf=sf, mix=mix,
            kill_mid_run=kill_mid_run, root=root,
            result_timeout=result_timeout, fleet_trace=fleet_trace)
    finally:
        if router is not None:
            router.close()
        for p in procs:
            try:
                p.terminate()
            except Exception:  # pragma: no cover - teardown best-effort
                pass


def _drive_fleet_bench(router, procs, layout, oracles, *, clients,
                       requests, sf, mix, kill_mid_run, root,
                       result_timeout, fleet_trace=False) -> dict:
    """The measured body of :func:`run_fleet_bench` (engines/router
    lifecycle owned by the caller's try/finally)."""
    import numpy as np  # noqa: F401  (quantiles in _phase_p99s)

    from cylon_tpu.serve.bench import _results_match

    t0 = time.perf_counter()
    samples: "list[tuple[float, float, float]]" = []  # (start, end, wall)
    mismatches: list = []
    errors: list = []
    completed = [0]
    shed = [0]
    lock = threading.Lock()
    kill_ts = [None]
    total = clients * requests
    kill_at = max(total // 3, 1)  # after ~1/3 of acks land

    def client_thread(i: int):
        # sequential submit→result per client (one outstanding request
        # each): submissions spread across the whole run, so the
        # before/during/after phase populations all exist
        tenant = f"tenant{i}"
        for r in range(requests):
            q = mix[(i + r) % len(mix)]
            key = f"c{i}-r{r}"
            try:
                tk = router.submit(q, tenant=tenant,
                                   idempotency_key=key)
                got = tk.result(result_timeout)
            except Exception as e:
                with lock:
                    if isinstance(e, (ResourceExhausted,
                                      EngineUnavailable)):
                        shed[0] += 1
                    errors.append((key,
                                   f"{type(e).__name__}: {e}"))
                continue
            end = time.monotonic()
            with lock:
                samples.append((tk.submitted, end,
                                end - tk.submitted))
                completed[0] += 1
            if not _results_match(got, oracles[q]):
                with lock:
                    mismatches.append((key, q))

    def killer():
        # wait until ~1/3 of the run completed, then SIGKILL e0
        while True:
            with lock:
                if completed[0] >= kill_at:
                    break
            if all(not th.is_alive() for th in threads):
                return  # run ended (e.g. everything shed) — no kill
            time.sleep(0.05)
        kill_ts[0] = time.monotonic()
        get_logger().warning("fleet bench: SIGKILL engine %r (pid "
                             "%d) mid-run", procs[0].name,
                             procs[0].pid)
        procs[0].kill()

    threads = [threading.Thread(target=client_thread, args=(i,),
                                name=f"fleet-client-{i}")
               for i in range(clients)]
    kt = (threading.Thread(target=killer, name="fleet-killer")
          if kill_mid_run else None)
    for th in threads:
        th.start()
    if kt is not None:
        kt.start()
    for th in threads:
        th.join()
    if kt is not None:
        kt.join()
    wall = time.perf_counter() - t0

    rep = router.report()
    recovered_ts = (rep["failovers"][0]["completed_ts"]
                    if rep["failovers"] else None)

    # the post-failover idempotent-retry probe: re-submit an already-
    # completed key through the router — it must come back from the
    # ack cache without executing anywhere (the ISSUE 15 "a retried
    # one never double-executes" half, measured)
    retry_deduped = None
    if samples:
        probe_key = "c0-r0"
        before = telemetry.total("fleet.deduped")
        try:
            router.submit(mix[0], tenant="tenant0",
                          idempotency_key=probe_key).result(30)
            retry_deduped = telemetry.total("fleet.deduped") > before
        except Exception as e:  # pragma: no cover - probe best-effort
            retry_deduped = False
            errors.append(("retry_probe",
                           f"{type(e).__name__}: {e}"))

    # the stitched trace must be collected while survivors still
    # answer /trace (the final cursor drain) and before the poll
    # loops stop
    trace_extra = (_fleet_trace_artifact(router, root)
                   if fleet_trace else None)
    # stop the poll loop BEFORE terminating survivors (a still-running
    # poll would read the graceful shutdown as one more "failover"),
    # then stop the engines so their journals are quiescent to audit
    router.close()
    for p in procs:
        p.terminate()
    doubles, double_detail = audit_double_executions(
        layout, rep["replayed_keys"])
    record = {
        "metric": "fleet_bench_tpch_mix",
        "engines": len(procs),
        "clients": clients,
        "requests_total": total,
        "completed": completed[0],
        "shed": shed[0],
        "wall_s": round(wall, 3),
        "sf": sf,
        "mix": list(mix),
        "kill": ("sigkill_mid_run" if kill_mid_run else None),
        "failovers": len(rep["failovers"]),
        "failover_detail": [
            {k: v for k, v in f.items() if k != "completed_ts"}
            for f in rep["failovers"]],
        "replayed": telemetry.total("fleet.replayed"),
        "lost_acks": rep["lost_acks"],
        "routed": rep["routed"],
        "deduped": rep["deduped"],
        "retry_deduped": retry_deduped,
        "double_executions": doubles,
        "double_execution_detail": double_detail,
        "oracle_mismatches": len(mismatches),
        "mismatch_detail": mismatches[:8],
        "errors": len(errors),
        "error_detail": errors[:8],
        "p99_before_s": None,
        "p99_during_s": None,
        "p99_after_s": None,
        "fleet_root": root,
        # the shared store's per-table generation stamps (quiescent —
        # engines are down): what a failover recover() would restore
        "table_generations": snapshot_generations(root),
    }
    phases = _phase_p99s(samples, kill_ts[0], recovered_ts)
    record.update(p99_before_s=phases["before"],
                  p99_during_s=phases["during"],
                  p99_after_s=phases["after"])
    for k in ("p99_before_s", "p99_during_s", "p99_after_s"):
        if record[k] is not None:
            record[k] = round(record[k], 4)
    if trace_extra is not None:
        record.update(trace_extra)
        # the engines just closed cleanly (terminate → SIGTERM →
        # engine.close saves profile_history.json), so the merged
        # query-profile history is on disk to audit the cost model
        record["cost_model"] = _fleet_history_check(layout, mix)
    return record


# ----------------------------------------------------------- __main__
def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="run ONE fleet engine process (the fleet bench / "
                    "chaos harness spawns these; humans usually want "
                    "`python -m cylon_tpu.serve.bench --fleet`)")
    p.add_argument("--root", required=True,
                   help="fleet durable root (FleetLayout)")
    p.add_argument("--name", required=True, help="engine name")
    p.add_argument("--sf", type=float, default=0.002)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--mix", default=",".join(DEFAULT_MIX))
    p.add_argument("--gateway-port", type=int, default=0)
    return _engine_main(p.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
