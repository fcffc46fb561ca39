"""Arrow-IPC front door: cross-process serving for the query service.

PR 10's :class:`~nds_tpu.service.QueryService` is in-process — "N
clients" meant N threads importing the engine. This module is the wire
layer that turns one engine process into a server: N client PROCESSES
submit SQL + tenant + deadline over a stdlib socket, results return as
Arrow IPC, and every admission/breaker/deadline/batching/fair-scheduling
decision stays in ``service.py`` unchanged (the front door calls
``service.submit`` like any in-process client would).

Frame layout (both directions, one frame per message)::

    u32 big-endian  header length H
    H bytes         header, UTF-8 JSON (op / status / stats / error)
    u64 big-endian  body length B
    B bytes         body (Arrow IPC stream bytes; empty when B = 0)

Request ops:

- ``query``: ``{op, sql, tenant, label, deadline_s, backend, hash}`` —
  the USER query path. The handler thread submits, blocks on the
  ticket, materializes, and serializes — all OFF the device lane, which
  only ever sees the dispatch itself. Response body = result as one
  Arrow IPC stream; header carries the per-query stats and (``hash:
  true`` requests) a canonical engine-result hash for bit-identity
  audits.
- ``ping``: liveness + the server's cache EPOCH (fresh per server
  start, so a restarted engine invalidates every client-held entry).
- ``cache_snapshot``: the result cache's exact tier as Arrow IPC — the
  header lists (sql, backend, gens, snaps) per entry, the body is the
  concatenation of ``u64 len | IPC stream`` blobs in header order.
  N fresh front-end processes warm from one snapshot instead of N cold
  sets.
- ``cache_validate``: the invalidation handshake — the client sends the
  stamps (per-table catalog generations + warehouse snapshot versions)
  and epoch of entries it wants to trust, the server answers one bool
  each against the LIVE session. A commit or re-registration between
  snapshot and use answers False; an epoch mismatch answers all False.
- ``chaos``: arm fault specs in the SERVER process (the topology
  campaign's remote trigger). Refused unless the server was started
  with ``allow_chaos=True`` — never on by default.

Errors cross the wire TYPED: the response header carries the resilience
class name + its constructor fields, and the client reconstructs the
real exception (:class:`AdmissionRejected` with depth/limit,
:class:`CircuitOpen` with error_class/retry_after_s, ...) so every
existing backoff/retry policy works unchanged against remote failures.
Unknown classes land as :class:`RemoteQueryError` — still typed, never
a bare string.

Fault points (chaos topology campaign): ``frontdoor.drop`` severs the
connection instead of writing a response (client sees EOF mid-frame and
raises :class:`ConnectionDropped`, a TransientError — its retry loop
re-submits); ``frontdoor.kill`` hard-exits the engine process before a
query dispatches (the mid-query kill).
"""
from __future__ import annotations

import json
import os
import socket
import socketserver
import struct
import threading
import time
import uuid
from typing import Optional

from ..obs import metrics as _metrics
from ..obs.flight import FLIGHT
from ..obs.trace import TRACER
from ..resilience import (FAULTS, AdmissionRejected, CircuitOpen,
                          DeadlineExceeded, FaultError, TransientError)
from .service import ServiceClosed

#: request header / body hard bounds: a malformed or hostile length
#: prefix fails typed instead of ballooning server memory
MAX_HEADER_BYTES = 1 << 20
MAX_BODY_BYTES = 1 << 28
#: default client-side wall for one blocking request
DEFAULT_TIMEOUT_S = 300.0


class ConnectionDropped(TransientError):
    """The front-door connection died mid-request (EOF, reset, refused):
    transient by classification — the client retry loop reconnects and
    re-submits, the wire-level analogue of the service requeue."""


class RemoteQueryError(RuntimeError):
    """A server-side error class the client has no local type for —
    still typed (``cls`` carries the remote class name)."""

    def __init__(self, message: str, cls: str = ""):
        super().__init__(message)
        self.cls = cls


# -- frame + payload codecs ----------------------------------------------------

def write_frame(wfile, header: dict, body: bytes = b"") -> None:
    h = json.dumps(header, separators=(",", ":")).encode()
    wfile.write(struct.pack(">I", len(h)) + h
                + struct.pack(">Q", len(body)) + body)
    wfile.flush()


def _read_exact(rfile, n: int) -> bytes:
    out = b""
    while len(out) < n:
        chunk = rfile.read(n - len(out))
        if not chunk:
            raise ConnectionDropped(
                f"connection closed mid-frame ({len(out)}/{n} bytes)")
        out += chunk
    return out


def read_frame(rfile) -> tuple[dict, bytes]:
    """One frame, or raises ConnectionDropped (EOF/short read) /
    ValueError (bound exceeded, malformed JSON)."""
    hlen = struct.unpack(">I", _read_exact(rfile, 4))[0]
    if hlen > MAX_HEADER_BYTES:
        raise ValueError(f"frame header {hlen} bytes exceeds "  # lint: typed-error-exempt (framing-bound violation is deliberately NOT retryable: a typed TransientError would make clients re-send the same oversized frame; the connection is torn down instead)
                         f"bound {MAX_HEADER_BYTES}")
    header = json.loads(_read_exact(rfile, hlen).decode())
    blen = struct.unpack(">Q", _read_exact(rfile, 8))[0]
    if blen > MAX_BODY_BYTES:
        raise ValueError(f"frame body {blen} bytes exceeds "  # lint: typed-error-exempt (same deliberate non-retryable framing bound as the header check above)
                         f"bound {MAX_BODY_BYTES}")
    return header, _read_exact(rfile, blen) if blen else b""


def table_to_ipc(table) -> bytes:
    """One pa.Table -> Arrow IPC stream bytes."""
    import pyarrow as pa
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as w:
        w.write_table(table)
    return sink.getvalue().to_pybytes()


def ipc_to_table(data: bytes):
    """Arrow IPC stream bytes -> pa.Table."""
    import pyarrow as pa
    return pa.ipc.open_stream(pa.BufferReader(data)).read_all()


def result_hash(table) -> str:
    """Canonical engine-result digest (chaos.result_hash's recipe): the
    server stamps responses with it so clients/benches can assert
    bit-identity against a serial execution without shipping both."""
    import hashlib
    return hashlib.sha1(repr(table.to_pylist()).encode()).hexdigest()


def _error_doc(e: BaseException) -> dict:
    """Typed error -> wire dict: class name + the resilience hierarchy's
    constructor fields (absent fields are simply not sent)."""
    fields = {}
    for k in ("depth", "limit", "error_class", "retry_after_s"):
        v = getattr(e, k, None)
        if v is not None:
            fields[k] = v
    return {"cls": type(e).__name__, "msg": str(e), "fields": fields}


def reconstruct_error(doc: dict) -> BaseException:
    """Wire dict -> the real typed exception, so client-side retry
    policies classify remote failures exactly like local ones."""
    cls = doc.get("cls", "RemoteQueryError")
    msg = doc.get("msg", "")
    f = doc.get("fields") or {}
    if cls == "ServiceClosed":
        return ServiceClosed(msg, depth=f.get("depth"),
                             limit=f.get("limit"))
    if cls == "CircuitOpen":
        return CircuitOpen(msg, error_class=f.get("error_class"),
                           retry_after_s=f.get("retry_after_s"))
    if cls == "AdmissionRejected":
        return AdmissionRejected(msg, depth=f.get("depth"),
                                 limit=f.get("limit"))
    if cls == "DeadlineExceeded":
        return DeadlineExceeded(msg)
    if cls == "FaultError":
        return FaultError(msg)
    if cls == "ConnectionDropped":
        return ConnectionDropped(msg)
    if cls == "TransientError":
        return TransientError(msg)
    if cls == "TimeoutError":
        return TimeoutError(msg)
    if cls == "PermissionError":
        return PermissionError(msg)
    return RemoteQueryError(f"{cls}: {msg}", cls=cls)


# -- server --------------------------------------------------------------------

class _FrontDoorTCPServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True
    frontdoor: "FrontDoorServer"


class _Handler(socketserver.StreamRequestHandler):
    """One connected client process: frames served in a loop until EOF
    (connections are persistent — a dashboard client submits thousands
    of queries over one socket). Everything here runs on the acceptor's
    per-connection thread: admission, blocking on the ticket, deferred
    materialization, Arrow serialization — the device lane never waits
    on this socket."""

    def handle(self) -> None:
        fd = self.server.frontdoor
        while True:
            try:
                header, body = read_frame(self.rfile)
            except ConnectionDropped:
                return                      # client went away: normal
            except Exception as e:
                # malformed frame: answer typed once, then drop the
                # connection (framing is lost — resync is impossible)
                self._reply_error(ValueError(f"malformed frame: {e}"))
                return
            _metrics.FRONTDOOR_REQUESTS.inc()
            try:
                if not self._serve_one(fd, header, body):
                    return
            except ConnectionDropped:
                return                      # injected drop severed us
            except BrokenPipeError:
                return
            except Exception as e:
                if not self._reply_error(e):
                    return

    def _serve_one(self, fd: "FrontDoorServer", header: dict,
                   body: bytes) -> bool:
        """Dispatch one request frame; False ends the connection."""
        op = header.get("op")
        if op == "query":
            return self._op_query(fd, header)
        if op == "ping":
            return self._reply({"ok": True, "epoch": fd.epoch,
                                "pid": os.getpid()})
        if op == "cache_snapshot":
            return self._op_cache_snapshot(fd)
        if op == "cache_validate":
            return self._op_cache_validate(fd, header)
        if op == "chaos":
            return self._op_chaos(fd, header)
        return self._reply_error(ValueError(f"unknown op {op!r}"))

    def _op_query(self, fd: "FrontDoorServer", header: dict) -> bool:
        from ..engine import arrow_bridge

        sql = header.get("sql")
        if not isinstance(sql, str) or not sql.strip():
            return self._reply_error(ValueError("query op without sql"))
        label = header.get("label") or None
        # the mid-query kill window: the request is admitted to the
        # server's log/flight but its result will never be produced
        try:
            FAULTS.fire("frontdoor.kill", label or sql[:40])
        except FaultError:
            FLIGHT.trip("frontdoor_kill", label=label)
            os._exit(86)
        ticket = fd.service.submit(
            sql, label=label, tenant=header.get("tenant", "default"),
            deadline_s=header.get("deadline_s"),
            backend=header.get("backend"))
        table = ticket.result(timeout=fd.request_timeout_s)
        resp = {"ok": True,
                "stats": {
                    "mode": ticket.stats.mode if ticket.stats else None,
                    "queue_wait_ms": ticket.queue_wait_ms,
                    "plan_ms": ticket.plan_ms,
                    "exec_ms": ticket.exec_ms,
                    "preempted": ticket.preempted,
                    "template": ticket.template,
                }}
        if header.get("hash"):
            resp["result_hash"] = result_hash(table)
        # the last hop of a request: engine Table -> Arrow -> IPC bytes ->
        # socket, on this connection's thread, linked to the ticket's root
        with TRACER.span("frontdoor/reply", cat="service",
                         parent=ticket.trace_id, label=ticket.label):
            return self._reply(
                resp, table_to_ipc(arrow_bridge.to_arrow(table)))

    def _op_cache_snapshot(self, fd: "FrontDoorServer") -> bool:
        from ..engine import arrow_bridge

        cache = fd.service.result_cache
        if cache is None:
            return self._reply({"ok": True, "epoch": fd.epoch,
                                "entries": []})
        items = cache.export_snapshot()
        entries, blobs = [], []
        for it in items:
            blob = table_to_ipc(arrow_bridge.to_arrow(it["result"]))
            blobs.append(struct.pack(">Q", len(blob)) + blob)
            entries.append({"sql": it["sql"], "backend": it["backend"],
                            "gens": it["gens"], "snaps": it["snaps"]})
        _metrics.RESULT_CACHE_SNAPSHOTS.inc()
        FLIGHT.record("cache_snapshot", entries=len(entries))
        return self._reply({"ok": True, "epoch": fd.epoch,
                            "entries": entries}, b"".join(blobs))

    def _op_cache_validate(self, fd: "FrontDoorServer",
                           header: dict) -> bool:
        cache = fd.service.result_cache
        entries = header.get("entries") or []
        if header.get("epoch") != fd.epoch or cache is None:
            # a restarted server (fresh epoch) or a cache-less one can
            # vouch for nothing: every client-held entry is stale
            return self._reply({"ok": True,
                                "valid": [False] * len(entries)})
        valid = [bool(cache.validate_stamps(e.get("gens") or {},
                                            e.get("snaps") or {}))
                 for e in entries]
        return self._reply({"ok": True, "valid": valid})

    def _op_chaos(self, fd: "FrontDoorServer", header: dict) -> bool:
        if not fd.allow_chaos:
            return self._reply_error(PermissionError(
                "chaos op refused: server started without allow_chaos"))
        specs = header.get("specs") or []
        # fired counts of the batch being REPLACED: a disarm ([]) hands
        # the campaign its evidence that the faults actually fired
        fired = [{"point": s.point, "action": s.action, "fired": s.fired}
                 for s in FAULTS.specs() if s.source == "config"]
        FAULTS.configure([str(s) for s in specs])
        return self._reply({"ok": True, "armed": len(specs),
                            "fired": fired})

    # -- response writers ------------------------------------------------------
    def _maybe_drop(self) -> None:
        """The connection-drop fault point: armed, the handler severs
        the socket INSTEAD of writing the response — the client observes
        an abrupt EOF exactly where a real network failure would put
        one."""
        try:
            FAULTS.fire("frontdoor.drop")
        except FaultError:
            _metrics.FRONTDOOR_ERRORS.inc()
            try:
                self.connection.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            self.connection.close()
            raise ConnectionDropped("injected frontdoor.drop")

    def _reply(self, header: dict, body: bytes = b"") -> bool:
        self._maybe_drop()
        write_frame(self.wfile, header, body)
        return True

    def _reply_error(self, e: BaseException) -> bool:
        _metrics.FRONTDOOR_ERRORS.inc()
        FLIGHT.record("frontdoor_error", error=type(e).__name__)
        try:
            self._maybe_drop()
            write_frame(self.wfile, {"ok": False, "error": _error_doc(e)})
            return True
        except (ConnectionDropped, BrokenPipeError, OSError):
            return False


class FrontDoorServer:
    """The engine process's wire front door over one QueryService.

    Usage (one engine process)::

        svc = QueryService(session, cfg).start()
        door = FrontDoorServer(svc, port=0).start()
        print(door.port)          # ephemeral bind reads back
        ...
        door.stop()

    ``epoch`` is fresh per instance: client caches warmed from a
    previous server life validate False wholesale after a restart —
    the zero-stale-results guarantee does not depend on clients
    noticing the process died."""

    def __init__(self, service, host: str = "127.0.0.1", port: int = 0,
                 allow_chaos: bool = False,
                 request_timeout_s: float = DEFAULT_TIMEOUT_S):
        self.service = service
        self.host = host
        self._port = port
        self.allow_chaos = allow_chaos
        self.request_timeout_s = request_timeout_s
        self.epoch = uuid.uuid4().hex
        self._server: Optional[_FrontDoorTCPServer] = None
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self._server.server_address[1] if self._server \
            else self._port

    def start(self) -> "FrontDoorServer":
        if self._server is not None:
            return self
        self._server = _FrontDoorTCPServer((self.host, self._port),
                                           _Handler)
        self._server.frontdoor = self
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="frontdoor-acceptor",
                                        daemon=True)
        self._thread.start()
        FLIGHT.record("frontdoor_start", host=self.host, port=self.port)
        return self

    def stop(self) -> None:
        if self._server is None:
            return
        self._server.shutdown()
        self._server.server_close()
        self._server = None
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def __enter__(self) -> "FrontDoorServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


# -- client --------------------------------------------------------------------

class FlightClient:
    """Thin synchronous client for the front door (one socket, one
    in-flight request — N concurrency comes from N clients, matching
    the service's one-ticket-per-submit shape).

    ``use_cache=True`` arms the client-side result cache: warm it from
    the server's exact tier with :meth:`warm_cache`, and every ``sql``
    first revalidates a local entry over the ``cache_validate``
    handshake — a hit answers from local memory without touching the
    admission queue; a commit/re-registration/restart on the server
    invalidates the entry on its next use. NOT thread-safe (use one
    client per thread, like one cursor per thread)."""

    def __init__(self, host: str, port: int,
                 timeout_s: float = DEFAULT_TIMEOUT_S,
                 retries: int = 2, retry_backoff_s: float = 0.05,
                 use_cache: bool = False):
        self.host = host
        self.port = int(port)
        self.timeout_s = timeout_s
        self.retries = max(0, retries)
        self.retry_backoff_s = retry_backoff_s
        self.use_cache = use_cache
        self._sock: Optional[socket.socket] = None
        self._file = None
        #: (sql, backend_tag) -> {table, gens, snaps, epoch}
        self._cache: dict = {}

    # -- connection -------------------------------------------------------------
    def _connect(self):
        if self._file is None:
            try:
                self._sock = socket.create_connection(
                    (self.host, self.port), timeout=self.timeout_s)
            except OSError as e:
                raise ConnectionDropped(
                    f"connect {self.host}:{self.port} failed: {e}")
            self._file = self._sock.makefile("rwb")
        return self._file

    def close(self) -> None:
        for obj in (self._file, self._sock):
            if obj is not None:
                try:
                    obj.close()
                except OSError:
                    pass
        self._file = self._sock = None

    def __enter__(self) -> "FlightClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _rpc(self, header: dict, body: bytes = b"") -> tuple[dict, bytes]:
        """One request/response exchange; raises the reconstructed typed
        error on an error frame, ConnectionDropped on wire death."""
        f = self._connect()
        try:
            write_frame(f, header, body)
            resp, rbody = read_frame(f)
        except (ConnectionDropped, OSError) as e:
            self.close()
            if isinstance(e, ConnectionDropped):
                raise
            raise ConnectionDropped(f"wire failure: {e}")
        if not resp.get("ok", True) and "error" in resp:
            raise reconstruct_error(resp["error"])
        return resp, rbody

    # -- ops ----------------------------------------------------------------
    def ping(self) -> dict:
        return self._rpc({"op": "ping"})[0]

    def chaos(self, specs: list) -> dict:
        """Arm FaultRegistry specs inside the ENGINE process (replacing
        whatever was armed; ``[]`` disarms). Refused (PermissionError)
        unless the server started with ``allow_chaos`` — the topology
        campaign's remote fault-injection control channel."""
        return self._rpc({"op": "chaos", "specs": list(specs)})[0]

    def warm_cache(self) -> int:
        """Pull the server's exact-tier snapshot into the local cache;
        returns entries loaded. Requires ``use_cache=True``."""
        resp, body = self._rpc({"op": "cache_snapshot"})
        epoch = resp.get("epoch")
        off = 0
        n = 0
        for meta in resp.get("entries", []):
            (blen,) = struct.unpack_from(">Q", body, off)
            off += 8
            table = ipc_to_table(body[off:off + blen])
            off += blen
            self._cache[(meta["sql"], meta.get("backend", "jax"))] = {
                "table": table, "gens": meta.get("gens") or {},
                "snaps": meta.get("snaps") or {}, "epoch": epoch}
            n += 1
        return n

    def _cache_lookup(self, sql: str, backend: Optional[str]):
        """Snapshot-warmed lookup with the per-use validation handshake;
        a False (or failed) validation evicts and misses."""
        key = (sql, backend or "jax")
        entry = self._cache.get(key)
        if entry is None:
            return None
        resp, _ = self._rpc({"op": "cache_validate",
                             "epoch": entry["epoch"],
                             "entries": [{"gens": entry["gens"],
                                          "snaps": entry["snaps"]}]})
        if (resp.get("valid") or [False])[0]:
            _metrics.FRONTDOOR_CLIENT_CACHE_HITS.inc()
            return entry["table"]
        del self._cache[key]
        return None

    def query(self, sql: str, tenant: str = "default",
              label: Optional[str] = None,
              deadline_s: Optional[float] = None,
              backend: Optional[str] = None,
              want_hash: bool = False) -> tuple:
        """Submit one query; returns (pa.Table, response header).

        ConnectionDropped retries RECONNECT + RE-SUBMIT up to
        ``retries`` times (reads are idempotent — the wire analogue of
        the service's requeue); typed server errors raise as their real
        resilience classes."""
        attempt = 0
        while True:
            try:
                if self.use_cache:
                    hit = self._cache_lookup(sql, backend)
                    if hit is not None:
                        return hit, {"ok": True, "cache": "client"}
                header = {"op": "query", "sql": sql, "tenant": tenant}
                if label:
                    header["label"] = label
                if deadline_s is not None:
                    header["deadline_s"] = deadline_s
                if backend:
                    header["backend"] = backend
                if want_hash:
                    header["hash"] = True
                resp, body = self._rpc(header)
                return ipc_to_table(body), resp
            except ConnectionDropped:
                attempt += 1
                if attempt > self.retries:
                    raise
                time.sleep(self.retry_backoff_s * attempt)

    def sql(self, sql: str, **kw):
        """Submit one query; returns its pa.Table."""
        return self.query(sql, **kw)[0]
