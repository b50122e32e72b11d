"""Supervised multi-process serving fleet (one engine process per host).

This is the deployment shape the paper assumes and the in-process
:class:`~repro.serving.cluster.MPICCluster` only simulates: each *host* is
its own OS process owning one :class:`~repro.serving.engine.MPICEngine`
and one :class:`~repro.cache.library.KVLibrary` with a **persistent
per-host spool dir**, exporting blocks to peers via
:class:`~repro.cache.net.KVPeerServer` and accepting work over a small
HTTP control plane.  A front-end :class:`FleetSupervisor` spawns the
hosts, routes requests by address, and owns the robustness story:

* **Liveness** — the supervisor heartbeats every host's ``GET /health``;
  ``miss_threshold`` consecutive misses (or a dead PID) declare the host
  down.  The heartbeat payload carries the same ``load_info`` an
  in-process replica exposes plus the library's gossiped ``{ident: tier}``
  map, so the existing affinity scoring routes cross-process with no
  shared memory (:func:`repro.serving.router.heartbeat_view`).
* **Crash recovery** — a dead host's in-flight requests are resubmitted
  to surviving hosts (PR 7's seeded replay: same ``Request.seed`` ⇒
  token-identical output), and the host is respawned with the SAME
  identity: same control/block ports (``SO_REUSEADDR`` — see
  ``cache/net.py``) and same spool dir, so the restarted library
  **rehydrates** its disk tier from the self-verifying content-hash spool
  files (``KVLibrary.rehydrate_spool``) and rejoins warm instead of
  recomputing.
* **Graceful drain** — SIGTERM (or ``POST /drain``) stops admission,
  finishes in-flight work, then lingers briefly so the supervisor can
  collect the last results before ``POST /shutdown`` exits the process.

Control protocol (one resource per verb, JSON or npz-blob bodies):

    GET  /health    -> 200 JSON  (load, media tiers, drain state, counters)
    POST /submit    -> 200 JSON  (body: request blob; 503 while draining)
    POST /upload    -> 200 JSON  (body: upload blob — precompute + store)
    GET  /results   -> 200 JSON  (terminal requests not yet delivered)
    POST /freeze    -> 200 JSON  (body: {req_id, spool} — snapshot a
                                  running session; returns its handle)
    POST /thaw      -> 200 JSON  (body: {handle, suffix?, max_new_tokens?}
                                  — resume a frozen session HERE; a missing
                                  snapshot is pulled from a peer)
    GET  /sessions  -> 200 JSON  (frozen session handles on this host)
    POST /drain     -> 200       (stop admission, finish in-flight)
    POST /shutdown  -> 200       (exit after the current step)

Cross-process clocks: ``Request.t_arrival`` is re-stamped when a host
decodes the wire request (``time.perf_counter`` is per-process), so the
reported ``ttft`` is host-side — queue wait + prefill on the serving
host.  The supervisor additionally records wall-clock submit→result
latency per request (``latency_s``).  A failover resubmission restarts
the host-side clock; end-to-end latency keeps accumulating.

CLI: ``python -m repro.launch.fleet --hosts 2 --requests 8`` runs a
demo fleet end to end; ``--serve-host`` is the internal per-host entry
point the supervisor spawns (not for direct use).
"""
from __future__ import annotations

import argparse
import hashlib
import io
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from collections import deque
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler
from typing import Dict, List, Optional

import numpy as np

# NOTE: jax / model imports happen inside host_main() and the demo — the
# supervisor itself must stay import-light so spawning N hosts doesn't pay
# N+1 jax initializations.

# ---------------------------------------------------------------------------
# wire helpers: npz blob with a __json__ header field
# ---------------------------------------------------------------------------


def pack_blob(header: dict, arrays: Dict[str, np.ndarray]) -> bytes:
    """Serialize ``header`` (JSON) + named numpy arrays into one npz blob."""
    wire = {"__json__": np.array(json.dumps(header))}
    for name, a in arrays.items():
        wire[name] = np.ascontiguousarray(a)
    buf = io.BytesIO()
    np.savez(buf, **wire)
    return buf.getvalue()


def unpack_blob(data: bytes):
    """Inverse of :func:`pack_blob` → ``(header, arrays)``."""
    arrays: Dict[str, np.ndarray] = {}
    with np.load(io.BytesIO(data)) as z:
        header = json.loads(str(z["__json__"]))
        for name in z.files:
            if name != "__json__":
                arrays[name] = z[name]
    return header, arrays


def encode_request(req) -> bytes:
    """Request → wire blob.  Segment structure goes in the header, token
    and embedding arrays ride as npz fields, and ``req_id``/``seed``
    travel verbatim — the receiving host reconstructs a request whose
    seeded decode replays token-identically (the failover contract)."""
    segs, arrays = [], {}
    for i, s in enumerate(req.prompt.segments):
        d = {"kind": s.kind, "length": int(s.length),
             "media_id": s.media_id}
        if s.tokens is not None:
            arrays[f"tok{i}"] = s.tokens
        if s.embeds is not None:
            arrays[f"emb{i}"] = s.embeds
        segs.append(d)
    header = {"req_id": req.req_id, "user_id": req.prompt.user_id,
              "segments": segs, "policy": req.policy,
              "policy_kwargs": req.policy_kwargs,
              "max_new_tokens": int(req.max_new_tokens),
              "priority": int(req.priority), "seed": int(req.seed),
              "deadline_s": req.deadline_s,
              "session_id": req.session_id,
              "freeze_after": req.freeze_after}
    return pack_blob(header, arrays)


def decode_request(data: bytes):
    """Wire blob → a fresh :class:`~repro.serving.request.Request` (new
    ``t_arrival`` — per-process clock; see module docstring)."""
    from repro.core.segments import Prompt, Segment
    from repro.serving.request import Request
    header, arrays = unpack_blob(data)
    segments = []
    for i, d in enumerate(header["segments"]):
        segments.append(Segment(
            kind=d["kind"], length=d["length"],
            tokens=arrays.get(f"tok{i}"), media_id=d.get("media_id"),
            embeds=arrays.get(f"emb{i}")))
    prompt = Prompt(segments=segments, user_id=header["user_id"])
    req = Request(prompt=prompt,
                  max_new_tokens=header["max_new_tokens"],
                  policy=header["policy"],
                  policy_kwargs=dict(header.get("policy_kwargs") or {}),
                  priority=header.get("priority", 0),
                  seed=header.get("seed", 0),
                  deadline_s=header.get("deadline_s"),
                  session_id=header.get("session_id"),
                  freeze_after=header.get("freeze_after"))
    req.req_id = header["req_id"]     # identity survives the hop
    return req


def encode_upload(user_id: str, media_id: str, embeds: np.ndarray, *,
                  ttl: float = float("inf"), dynamic: bool = False) -> bytes:
    header = {"user_id": user_id, "media_id": media_id,
              "ttl": ttl, "dynamic": dynamic}
    return pack_blob(header, {"embeds": np.asarray(embeds)})


# ---------------------------------------------------------------------------
# engine-host process (spawned by the supervisor; --serve-host entry)
# ---------------------------------------------------------------------------


class _HostState:
    """Shared state between the control handler threads and the step loop.

    Two locks with very different hold times keep the control plane
    responsive while the engine compiles/steps:

      * ``lock`` — the engine mutex.  Held by the step loop around
        ``submit``/``step`` (which can take tens of seconds on a first
        jit compile) and by ``/upload`` (the one handler that must call
        into the engine synchronously).
      * ``qlock`` — a micro-mutex over the inbox/outbox/snapshot.  This
        is all ``/submit``, ``/health`` and ``/results`` ever touch, so
        heartbeats and dispatches answer in microseconds even mid-compile
        — a slow engine must never read as a dead host.
    """

    def __init__(self, host_id: int):
        self.host_id = host_id
        self.lock = threading.Lock()    # engine mutex (long holds OK)
        self.qlock = threading.Lock()   # queue mutex (micro holds only)
        self.engine = None
        self.draining = threading.Event()
        self.shutdown = threading.Event()
        self.steps = 0
        self.seen: set = set()          # req_ids accepted (dedup resubmits)
        self.delivered: set = set()     # req_ids already returned by /results
        self.inbox: list = []           # decoded Requests awaiting the loop
        self.outbox: Dict[str, dict] = {}   # req_id -> terminal result row
        self.snapshot: dict = {}        # last engine load/done published


def _result_row(r, host_id: int, session: Optional[dict] = None) -> dict:
    from repro.serving.request import State
    state = {State.DONE: "done", State.FAILED: "failed",
             State.DEADLINE: "deadline"}.get(r.state, r.state.value)
    row = {"req_id": r.req_id, "state": state, "host": host_id,
           "tokens": [int(t) for t in r.output_tokens],
           "ttft": r.ttft if r.t_first_token else None,
           "n_reused": int(r.prefill_stats.get("n_reused", 0)),
           "error": r.error}
    if session is not None:
        # a FROZEN request is terminal *on this host*; the handle rides
        # the result row so the supervisor can thaw it anywhere
        row["session"] = session
    return row


class _CtrlHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # noqa: ARG002
        pass

    def _json(self, obj, status: int = 200) -> None:
        body = json.dumps(obj).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _body(self) -> bytes:
        length = int(self.headers.get("Content-Length", 0))
        return self.rfile.read(length)

    def do_GET(self):
        st: _HostState = self.server.state
        if self.path == "/health":
            # lock-free w.r.t. the engine: load/done come from the step
            # loop's published snapshot, the media map from the library's
            # own (briefly held) lock — a mid-compile engine still beats
            # the heartbeat deadline
            lib = st.engine.static_lib
            with st.qlock:
                snap = dict(st.snapshot)
                accepted = len(st.seen)
            payload = {
                "host": st.host_id, "pid": os.getpid(),
                "draining": st.draining.is_set(),
                "steps": st.steps, "load": snap.get("load", {}),
                "media": lib.ident_tiers(),
                "rehydrate": lib.rehydrate_stats,
                "sessions": lib.stats().get("sessions", {}),
                "done": snap.get("done", 0), "accepted": accepted,
            }
            self._json(payload)
        elif self.path == "/sessions":
            # frozen session handles held by this host's engine (any host
            # can thaw them — the snapshot lives in the tiered library and
            # travels over the peer block protocol)
            with st.lock:
                handles = {sid: h.to_json()
                           for sid, h in st.engine.sessions.handles.items()}
            self._json({"sessions": handles})
        elif self.path == "/results":
            rows = []
            with st.qlock:
                for req_id, row in st.outbox.items():
                    if req_id not in st.delivered:
                        st.delivered.add(req_id)
                        rows.append(row)
            self._json({"results": rows})
        else:
            self.send_error(404)

    def do_POST(self):
        st: _HostState = self.server.state
        if self.path == "/submit":
            data = self._body()
            if st.draining.is_set():
                self._json({"error": "draining"}, status=503)
                return
            try:
                req = decode_request(data)
            except Exception as exc:
                self._json({"error": f"bad request blob: {exc}"},
                           status=400)
                return
            with st.qlock:
                if req.req_id in st.seen:
                    # idempotent resubmit: the earlier copy is queued,
                    # running, or already terminal here — either way
                    # accepting again would double-serve it
                    self._json({"req_id": req.req_id, "dup": True})
                    return
                st.seen.add(req.req_id)
                st.inbox.append(req)
            # accepted into the inbox; the step loop feeds the engine and
            # a submit-time failure surfaces as a failed row in /results
            self._json({"req_id": req.req_id})
        elif self.path == "/upload":
            data = self._body()
            try:
                header, arrays = unpack_blob(data)
            except Exception as exc:
                self._json({"error": f"bad upload blob: {exc}"},
                           status=400)
                return
            with st.lock:
                st.engine.upload(header["user_id"], header["media_id"],
                                 arrays["embeds"],
                                 ttl=float(header.get("ttl", float("inf"))),
                                 dynamic=bool(header.get("dynamic")))
            self._json({"media_id": header["media_id"]})
        elif self.path == "/freeze":
            # body: {"req_id": ..., "spool": bool} → the handle JSON.
            # Always spooled by default: a fleet freeze exists to survive
            # the host, so the snapshot must reach the durable disk tier.
            try:
                body = json.loads(self._body().decode() or "{}")
                req_id = body["req_id"]
            except Exception as exc:
                self._json({"error": f"bad freeze body: {exc}"}, status=400)
                return
            with st.lock:
                try:
                    handle = st.engine.freeze(
                        req_id, spool=bool(body.get("spool", True)))
                except (KeyError, ValueError, RuntimeError) as exc:
                    self._json({"error": str(exc)}, status=409)
                    return
            self._json({"handle": handle.to_json()})
        elif self.path == "/thaw":
            # body: {"handle": {...}, "suffix": [...], "max_new_tokens": n}
            # Resume-anywhere: if this host lacks the snapshot blocks, the
            # library's network tier pulls them from a peer.
            from repro.serving.sessions import SessionHandle
            try:
                body = json.loads(self._body().decode() or "{}")
                handle = SessionHandle.from_json(body["handle"])
            except Exception as exc:
                self._json({"error": f"bad thaw body: {exc}"}, status=400)
                return
            if st.draining.is_set():
                self._json({"error": "draining"}, status=503)
                return
            mnt = body.get("max_new_tokens")
            with st.lock:
                try:
                    req = st.engine.thaw(
                        handle, body.get("suffix") or None,
                        max_new_tokens=int(mnt) if mnt is not None else None)
                except Exception as exc:
                    self._json({"error": str(exc)}, status=409)
                    return
            with st.qlock:
                st.seen.add(req.req_id)
            self._json({"req_id": req.req_id,
                        "session_id": req.session_id})
        elif self.path == "/drain":
            st.draining.set()
            self._json({"draining": True})
        elif self.path == "/shutdown":
            st.shutdown.set()
            self._json({"shutdown": True})
        else:
            self.send_error(404)


def host_main(args) -> int:
    """Entry point of one engine-host process (``--serve-host``).

    Builds the model (same ``PRNGKey(0)`` init as every other host —
    identical params are what make cross-host failover token-identical),
    **rehydrates** the library from the per-host spool dir, then serves
    the control plane + peer block server until drained/shut down.
    SIGTERM triggers the graceful drain path.
    """
    import jax

    from repro.cache.library import KVLibrary
    from repro.cache.net import (KVPeerServer, PeerTransport,
                                 ReusableThreadingHTTPServer)
    from repro.configs import serving_config
    from repro.launch.device import expected_platform, require_platform
    from repro.models import build_model
    from repro.serving import EngineConfig, MPICEngine
    from repro.serving.sharding import init_params

    # one host per chip: a host that finds its chip taken must die here,
    # loudly, not serve on the CPU JAX falls back to
    require_platform(expected_platform())
    cfg = serving_config(args.arch, smoke=args.smoke, layers=args.layers)
    model = build_model(cfg)
    params = init_params(model, jax.random.PRNGKey(0))
    lib_kw = {}
    if args.hbm_bytes > 0:
        lib_kw["hbm_capacity"] = args.hbm_bytes
    if args.host_bytes > 0:
        lib_kw["host_capacity"] = args.host_bytes
    lib = KVLibrary(spool_dir=args.spool_dir, rehydrate=True, **lib_kw)
    peers = [p.strip() for p in args.peers.split(",") if p.strip()]
    if peers:
        # snappy transports: a dead peer must cost well under a heartbeat
        # interval, and the breaker mutes it after a few misses
        lib.connect_peers([PeerTransport(p, timeout_s=args.peer_timeout_s,
                                         retries=0) for p in peers])
    engine = MPICEngine(model, params,
                        EngineConfig(max_seq_len=args.max_seq_len,
                                     decode_slots=args.slots,
                                     freeze_idle_s=args.freeze_idle_s),
                        static_library=lib)
    peer_server = KVPeerServer(lib, port=args.block_port)

    st = _HostState(args.host_id)
    st.engine = engine
    ctrl = ReusableThreadingHTTPServer(("127.0.0.1", args.ctrl_port),
                                       _CtrlHandler)
    ctrl.state = st
    ctrl_thread = threading.Thread(target=ctrl.serve_forever, daemon=True)
    ctrl_thread.start()

    signal.signal(signal.SIGTERM, lambda *_: st.draining.set())
    print(f"[host {st.host_id}] up pid={os.getpid()} "
          f"ctrl={args.ctrl_port} blocks={peer_server.address} "
          f"rehydrated={lib.rehydrate_stats}", flush=True)

    def _publish() -> None:
        """Copy engine results/load into the handler-visible snapshot.
        Called with ``st.lock`` held; takes ``st.qlock`` briefly."""
        rows = [_result_row(r, st.host_id)
                for r in (engine.finished + engine.failed + engine.expired)]
        for r in engine.frozen:
            h = engine.sessions.handles.get(r.session_id)
            rows.append(_result_row(
                r, st.host_id,
                session=h.to_json() if h is not None else None))
        load = engine.load_info()
        with st.qlock:
            for row in rows:
                st.outbox.setdefault(row["req_id"], row)
            st.snapshot = {"load": load, "done": len(rows)}

    with st.lock:
        _publish()      # health answers sanely before the first step

    idle_since = None
    while not st.shutdown.is_set():
        with st.qlock:
            inbox, st.inbox = st.inbox, []
        with st.lock:
            for req in inbox:
                try:
                    engine.submit(req)
                except Exception as exc:       # e.g. prompt too long
                    with st.qlock:
                        st.outbox[req.req_id] = {
                            "req_id": req.req_id, "state": "failed",
                            "host": st.host_id, "tokens": [], "ttft": None,
                            "n_reused": 0, "error": str(exc)}
            work = engine.has_work
            if work:
                engine.step()
                st.steps += 1
            if work or inbox:
                _publish()
        if work:
            idle_since = None
            continue
        if st.draining.is_set():
            # drained + idle: linger so the supervisor can pull the last
            # results, then exit on /shutdown or the linger timeout
            if idle_since is None:
                idle_since = time.monotonic()
            elif time.monotonic() - idle_since > args.linger_s:
                break
        time.sleep(0.005)

    ctrl.shutdown()
    ctrl.server_close()
    peer_server.close()
    print(f"[host {st.host_id}] exit steps={st.steps}", flush=True)
    return 0


# ---------------------------------------------------------------------------
# supervisor (front-end router + process babysitter)
# ---------------------------------------------------------------------------


@dataclass
class HostSpec:
    """A host's stable identity: restarting reuses ALL of it (ports +
    spool dir), which is what makes warm rejoin possible."""
    host_id: int
    ctrl_port: int
    block_port: int
    spool_dir: str


@dataclass
class FleetHost:
    spec: HostSpec
    proc: Optional[subprocess.Popen] = None
    state: str = "starting"         # starting | up | dead | draining
    misses: int = 0                 # consecutive heartbeat failures
    restarts: int = 0
    health: Optional[dict] = None   # last good heartbeat payload
    spawned_at: float = 0.0         # monotonic spawn time (startup grace)

    @property
    def ctrl_addr(self) -> str:
        return f"127.0.0.1:{self.spec.ctrl_port}"

    @property
    def block_addr(self) -> str:
        return f"127.0.0.1:{self.spec.block_port}"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@dataclass
class _Inflight:
    data: bytes                     # encoded request (replayable verbatim)
    req: object                     # the original Request (routing inputs)
    host: Optional[int] = None      # host currently serving it
    t_submit: float = field(default_factory=time.perf_counter)
    resubmits: int = 0
    session: bool = False           # thawed session (no wire blob to replay)


class FleetSupervisor:
    """Spawn, heartbeat, route, fail over, and drain a fleet of engine
    host processes.  Single-threaded by design: callers drive it with
    :meth:`pump` / :meth:`run_until_done`, so tests and benchmarks get a
    deterministic event order."""

    def __init__(self, hosts: int = 2, *, arch: str = "llava-1.6-7b",
                 base_dir: Optional[str] = None, router: str = "affinity",
                 heartbeat_s: float = 0.25, miss_threshold: int = 3,
                 auto_restart: bool = True, slots: int = 2,
                 max_seq_len: int = 256, peer_timeout_s: float = 0.5,
                 linger_s: float = 20.0, hbm_bytes: int = 0,
                 host_bytes: int = 0, start_grace_s: float = 180.0,
                 freeze_idle_s: float = 0.0, smoke: bool = False,
                 layers: int = 0, env: Optional[dict] = None):
        """``smoke``/``layers`` pick each host's model exactly as
        ``serve --smoke --layers`` does (:func:`repro.configs
        .serving_config`).  On a TPU machine every host needs a chip of
        its own: more hosts than attached chips is refused here, before
        anything is spawned."""
        from repro.launch.device import attached_tpu_chips, expected_platform
        from repro.serving.router import make_router
        assert hosts >= 1
        self._env = env
        # each host, spawned with this env, expects the same platform
        if expected_platform(os.environ if env is None else env) == "tpu":
            chips = attached_tpu_chips()
            if hosts > chips:
                raise ValueError(
                    f"a fleet of {hosts} engine hosts needs {hosts} TPU "
                    f"chips (one process per chip), but {chips} are "
                    f"attached; use --fleet {max(chips, 1)} or fewer, or "
                    f"--replicas for in-process replicas")
        self.arch = arch
        self.smoke = smoke
        self.layers = layers
        self.base_dir = base_dir or os.path.join(
            tempfile.gettempdir(), f"mpic_fleet_{os.getpid()}")
        os.makedirs(self.base_dir, exist_ok=True)
        self.router = make_router(router)
        self.router_name = router
        self.heartbeat_s = heartbeat_s
        self.miss_threshold = miss_threshold
        self.auto_restart = auto_restart
        self.slots = slots
        self.max_seq_len = max_seq_len
        self.peer_timeout_s = peer_timeout_s
        self.linger_s = linger_s
        self.hbm_bytes = hbm_bytes
        self.host_bytes = host_bytes
        self.start_grace_s = start_grace_s
        self.freeze_idle_s = freeze_idle_s
        self.hosts: List[FleetHost] = []
        for i in range(hosts):
            spool = os.path.join(self.base_dir, f"host{i}", "spool")
            os.makedirs(spool, exist_ok=True)
            self.hosts.append(FleetHost(HostSpec(
                host_id=i, ctrl_port=_free_port(),
                block_port=_free_port(), spool_dir=spool)))
        self.inflight: Dict[str, _Inflight] = {}
        self.pending: deque = deque()   # req_ids awaiting a routable host
        self.results: Dict[str, dict] = {}
        self.latency_s: Dict[str, float] = {}
        self.requeued = 0               # failover resubmissions issued
        self.deaths = 0
        self._last_beat = 0.0

    # -- process management -------------------------------------------------
    def _spawn(self, host: FleetHost) -> None:
        spec = host.spec
        peers = ",".join(h.block_addr for h in self.hosts
                         if h.spec.host_id != spec.host_id)
        cmd = [sys.executable, "-m", "repro.launch.fleet", "--serve-host",
               "--host-id", str(spec.host_id), "--arch", self.arch,
               "--ctrl-port", str(spec.ctrl_port),
               "--block-port", str(spec.block_port),
               "--spool-dir", spec.spool_dir, "--peers", peers,
               "--slots", str(self.slots),
               "--max-seq-len", str(self.max_seq_len),
               "--peer-timeout-s", str(self.peer_timeout_s),
               "--linger-s", str(self.linger_s),
               "--hbm-bytes", str(self.hbm_bytes),
               "--host-bytes", str(self.host_bytes),
               "--freeze-idle-s", str(self.freeze_idle_s),
               "--layers", str(self.layers)]
        if self.smoke:
            cmd.append("--smoke")
        env = dict(os.environ if self._env is None else self._env)
        src = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        log = open(os.path.join(self.base_dir,
                                f"host{spec.host_id}.log"), "ab")
        host.proc = subprocess.Popen(cmd, env=env, stdout=log, stderr=log)
        log.close()                    # the child keeps its own fd
        host.state = "starting"
        host.misses = 0
        host.health = None
        host.spawned_at = time.monotonic()

    def start(self, timeout_s: float = 180.0) -> None:
        """Spawn every host and block until all are healthy."""
        for h in self.hosts:
            self._spawn(h)
        self.wait_healthy(timeout_s=timeout_s)

    def wait_healthy(self, host_ids=None, *, timeout_s: float = 180.0):
        """Poll heartbeats until the given hosts (default: all with a live
        process) report healthy; raises ``TimeoutError`` otherwise."""
        want = set(host_ids if host_ids is not None
                   else [h.spec.host_id for h in self.hosts])
        ok: set = set()        # a FRESH probe must succeed (stale state
        deadline = time.monotonic() + timeout_s   # from before a kill lies)
        while time.monotonic() < deadline:
            for h in self.hosts:
                if h.spec.host_id not in want or h.spec.host_id in ok:
                    continue
                if h.proc is not None and h.proc.poll() is not None:
                    # died while starting (e.g. its chip was taken): no
                    # heartbeat will ever come, so say why now
                    raise RuntimeError(
                        f"host {h.spec.host_id} exited with "
                        f"{h.proc.returncode} before it was healthy:\n"
                        f"{self._log_tail(h)}")
                hb = self._http("GET", h, "/health", timeout=1.0)
                if hb is not None:
                    h.health, h.misses = hb, 0
                    h.state = "draining" if hb.get("draining") else "up"
                    ok.add(h.spec.host_id)
            if ok == want:
                return
            time.sleep(0.2)
        raise TimeoutError(
            f"hosts {sorted(want)} not healthy after {timeout_s}s "
            f"(states: {[(h.spec.host_id, h.state) for h in self.hosts]})")

    def _log_tail(self, host: FleetHost, n: int = 2000) -> str:
        path = os.path.join(self.base_dir, f"host{host.spec.host_id}.log")
        try:
            with open(path, "rb") as f:
                return f.read()[-n:].decode(errors="replace")
        except OSError:
            return f"(no log at {path})"

    def _host(self, host_id: int) -> FleetHost:
        return self.hosts[host_id]

    def kill_host(self, host_id: int) -> None:
        """kill -9 a host (the benchmark's mid-wave murder).  Detection,
        failover and restart happen in subsequent :meth:`pump` calls —
        exactly as they would for a real crash."""
        proc = self._host(host_id).proc
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)

    def restart_host(self, host_id: int, *, wipe_spool: bool = False,
                     timeout_s: float = 180.0) -> None:
        """Deliberate restart (benchmark's warm-vs-cold probe).  With
        ``wipe_spool`` the host comes back truly cold — the rehydration
        scan finds an empty dir."""
        h = self._host(host_id)
        if h.proc is not None and h.proc.poll() is None:
            h.proc.kill()
            h.proc.wait(timeout=10)
        if wipe_spool:
            for fname in os.listdir(h.spec.spool_dir):
                try:
                    os.unlink(os.path.join(h.spec.spool_dir, fname))
                except OSError:
                    pass
        h.restarts += 1
        self._spawn(h)
        self.wait_healthy([host_id], timeout_s=timeout_s)

    # -- HTTP plumbing ------------------------------------------------------
    def _http(self, method: str, host: FleetHost, path: str, *,
              data: Optional[bytes] = None, timeout: float = 2.0):
        """One control-plane call; ``None`` on any transport/HTTP failure
        (the heartbeat loop turns repeated Nones into a death verdict)."""
        url = f"http://{host.ctrl_addr}{path}"
        req = urllib.request.Request(url, data=data, method=method)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return json.loads(resp.read().decode() or "{}")
        except Exception:
            return None

    # -- liveness -----------------------------------------------------------
    def heartbeat(self) -> None:
        """One liveness round: probe every non-dead host, update its
        gossiped state, and declare death after ``miss_threshold``
        consecutive misses or a reaped PID."""
        for h in self.hosts:
            if h.state == "dead":
                continue
            exited = h.proc is None or h.proc.poll() is not None
            hb = None if exited else self._http("GET", h, "/health",
                                                timeout=1.0)
            if hb is not None:
                h.health, h.misses = hb, 0
                h.state = "draining" if hb.get("draining") else "up"
                continue
            if (not exited and h.state == "starting"
                    and time.monotonic() - h.spawned_at
                    < self.start_grace_s):
                # cold boot (model build + jit + rehydration) takes tens
                # of seconds — don't declare a starting host dead until
                # its grace runs out; a reaped PID still dies immediately
                continue
            h.misses += 1
            if exited or h.misses >= self.miss_threshold:
                self._on_death(h)

    def _on_death(self, host: FleetHost) -> None:
        """Host declared dead: fail its in-flight work over to the
        survivors (seeded replay keeps tokens identical) and — under
        ``auto_restart`` — respawn it with the same identity so it
        rehydrates its spool dir and rejoins warm."""
        host.state = "dead"
        host.health = None
        self.deaths += 1
        if host.proc is not None and host.proc.poll() is None:
            host.proc.kill()        # half-dead (wedged) process: finish it
            try:
                host.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        hid = host.spec.host_id
        lost_sessions = []
        for req_id, rec in self.inflight.items():
            if rec.host == hid and req_id not in self.results:
                if rec.session:
                    # a thawed session has no wire blob to replay; its
                    # snapshot is still in the library (spooled on freeze),
                    # so the caller re-thaws from the handle instead
                    self.results[req_id] = {
                        "req_id": req_id, "state": "failed", "host": hid,
                        "tokens": [], "ttft": None, "n_reused": 0,
                        "error": "host died mid-resume; re-thaw the handle"}
                    lost_sessions.append(req_id)
                    continue
                rec.host = None
                rec.resubmits += 1
                self.requeued += 1
                if req_id not in self.pending:
                    self.pending.append(req_id)
        for req_id in lost_sessions:
            self.inflight.pop(req_id, None)
        if self.auto_restart:
            host.restarts += 1
            self._spawn(host)       # rejoins via the heartbeat loop

    # -- routing + dispatch -------------------------------------------------
    def _routable(self) -> List[FleetHost]:
        return [h for h in self.hosts
                if h.state == "up" and h.health is not None]

    def _route(self, req) -> Optional[FleetHost]:
        from repro.serving.router import heartbeat_view
        cands = self._routable()
        if not cands:
            return None
        views = [heartbeat_view(h.spec.host_id, h.ctrl_addr, h.health, req)
                 for h in cands]
        decision = self.router.route(req, views)
        return self._host(decision.replica)

    def submit(self, req, *, host: Optional[int] = None) -> str:
        """Route + POST one request.  ``host=`` pins the choice (the
        benchmark's warm/cold probes).  Unroutable requests queue in
        ``pending`` and dispatch on a later :meth:`pump`."""
        rec = _Inflight(data=encode_request(req), req=req, host=host)
        self.inflight[req.req_id] = rec
        self._dispatch(req.req_id, rec)
        return req.req_id

    def _dispatch(self, req_id: str, rec: _Inflight) -> None:
        target = (self._host(rec.host) if rec.host is not None
                  else self._route(rec.req))
        if target is None or target.state != "up":
            if req_id not in self.pending:
                self.pending.append(req_id)
            return
        resp = self._http("POST", target, "/submit", data=rec.data,
                          timeout=5.0)
        if resp is None or "error" in resp:
            # transport failure or rejection: let the heartbeat decide the
            # host's fate; the request waits in pending meanwhile
            target.misses += 1
            rec.host = None
            if req_id not in self.pending:
                self.pending.append(req_id)
            return
        rec.host = target.spec.host_id

    def upload(self, user_id: str, media_id: str, embeds, *,
               ttl: float = float("inf"), host: Optional[int] = None,
               dynamic: bool = False) -> int:
        """Upload media to one host (default: spread round-robin by
        media-id digest).  Other hosts reach the block over the peer
        network tier; the affinity router steers requests to the owner."""
        cands = self._routable() or [h for h in self.hosts
                                     if h.state != "dead"]
        assert cands, "no live hosts to upload to"
        if host is not None:
            target = self._host(host)
        else:
            # stable digest, NOT hash(): PYTHONHASHSEED must not decide
            # media placement (benchmark legs need identical layouts)
            digest = int(hashlib.sha1(media_id.encode()).hexdigest(), 16)
            target = cands[digest % len(cands)]
        data = encode_upload(user_id, media_id, embeds, ttl=ttl,
                             dynamic=dynamic)
        resp = self._http("POST", target, "/upload", data=data,
                          timeout=30.0)
        assert resp is not None and "error" not in resp, \
            f"upload of {media_id} to host {target.spec.host_id} failed"
        return target.spec.host_id

    # -- session control ----------------------------------------------------
    def freeze(self, host_id: int, req_id: str, *,
               spool: bool = True) -> dict:
        """Freeze a request running on ``host_id``; returns the handle
        JSON (``SessionHandle.from_json``-able).  Spooled by default so
        the snapshot survives the host process."""
        body = json.dumps({"req_id": req_id, "spool": spool}).encode()
        resp = self._http("POST", self._host(host_id), "/freeze",
                          data=body, timeout=30.0)
        if resp is None or "error" in resp:
            raise RuntimeError(
                f"freeze of {req_id!r} on host {host_id} failed: "
                f"{(resp or {}).get('error', 'transport error')}")
        return resp["handle"]

    def thaw(self, host_id: int, handle, *, suffix=None,
             max_new_tokens: Optional[int] = None) -> str:
        """Resume a frozen session on ``host_id`` (any host will do —
        a host that lacks the snapshot blocks pulls them over the peer
        protocol).  Returns the resumed ``req_id``; the result arrives
        through the normal :meth:`poll` path."""
        hj = handle if isinstance(handle, dict) else handle.to_json()
        body = json.dumps({
            "handle": hj,
            "suffix": [int(t) for t in (suffix or [])],
            "max_new_tokens": max_new_tokens}).encode()
        resp = self._http("POST", self._host(host_id), "/thaw",
                          data=body, timeout=120.0)
        if resp is None or "error" in resp:
            raise RuntimeError(
                f"thaw of {hj.get('session_id')!r} on host {host_id} "
                f"failed: {(resp or {}).get('error', 'transport error')}")
        req_id = resp["req_id"]
        self.inflight[req_id] = _Inflight(data=b"", req=None,
                                          host=host_id, session=True)
        return req_id

    def session_handles(self) -> Dict[str, dict]:
        """Fleet-wide ``session_id -> handle JSON`` map (live hosts)."""
        out: Dict[str, dict] = {}
        for h in self.hosts:
            if h.state not in ("up", "draining"):
                continue
            resp = self._http("GET", h, "/sessions", timeout=5.0)
            if resp is not None:
                out.update(resp.get("sessions", {}))
        return out

    # -- result collection --------------------------------------------------
    def poll(self) -> int:
        """Pull terminal requests from every live host.  First completion
        wins — a resubmitted request that (rarely) finishes twice is
        counted once.  Returns the number of new results."""
        new = 0
        for h in self.hosts:
            if h.state not in ("up", "draining"):
                continue
            resp = self._http("GET", h, "/results", timeout=5.0)
            if resp is None:
                continue
            for row in resp.get("results", []):
                req_id = row["req_id"]
                if req_id in self.results:
                    continue
                self.results[req_id] = row
                rec = self.inflight.pop(req_id, None)
                if rec is not None:
                    self.latency_s[req_id] = \
                        time.perf_counter() - rec.t_submit
                try:
                    self.pending.remove(req_id)
                except ValueError:
                    pass
                new += 1
        return new

    # -- the drive loop -----------------------------------------------------
    def pump(self) -> None:
        """One supervisor iteration: heartbeat (rate-limited), collect
        results, dispatch whatever is pending."""
        now = time.monotonic()
        if now - self._last_beat >= self.heartbeat_s:
            self._last_beat = now
            self.heartbeat()
        self.poll()
        for req_id in list(self.pending):
            rec = self.inflight.get(req_id)
            if rec is None:
                try:
                    self.pending.remove(req_id)
                except ValueError:
                    pass
                continue
            if self._routable():
                try:
                    self.pending.remove(req_id)
                except ValueError:
                    pass
                self._dispatch(req_id, rec)

    def run_until_done(self, timeout_s: float = 300.0) -> Dict[str, dict]:
        """Pump until every submitted request has a result (completions
        keep arriving through crashes, failovers and restarts)."""
        deadline = time.monotonic() + timeout_s
        while self.inflight or self.pending:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"fleet stuck: {len(self.inflight)} in flight, "
                    f"{len(self.pending)} pending after {timeout_s}s "
                    f"(states: {[(h.spec.host_id, h.state) for h in self.hosts]})")
            self.pump()
            time.sleep(0.02)
        return self.results

    def drain(self, timeout_s: float = 120.0) -> None:
        """Graceful end of life: stop admission everywhere, wait for the
        last results, then shut every host down and reap the PIDs."""
        for h in self.hosts:
            if h.state in ("up", "draining"):
                self._http("POST", h, "/drain", timeout=2.0)
        if self.inflight or self.pending:
            self.run_until_done(timeout_s=timeout_s)
        for h in self.hosts:
            if h.proc is not None and h.proc.poll() is None:
                self._http("POST", h, "/shutdown", timeout=2.0)
        for h in self.hosts:
            if h.proc is None:
                continue
            try:
                h.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                h.proc.kill()
                h.proc.wait(timeout=10)
        for h in self.hosts:
            h.state = "dead"

    def stop(self) -> None:
        """Hard stop (teardown path): SIGKILL every live host."""
        for h in self.hosts:
            if h.proc is not None and h.proc.poll() is None:
                h.proc.kill()
                try:
                    h.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
            h.state = "dead"

    def report(self) -> dict:
        lat = sorted(self.latency_s.values())
        out = {
            "hosts": len(self.hosts),
            "router": self.router_name,
            "completed": len(self.results),
            "failed": sum(1 for r in self.results.values()
                          if r["state"] not in ("done", "frozen")),
            "frozen": sum(1 for r in self.results.values()
                          if r["state"] == "frozen"),
            "deaths": self.deaths,
            "restarts": sum(h.restarts for h in self.hosts),
            "requeued": self.requeued,
        }
        sess: Dict[str, float] = {}
        for h in self.hosts:
            for k, v in ((h.health or {}).get("sessions") or {}).items():
                sess[k] = sess.get(k, 0) + v
        if sess:
            out["sessions"] = sess
        if lat:
            out["mean_latency_s"] = float(np.mean(lat))
            out["p95_latency_s"] = float(lat[int(0.95 * (len(lat) - 1))])
        return out


# ---------------------------------------------------------------------------
# CLI: demo driver + internal --serve-host entry
# ---------------------------------------------------------------------------


def run_fleet(*, hosts: int = 2, requests: int = 8,
              arch: str = "llava-1.6-7b", policy: str = "mpic",
              max_new_tokens: int = 8, mpic_k: int = 8,
              router: str = "affinity",
              deadline_s: Optional[float] = None,
              timeout_s: float = 300.0, freeze_idle_s: float = 0.0,
              smoke: bool = False, layers: int = 0) -> dict:
    """End-to-end fleet demo: spawn hosts, upload media, serve a synthetic
    request wave cross-process, drain, and return the report (used by
    ``serve.py --fleet N`` and the CLI below)."""
    from repro.configs import serving_config
    from repro.data import image_embeds, make_dialogues
    from repro.serving.request import Request

    cfg = serving_config(arch, smoke=smoke, layers=layers)
    media_len = cfg.media_token_len
    fleet = FleetSupervisor(hosts, arch=arch, router=router,
                            freeze_idle_s=freeze_idle_s, smoke=smoke,
                            layers=layers)
    try:
        print(f"starting {hosts} engine host(s)…", flush=True)
        fleet.start()
        dialogues = make_dialogues(n=requests, n_images=2,
                                   d_model=cfg.d_model,
                                   media_len=media_len, style="mmdu",
                                   user_id="u1")
        seen = {}
        for d in dialogues:
            for mid in d.media_ids:
                if mid not in seen:
                    seen[mid] = fleet.upload(
                        "u1", mid, image_embeds(mid, media_len,
                                                cfg.d_model))
        policies = [p.strip() for p in policy.split(",") if p.strip()]
        for i, d in enumerate(dialogues):
            pol = policies[i % len(policies)]
            kw = {"k": mpic_k} if pol == "mpic" else {}
            fleet.submit(Request(prompt=d.prompt,
                                 max_new_tokens=max_new_tokens,
                                 policy=pol, policy_kwargs=kw,
                                 deadline_s=deadline_s))
        fleet.run_until_done(timeout_s=timeout_s)
        fleet.drain()
        rep = fleet.report()
        for req_id in sorted(fleet.results,
                             key=lambda r: int(r.strip("req") or 0)
                             if r.startswith("req") else 0):
            row = fleet.results[req_id]
            ttft = row.get("ttft")
            print(f"  {req_id}: host={row['host']} state={row['state']} "
                  f"ttft={(ttft or 0) * 1e3:7.0f} ms "
                  f"reused={row['n_reused']:4d} "
                  f"tokens={len(row['tokens'])}")
        for k, v in rep.items():
            print(f"  {k}: {v}")
        return rep
    finally:
        fleet.stop()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--serve-host", action="store_true",
                    help="internal: run as one engine-host process "
                         "(spawned by the supervisor)")
    # host-mode args
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--arch", default="llava-1.6-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="the reduced CPU preset of --arch instead of its "
                         "published widths")
    ap.add_argument("--layers", type=int, default=0,
                    help=">0: cut the model to this many decoder layers")
    ap.add_argument("--ctrl-port", type=int, default=0)
    ap.add_argument("--block-port", type=int, default=0)
    ap.add_argument("--spool-dir", default=os.path.join(
        tempfile.gettempdir(), "mpic_fleet_host", "spool"))
    ap.add_argument("--peers", default="",
                    help="comma-separated host:port peer BLOCK servers")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-seq-len", type=int, default=256)
    ap.add_argument("--peer-timeout-s", dest="peer_timeout_s",
                    type=float, default=0.5)
    ap.add_argument("--linger-s", dest="linger_s", type=float, default=20.0)
    ap.add_argument("--hbm-bytes", dest="hbm_bytes", type=int, default=0,
                    help=">0: host library HBM budget (small values force "
                         "demotion through the tiers — the durability story)")
    ap.add_argument("--host-bytes", dest="host_bytes", type=int, default=0,
                    help=">0: host library host-RAM budget (small values "
                         "spool media KV to the per-host disk tier)")
    ap.add_argument("--freeze-idle-s", dest="freeze_idle_s", type=float,
                    default=0.0,
                    help=">0: spool frozen session snapshots idle this "
                         "many seconds to the disk tier")
    # demo-mode args
    ap.add_argument("--hosts", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--policy", default="mpic")
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--mpic-k", type=int, default=8)
    ap.add_argument("--router", default="affinity",
                    choices=["random", "least_loaded", "affinity"])
    ap.add_argument("--deadline-s", type=float, default=None)
    args = ap.parse_args()
    if args.serve_host:
        return host_main(args)
    rep = run_fleet(hosts=args.hosts, requests=args.requests,
                    arch=args.arch, policy=args.policy,
                    max_new_tokens=args.max_new_tokens, mpic_k=args.mpic_k,
                    router=args.router, deadline_s=args.deadline_s,
                    smoke=args.smoke, layers=args.layers)
    return 1 if rep["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
