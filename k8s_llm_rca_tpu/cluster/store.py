"""Fleet-scale cache fabric: a cross-host PrefixStore service.

PR 10's tiered prefix cache made the RCA sweep's shared metagraph/
stategraph preambles nearly free — but only within one process.  Once the
fleet went multi-process (PR 12), cross-host (PR 13) and disaggregated
(PR 14), every crash-restart, drain migration and prefill-death fallback
re-prefilled from scratch: the store each engine demoted into died with
the engine.  This module moves the store out of the engine process:

- ``StoreServer`` — parent-side handle for a store worker subprocess
  serving ``put``/``get``/``probe``/``stats`` over the CRC-framed wire
  codec (cluster/wire.py), over the PR 12 stdio pipes or a PR 13 TCP
  socket that any number of engine workers dial concurrently.
- ``RemoteStore`` — a client presenting the exact ``PrefixStore``
  surface (``contains``/``put``/``get``/``n_host``/``n_disk``), so
  ``build_replicas``, ``build_proc_replicas``, supervisor ``rebuild()``
  and ``TierRouter`` plug it in unchanged.
- ``StoreFabric`` — the soak-facing bundle (server + client + exercise
  bookkeeping) that faults/soak.py attaches to a chaos run.

The one wire/disk format
    The payload of every store op is the page-record frame produced by
    ``utils/pages.py:encode_page_record`` — byte-for-byte the content of
    a ``PrefixStore`` L2 ``<hex>.page`` file (engine/prefix.py:_to_disk)
    and a legal ``utils/wal.py`` record, because all three layers share
    ``wal.HEADER``/``wal.MAX_RECORD_SIZE``.  A record written by L2 disk
    is servable verbatim over the wire; the server persists exactly the
    bytes it was shipped and never decodes them (it runs without JAX or
    numpy — pages are opaque checksummed blobs to it).

The failure contract — the third tier of the tree's three
    The WAL *recovers* a clean prefix (torn tails are normal); the wire
    *raises* (a torn frame means the peer is gone).  A shared cache is
    neither: it is an optimization, so every failure mode here — torn or
    corrupt frame, ``WireTimeout``, dead server, version-mismatched
    record, fault-plan drop/partition — degrades to a *silent cold miss
    plus a counted metric* (``engine.prefix_store_misses_remote``),
    never an engine error.  A dead store turns the fleet local-only; it
    cannot become a new single point of failure.

Faultability
    ``RemoteStore`` polls its OWN seeded plan once per store op at
    ``inject.SITE_STORE`` (kinds drop/corrupt/delay/partition/heal),
    mirroring the netem link discipline; ``faults/supervisor.py``'s
    ``StoreKiller`` SIGKILLs and heals the server process between
    incidents.  Both compose with the existing killers because
    SITE_STORE is a new, disjoint site.

The reference's cache story is an in-process ``functools.lru_cache`` on
the metagraph loader (graph_loader.py:41-44 in /root/reference); it has
no notion of cross-process reuse, which is exactly the gap the paper's
100-incident sweep makes expensive.
"""

from __future__ import annotations

import base64
import binascii
import json
import os
import select
import signal
import socket
import subprocess
import sys
import time
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

from k8s_llm_rca_tpu.cluster.wire import (
    FrameReader, WireEOF, WireError, WireTimeout, pack_frame, write_frame,
)
from k8s_llm_rca_tpu.utils import wal
from k8s_llm_rca_tpu.utils.logging import METRICS, get_logger

log = get_logger(__name__)

STORE_TRANSPORTS = ("pipe", "socket")

# the store worker answers from RAM/disk with no model in the loop, so
# RPCs are fast; a short deadline keeps a wedged server from stalling an
# engine tick for longer than a cold prefill would have cost anyway
DEFAULT_STORE_RPC_TIMEOUT_S = 5.0
DEFAULT_STORE_SPAWN_TIMEOUT_S = 60.0

_LEASH_CHUNK = 4096


def _store_env() -> Dict[str, str]:
    """Spawn environment for the store worker: PYTHONPATH is the repo
    root, and JAX_PLATFORMS is pinned to the CPU like every fleet worker
    (cluster/proc.py worker_env) even though the store worker never
    imports jax: pages are opaque bytes to it."""
    import k8s_llm_rca_tpu

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(k8s_llm_rca_tpu.__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = root
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _valid_frame(data: bytes) -> bool:
    """True iff ``data`` is exactly one well-formed CRC frame — the same
    check ``decode_page_record`` starts with, minus the numpy decode the
    server cannot (and need not) perform."""
    for _payload, end in wal.iter_records(data):
        return end == len(data)
    return False


# ---------------------------------------------------------------------------
# worker side (no jax, no numpy: pages are opaque checksummed blobs)
# ---------------------------------------------------------------------------


class _FrameStore:
    """The server's two-tier byte store: L1 host RAM (OrderedDict, LRU),
    L2 disk (``<hex>.page`` files, the PrefixStore on-disk format and
    atomic temp+fsync+``os.replace`` recipe — engine/prefix.py:207-226),
    both capped by entry count.  Mirrors ``PrefixStore`` semantics
    exactly so local and remote tiers are interchangeable: L1-first
    insert, LRU overflow demotes to disk, corrupt disk entries are
    dropped on read (cold miss, never an error)."""

    def __init__(self, host_pages: int = 0, disk_dir: Optional[str] = None,
                 disk_pages: int = 0):
        if host_pages < 0 or disk_pages < 0:
            raise ValueError("store tier capacities must be >= 0, got "
                             f"host_pages={host_pages} disk_pages={disk_pages}")
        if disk_pages > 0 and disk_dir is None:
            raise ValueError("disk_pages > 0 requires disk_dir")
        self.host_pages = int(host_pages)
        self.disk_dir = disk_dir
        self.disk_pages = int(disk_pages)
        self._l1: "OrderedDict[bytes, bytes]" = OrderedDict()
        self._l2: "OrderedDict[bytes, str]" = OrderedDict()
        if disk_dir is not None:
            os.makedirs(disk_dir, exist_ok=True)
            # re-index what a previous incarnation persisted: sorted for
            # determinism (the LRU order of a dead process is gone)
            for name in sorted(os.listdir(disk_dir)):
                if not name.endswith(".page"):
                    continue
                try:
                    key = bytes.fromhex(name[:-5])
                except ValueError:
                    continue
                self._l2[key] = os.path.join(disk_dir, name)

    def _path(self, key: bytes) -> str:
        return os.path.join(self.disk_dir, key.hex() + ".page")

    def _to_disk(self, key: bytes, frame: bytes) -> None:
        if self.disk_pages <= 0:
            return                      # no disk tier: LRU overflow drops
        path = self._path(key)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(frame)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        self._l2[key] = path
        self._l2.move_to_end(key)
        while len(self._l2) > self.disk_pages:
            old, old_path = self._l2.popitem(last=False)
            try:
                os.unlink(old_path)
            except OSError:
                pass

    def put(self, key: bytes, frame: bytes) -> bool:
        """Store one validated frame; returns False when the frame fails
        the CRC check or every tier is full-and-capped to zero."""
        if not _valid_frame(frame):
            return False
        if self.host_pages <= 0 and self.disk_pages <= 0:
            return False
        if key in self._l1:
            self._l1.move_to_end(key)
            return True
        self._l1[key] = frame
        while len(self._l1) > max(0, self.host_pages):
            old, old_frame = self._l1.popitem(last=False)
            self._to_disk(old, old_frame)
        return True

    def get(self, key: bytes) -> Optional[Tuple[bytes, int]]:
        frame = self._l1.get(key)
        if frame is not None:
            self._l1.move_to_end(key)
            return frame, 1
        path = self._l2.get(key)
        if path is not None:
            self._l2.move_to_end(key)
            try:
                with open(path, "rb") as f:
                    frame = f.read()
            except OSError:
                frame = None
            if frame is not None and _valid_frame(frame):
                return frame, 2
            # corrupt/torn disk entry: drop it — cold miss, never an error
            self._l2.pop(key, None)
            try:
                os.unlink(path)
            except OSError:
                pass
        return None

    def contains(self, key: bytes) -> bool:
        return key in self._l1 or key in self._l2

    @property
    def n_host(self) -> int:
        return len(self._l1)

    @property
    def n_disk(self) -> int:
        return len(self._l2)


def _handle_store_op(msg: Dict[str, Any], store: _FrameStore,
                     stats: Dict[str, float],
                     inc: int) -> Tuple[Dict[str, Any], bool]:
    """One decoded request -> ``(reply, drain)`` — shared by the pipe
    loop and the socket loop so both transports speak the identical op
    surface.  Malformed requests get ``ok: False`` replies (which the
    client degrades to a cold miss); they never kill the server."""
    op = msg.get("op")
    reply: Dict[str, Any] = {"id": msg.get("id"), "inc": inc, "ok": True}
    if op == "drain":
        reply["drain"] = True
        return reply, True
    if op == "stats":
        reply["stats"] = dict(stats, n_host=store.n_host,
                              n_disk=store.n_disk, pid=os.getpid())
        return reply, False
    try:
        key = bytes.fromhex(msg["key"])
    except (KeyError, TypeError, ValueError):
        return {"id": msg.get("id"), "inc": inc, "ok": False,
                "err": "bad key"}, False
    if op == "put":
        try:
            frame = base64.b64decode(msg["page"], validate=True)
        except (KeyError, TypeError, binascii.Error):
            stats["rejected"] += 1
            return {"id": msg.get("id"), "inc": inc, "ok": False,
                    "err": "bad page"}, False
        stats["puts"] += 1
        if store.put(key, frame):
            return reply, False
        stats["rejected"] += 1
        return {"id": msg.get("id"), "inc": inc, "ok": False,
                "err": "rejected"}, False
    if op == "get":
        stats["gets"] += 1
        hit = store.get(key)
        if hit is None:
            stats["misses"] += 1
            reply["hit"] = False
        else:
            frame, tier = hit
            stats[f"hits_l{tier}"] += 1
            reply["hit"] = True
            reply["tier"] = tier
            reply["page"] = base64.b64encode(frame).decode("ascii")
        return reply, False
    if op == "probe":
        reply["hit"] = store.contains(key)
        return reply, False
    return {"id": msg.get("id"), "inc": inc, "ok": False,
            "err": f"unknown op {op!r}"}, False


def _fresh_stats() -> Dict[str, float]:
    return {"puts": 0.0, "gets": 0.0, "hits_l1": 0.0, "hits_l2": 0.0,
            "misses": 0.0, "rejected": 0.0}


def _serve_store_pipe(out, store: _FrameStore, inc: int) -> int:
    """Stdio-pipe mode: ready frame, then one reply per request until
    drain or stdin EOF (the store never outlives its parent)."""
    write_frame(out, {"op": "ready", "id": -1, "inc": inc,
                      "pid": os.getpid()})
    stats = _fresh_stats()
    reader = FrameReader(sys.stdin.buffer)
    while True:
        try:
            msg = reader.read_frame()
        except WireEOF:
            return 0
        reply, drain = _handle_store_op(msg, store, stats, inc)
        write_frame(out, reply)
        if drain:
            return 0


def _serve_store_listen(spec: Dict[str, Any], out, store: _FrameStore,
                        inc: int) -> int:
    """``--listen`` socket mode: announce the port in a ``listening``
    bootstrap frame on stdout, then serve ANY number of concurrent
    client links — unlike the proc worker's single fenced link, store
    ops are content-addressed and idempotent, so there is no split-brain
    to fence against and every engine in the fleet may dial in.  stdin
    is the lifetime leash (proc.py:_serve_listen discipline): EOF there
    means the parent is gone and the store exits 0."""
    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind((spec.get("listen_host", "127.0.0.1"),
                   int(spec.get("listen_port", 0))))
    listener.listen(16)
    port = listener.getsockname()[1]
    write_frame(out, {"op": "listening", "id": -1, "inc": inc,
                      "pid": os.getpid(), "port": port})
    stats = _fresh_stats()
    leash = sys.stdin.buffer
    conns: Dict[socket.socket, FrameReader] = {}
    try:
        while True:
            rlist = [leash, listener] + list(conns)
            readable, _, _ = select.select(rlist, [], [])
            if leash in readable:
                if not os.read(leash.fileno(), _LEASH_CHUNK):
                    return 0          # parent went away
            if listener in readable:
                fresh, _addr = listener.accept()
                conns[fresh] = FrameReader(fresh.makefile("rb", buffering=0))
            for conn in [c for c in readable
                         if isinstance(c, socket.socket) and c in conns]:
                reader = conns[conn]
                first = True
                while True:
                    try:
                        if first:
                            # short deadline: a partial frame parks until
                            # the rest of its bytes arrive (the reader
                            # buffers what it got)
                            msg = reader.read_frame(timeout_s=0.05)
                            first = False
                        else:
                            # drain every complete frame this wakeup
                            # delivered without touching the stream again
                            msg = reader.pending()
                            if msg is None:
                                break
                    except WireTimeout:
                        break
                    except (WireError, OSError):
                        conns.pop(conn, None)
                        conn.close()
                        break
                    reply, drain = _handle_store_op(msg, store, stats, inc)
                    try:
                        conn.sendall(pack_frame(reply))
                    except OSError:
                        conns.pop(conn, None)
                        conn.close()
                        break
                    if drain:
                        return 0
    finally:
        for conn in conns:
            conn.close()
        listener.close()


def store_main(argv) -> int:
    """Store worker entry (``python -m k8s_llm_rca_tpu.cluster.store``).
    Claims the real stdout fd for frames first and repoints
    ``sys.stdout`` at stderr (proc.py:worker_main discipline), so a
    stray print garbles a log line instead of a frame."""
    out = sys.stdout.buffer
    sys.stdout = sys.stderr
    args = list(argv)
    mode = "pipe"
    if args and args[0] == "--listen":
        mode = "listen"
        args = args[1:]
    if len(args) != 1:
        raise SystemExit("usage: python -m k8s_llm_rca_tpu.cluster.store "
                         "[--listen] '<spec-json>'")
    spec = json.loads(args[0])
    inc = int(spec.get("incarnation", 0))
    store = _FrameStore(host_pages=int(spec.get("host_pages", 0)),
                        disk_dir=spec.get("disk_dir"),
                        disk_pages=int(spec.get("disk_pages", 0)))
    if mode == "listen":
        return _serve_store_listen(spec, out, store, inc)
    return _serve_store_pipe(out, store, inc)


# ---------------------------------------------------------------------------
# parent side
# ---------------------------------------------------------------------------


class StoreServer:
    """Parent-side handle for one store worker process.

    Spawns the worker, waits for its bootstrap frame, and exposes a
    synchronous ``rpc`` (raising ``WireError``/``OSError`` on any
    transport failure — the RemoteStore above it is what degrades those
    to cold misses).  ``kill``/``respawn`` are the ``StoreKiller``'s
    levers: SIGKILL loses L1 (host RAM) but a respawned incarnation
    re-indexes the surviving L2 ``.page`` files from ``disk_dir``, so a
    healed store is disk-warm — the same asymmetry a real host reboot
    has."""

    def __init__(self, host_pages: int = 64, disk_dir: Optional[str] = None,
                 disk_pages: int = 0, transport: str = "pipe",
                 listen_host: str = "127.0.0.1",
                 spawn_timeout_s: float = DEFAULT_STORE_SPAWN_TIMEOUT_S,
                 rpc_timeout_s: float = DEFAULT_STORE_RPC_TIMEOUT_S):
        if transport not in STORE_TRANSPORTS:
            raise ValueError(f"unknown store transport {transport!r}: "
                             f"expected one of {STORE_TRANSPORTS}")
        if host_pages < 0 or disk_pages < 0:
            raise ValueError("store tier capacities must be >= 0, got "
                             f"host_pages={host_pages} "
                             f"disk_pages={disk_pages}")
        if disk_pages > 0 and disk_dir is None:
            raise ValueError("disk_pages > 0 requires disk_dir")
        if host_pages == 0 and disk_pages == 0:
            raise ValueError("a store with zero host AND disk capacity "
                             "can never serve a hit; give it at least "
                             "one tier")
        self.host_pages = int(host_pages)
        self.disk_dir = disk_dir
        self.disk_pages = int(disk_pages)
        self.transport = transport
        self.listen_host = listen_host
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.rpc_timeout_s = float(rpc_timeout_s)
        self.incarnation = 0
        self.port: Optional[int] = None
        self.pid: Optional[int] = None
        self._proc: Optional[subprocess.Popen] = None
        self._reader: Optional[FrameReader] = None
        self._sock: Optional[socket.socket] = None
        self._sock_reader: Optional[FrameReader] = None
        self._next_id = 0
        self._spawn()

    # ------------------------------------------------------------ spawn

    def _spawn(self) -> None:
        spec: Dict[str, Any] = {"host_pages": self.host_pages,
                                "disk_dir": self.disk_dir,
                                "disk_pages": self.disk_pages,
                                "incarnation": self.incarnation}
        argv = [sys.executable, "-m", "k8s_llm_rca_tpu.cluster.store"]
        if self.transport == "socket":
            spec["listen_host"] = self.listen_host
            if self.port is not None:
                # a healed store keeps its address so addr-mode clients
                # (engine workers holding only host:port) recover too
                spec["listen_port"] = self.port
            argv.append("--listen")
        argv.append(json.dumps(spec, sort_keys=True))
        self._proc = subprocess.Popen(argv, stdin=subprocess.PIPE,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.DEVNULL,
                                      env=_store_env())
        self._reader = FrameReader(self._proc.stdout)
        try:
            boot = self._reader.read_frame(timeout_s=self.spawn_timeout_s)
        except WireError:
            if (self.transport == "socket"
                    and spec.get("listen_port") is not None):
                # the old port was taken while the store was dead: give
                # up on address stability rather than on the heal
                self._reap()
                self.port = None
                return self._spawn()
            self._reap()
            raise
        self.pid = int(boot.get("pid", -1))
        if self.transport == "socket":
            self.port = int(boot["port"])
        METRICS.inc("cluster.store_spawns")
        from k8s_llm_rca_tpu.obs import trace as obs_trace

        obs_trace.event("cluster.store.serve", pid=self.pid,
                        inc=self.incarnation, transport=self.transport,
                        port=self.port if self.port is not None else -1)

    @property
    def addr(self) -> Tuple[str, int]:
        if self.transport != "socket" or self.port is None:
            raise ValueError("addr is only meaningful for a socket-"
                             "transport store server")
        return (self.listen_host, self.port)

    def alive(self) -> bool:
        return self._proc is not None and self._proc.poll() is None

    # -------------------------------------------------------------- rpc

    def _socket_link(self) -> Tuple[socket.socket, FrameReader]:
        if self._sock is None:
            sock = socket.create_connection(self.addr, timeout=2.0)
            sock.settimeout(None)
            self._sock = sock
            self._sock_reader = FrameReader(sock.makefile("rb", buffering=0))
        return self._sock, self._sock_reader

    def _drop_socket_link(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        self._sock = None
        self._sock_reader = None

    def rpc(self, msg: Dict[str, Any],
            timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """One request/reply over whichever transport the server runs.
        Raises ``WireError``/``OSError`` on ANY failure; callers that
        want the cold-miss contract go through ``RemoteStore``."""
        deadline = (timeout_s if timeout_s is not None
                    else self.rpc_timeout_s)
        self._next_id += 1
        rid = self._next_id
        msg = dict(msg, id=rid)
        if self.transport == "pipe":
            if not self.alive():
                raise WireEOF("store server is dead")
            write_frame(self._proc.stdin, msg)
            reader = self._reader
        else:
            try:
                sock, reader = self._socket_link()
                sock.sendall(pack_frame(msg))
            except OSError:
                # one re-dial per op: the server may have healed since
                # the link died
                self._drop_socket_link()
                sock, reader = self._socket_link()
                sock.sendall(pack_frame(msg))
        t0 = time.monotonic()
        while True:
            left = deadline - (time.monotonic() - t0)
            if left <= 0:
                raise WireTimeout(f"store rpc {msg.get('op')!r} timed out "
                                  f"after {deadline:.1f}s")
            try:
                reply = reader.read_frame(timeout_s=left)
            except WireError:
                if self.transport == "socket":
                    self._drop_socket_link()
                raise
            if reply.get("id") == rid:
                return reply
            # stale reply from an op that timed out earlier: discard

    # ------------------------------------------------------- lifecycle

    def kill(self) -> None:
        """SIGKILL, as a crash does it: no drain, L1 lost, L2 survives."""
        if self._proc is not None and self._proc.poll() is None:
            try:
                os.kill(self._proc.pid, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                pass
            self._proc.wait()
        self._drop_socket_link()

    def respawn(self) -> None:
        """Heal: next incarnation, same spec (and same port when it can
        be rebound), disk tier re-indexed by the fresh process."""
        self.kill()
        self.incarnation += 1
        self._spawn()

    def _reap(self) -> None:
        if self._proc is not None:
            if self._proc.poll() is None:
                try:
                    os.kill(self._proc.pid, signal.SIGKILL)
                except (OSError, ProcessLookupError):
                    pass
            self._proc.wait()

    def close(self) -> None:
        """Polite shutdown: close stdin (the leash — the worker exits 0
        on EOF), escalate to TERM/KILL if it lingers."""
        self._drop_socket_link()
        if self._proc is None:
            return
        if self._proc.poll() is None:
            try:
                self._proc.stdin.close()
            except OSError:
                pass
            try:
                self._proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                self._proc.terminate()
                try:
                    self._proc.wait(timeout=2.0)
                except subprocess.TimeoutExpired:
                    os.kill(self._proc.pid, signal.SIGKILL)
                    self._proc.wait()
        else:
            self._proc.wait()


class RemoteStore:
    """Client half: the exact ``PrefixStore`` surface, served remotely.

    ``PrefixCache`` (engine/prefix.py) talks to its store through three
    calls — ``contains``/``put``/``get`` — plus the capacity attributes;
    this class implements that surface over ``StoreServer.rpc`` (or a
    bare ``addr`` for engine workers that dialed in from another
    process) and enforces the fabric's failure contract: EVERY failure
    is a silent cold miss counted as ``engine.prefix_store_misses_remote``
    (through the engine's own ``count`` hook once the paged engine binds
    it, METRICS otherwise), never an exception out of a cache call.

    ``plan`` is the store's OWN seeded FaultPlan, polled exactly once
    per store op at ``inject.SITE_STORE``:

    - ``drop``      — the op silently never happens (miss);
    - ``corrupt``   — one payload byte is flipped, so the CRC/decoder
                      rejects it downstream (put poisons nothing: the
                      server's frame check refuses it; get returns an
                      undecodable record — both land as cold misses);
    - ``delay``     — virtual-clock sleep (plan.clock), then proceed;
    - ``partition`` — the link is severed and STAYS severed (every op
                      misses) until a scheduled ``heal`` fault or
                      ``heal_partition()`` clears it.
    """

    def __init__(self, server: Optional[StoreServer] = None,
                 addr: Optional[Tuple[str, int]] = None,
                 plan=None,
                 rpc_timeout_s: float = DEFAULT_STORE_RPC_TIMEOUT_S,
                 count=None):
        if (server is None) == (addr is None):
            raise ValueError("RemoteStore needs exactly one of server= "
                             "(in-parent handle) or addr= (dial a socket "
                             "store from another process)")
        self._server = server
        self._addr = (str(addr[0]), int(addr[1])) if addr is not None else None
        self._sock: Optional[socket.socket] = None
        self._sock_reader: Optional[FrameReader] = None
        self._next_id = 0
        self.plan = plan
        self.rpc_timeout_s = float(rpc_timeout_s)
        self.count = count if count is not None else METRICS.inc
        self._partitioned = False
        # PrefixStore duck attributes: capacity lives server-side; the
        # local view advertises none so nothing double-budgets it
        self.host_pages = server.host_pages if server is not None else 0
        self.disk_dir = None
        self.disk_pages = server.disk_pages if server is not None else 0

    # ------------------------------------------------------- transport

    def bind_count(self, count) -> None:
        """The paged engine rebinds miss-counting onto its per-tick
        ``_count`` hook so misses flow into TickSample/Chrome/Prometheus
        alongside the other prefix counters."""
        self.count = count

    def _dial_rpc(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        if self._sock is None:
            sock = socket.create_connection(self._addr, timeout=2.0)
            sock.settimeout(None)
            self._sock = sock
            self._sock_reader = FrameReader(sock.makefile("rb", buffering=0))
        self._next_id += 1
        rid = self._next_id
        msg = dict(msg, id=rid)
        self._sock.sendall(pack_frame(msg))
        t0 = time.monotonic()
        while True:
            left = self.rpc_timeout_s - (time.monotonic() - t0)
            if left <= 0:
                raise WireTimeout("store rpc timed out")
            reply = self._sock_reader.read_frame(timeout_s=left)
            if reply.get("id") == rid:
                return reply

    def _sever(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        self._sock = None
        self._sock_reader = None
        if self._server is not None:
            self._server._drop_socket_link()

    def heal_partition(self) -> None:
        self._partitioned = False

    def _rpc(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Raises on failure; ``_op`` turns raises into misses."""
        if self._server is not None:
            return self._server.rpc(msg, timeout_s=self.rpc_timeout_s)
        try:
            return self._dial_rpc(msg)
        except (WireError, OSError):
            # one re-dial per op: the server may have healed in place
            self._sever()
            return self._dial_rpc(msg)

    def _poll_fault(self):
        """One SITE_STORE poll per store op — the seam's own plan, so
        its draws never perturb any other site's schedule."""
        if self.plan is None:
            return None
        from k8s_llm_rca_tpu.faults import inject

        fault = self.plan.poll(inject.SITE_STORE)
        if fault is None:
            return None
        if fault.kind == "heal":
            self._partitioned = False
            return None
        if fault.kind == "delay":
            self.plan.clock.sleep(fault.delay_s)
            return None
        if fault.kind == "partition":
            self._partitioned = True
            self._sever()
            return fault
        return fault                    # drop / corrupt

    def _miss(self, op: str, n: float = 1.0) -> None:
        self.count("engine.prefix_store_misses_remote", n)
        METRICS.inc(f"cluster.store_degraded_{op}")

    # ------------------------------------------------- PrefixStore API

    def contains(self, key: bytes) -> bool:
        fault = self._poll_fault()
        if self._partitioned or (fault is not None
                                 and fault.kind in ("drop", "partition")):
            self._miss("probe")
            return False
        try:
            reply = self._rpc({"op": "probe", "key": key.hex()})
        except (WireError, OSError):
            self._miss("probe")
            return False
        if not reply.get("ok"):
            self._miss("probe")
            return False
        return bool(reply.get("hit"))

    def put(self, key: bytes, rec: Dict[str, Any]) -> None:
        from k8s_llm_rca_tpu.obs import trace as obs_trace
        from k8s_llm_rca_tpu.utils import pages

        fault = self._poll_fault()
        if self._partitioned or (fault is not None
                                 and fault.kind in ("drop", "partition")):
            self._miss("put")
            return
        try:
            frame = pages.encode_page_record(rec)
        except ValueError:
            self._miss("put")           # oversized record: local drop
            return
        if fault is not None and fault.kind == "corrupt":
            # flip one payload byte: the server's CRC check refuses the
            # frame, so a corrupt put can never poison the store
            pos = wal.HEADER_SIZE
            frame = frame[:pos] + bytes([frame[pos] ^ 0xFF]) + frame[pos + 1:]
        try:
            reply = self._rpc({"op": "put", "key": key.hex(),
                               "page": base64.b64encode(frame)
                               .decode("ascii")})
        except (WireError, OSError):
            self._miss("put")
            return
        if not reply.get("ok"):
            self._miss("put")
            return
        obs_trace.event("cluster.store.put", key=key.hex()[:12],
                        nbytes=len(frame))

    def get(self, key: bytes) -> Optional[Tuple[Dict[str, Any], int]]:
        from k8s_llm_rca_tpu.obs import trace as obs_trace
        from k8s_llm_rca_tpu.utils import pages

        fault = self._poll_fault()
        if self._partitioned or (fault is not None
                                 and fault.kind in ("drop", "partition")):
            self._miss("get")
            return None
        try:
            reply = self._rpc({"op": "get", "key": key.hex()})
        except (WireError, OSError):
            self._miss("get")
            return None
        if not reply.get("ok"):
            self._miss("get")
            return None
        if not reply.get("hit"):
            return None                 # honest miss: not a degradation
        try:
            frame = base64.b64decode(reply["page"], validate=True)
        except (KeyError, TypeError, binascii.Error):
            self._miss("get")
            return None
        if fault is not None and fault.kind == "corrupt":
            pos = wal.HEADER_SIZE
            frame = frame[:pos] + bytes([frame[pos] ^ 0xFF]) + frame[pos + 1:]
        rec = pages.decode_page_record(frame)
        if rec is None:
            # torn/corrupt/version-mismatched record: identical cold miss
            self._miss("get")
            return None
        tier = int(reply.get("tier", 1))
        obs_trace.event("cluster.store.get", key=key.hex()[:12], tier=tier)
        return rec, tier

    # ---------------------------------------------------- introspection

    def stats(self) -> Dict[str, Any]:
        try:
            reply = self._rpc({"op": "stats"})
        except (WireError, OSError):
            return {}
        return reply.get("stats", {}) if reply.get("ok") else {}

    @property
    def n_host(self) -> int:
        return int(self.stats().get("n_host", 0))

    @property
    def n_disk(self) -> int:
        return int(self.stats().get("n_disk", 0))


# ---------------------------------------------------------------------------
# soak-facing bundle
# ---------------------------------------------------------------------------


class StoreFabric:
    """Server + client + exercise bookkeeping for a chaos soak.

    ``run_chaos_soak(store_fabric=...)`` drives ``exercise(i)`` once per
    incident: a deterministic synthetic page record round-trips through
    the remote store, and the outcome lands ONLY in this object's
    counters — never in the soak report — which is exactly how the
    byte-identity bar is honest: the store is genuinely exercised across
    every kill/heal the ``StoreKiller`` schedules, and the report bytes
    cannot know whether a fabric was attached."""

    def __init__(self, server: StoreServer, remote: RemoteStore):
        self.server = server
        self.remote = remote
        self.exercised = 0
        self.put_ok = 0
        self.hits = 0
        self.misses = 0

    def _synthetic_record(self, i: int) -> Tuple[bytes, Dict[str, Any]]:
        import hashlib

        import numpy as np

        key = hashlib.sha1(b"store-fabric-%d" % i).digest()
        rng = np.random.default_rng(i)
        rec = {"n_pages": 1,
               "k": rng.standard_normal((1, 1, 4, 8), dtype=np.float32),
               "v": rng.standard_normal((1, 1, 4, 8), dtype=np.float32)}
        return key, rec

    def exercise(self, i: int) -> bool:
        """One put+get round trip keyed by incident index; True on hit.
        Failures are the fabric's own business (counted here), invisible
        to the report."""
        import numpy as np

        key, rec = self._synthetic_record(i)
        self.exercised += 1
        self.remote.put(key, rec)
        got = self.remote.get(key)
        if got is None:
            self.misses += 1
            return False
        back, _tier = got
        if not all(np.array_equal(back[f], rec[f]) for f in rec):
            self.misses += 1
            return False
        self.put_ok += 1
        self.hits += 1
        return True

    def close(self) -> None:
        self.server.close()


def build_store_fabric(transport: str = "socket", host_pages: int = 64,
                       disk_dir: Optional[str] = None, disk_pages: int = 0,
                       plan=None,
                       rpc_timeout_s: float = DEFAULT_STORE_RPC_TIMEOUT_S
                       ) -> StoreFabric:
    """The one-call soak/test recipe: spawn a store server and wrap it
    with a parent-handle RemoteStore (which survives kill/heal because
    it reaches the server through the handle, not a frozen address)."""
    server = StoreServer(host_pages=host_pages, disk_dir=disk_dir,
                         disk_pages=disk_pages, transport=transport,
                         rpc_timeout_s=rpc_timeout_s)
    remote = RemoteStore(server=server, plan=plan,
                         rpc_timeout_s=rpc_timeout_s)
    return StoreFabric(server, remote)


if __name__ == "__main__":
    raise SystemExit(store_main(sys.argv[1:]))
