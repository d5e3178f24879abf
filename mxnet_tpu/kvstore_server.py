"""KVStore server bootstrap + worker command channel
(ref: python/mxnet/kvstore_server.py:28-73; profiler command handling:
src/kvstore/kvstore_dist_server.h:276-287, include/mxnet/kvstore.h:49).

The reference blocks server-role processes in a ps-lite serving loop. The
TPU-native communication layer has no server role — reduction is collective
— so this module exists for launch-script compatibility: a process started
with a server role simply initializes the distributed runtime and joins the
collective group as a (passive) worker.

What DOES survive from the server design is the **command channel**: the
reference ships profiler commands (kSetConfig/kState/kPause/kDump) from a
worker to server processes over ps-lite so a training job can profile a
remote process. Here every worker runs a tiny TCP command endpoint
(`start_command_server`, port = MXTPU_CMD_PORT_BASE + rank, default base =
coordinator port + 100, host resolved via MXTPU_WORKER_HOSTS from the
launcher) and `send_command(rank, head, body)` is the client. The
KVStoreDistTPU profiler-command surface (`send_profiler_command`) and the
C API's MXKVStoreSendCommmandToServers ride on it.
"""
from __future__ import annotations

import json
import socket
import struct
import threading

from .base import env

__all__ = ["init_distributed", "KVStoreServer", "_init_kvstore_server_module",
           "start_command_server", "send_command", "worker_command_address"]


def init_distributed() -> bool:
    """Initialize jax.distributed from MXTPU_* env (set by tools/launch.py).

    Returns True if a multi-process group was joined.
    """
    coord = env.get("MXTPU_COORDINATOR")
    nproc = int(env.get("MXTPU_NUM_WORKERS"))
    rank = int(env.get("MXTPU_WORKER_ID"))
    if not coord or nproc <= 1:
        return False
    import jax
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nproc, process_id=rank)
    start_command_server()
    return True


# ---------------------------------------------------------------------------
# Worker command channel (profiler remote control et al.)
# ---------------------------------------------------------------------------

_cmd_server = None
_cmd_lock = threading.Lock()


def _cmd_port(rank: int) -> int:
    base = int(env.get("MXTPU_CMD_PORT_BASE"))
    if base <= 0:
        coord = env.get("MXTPU_COORDINATOR")
        if ":" not in coord:
            return 0
        base = int(coord.rsplit(":", 1)[1]) + 100
    return base + rank


def worker_command_address(rank: int):
    """(host, port) of worker `rank`'s command endpoint, from the
    launcher's MXTPU_WORKER_HOSTS placement (single-host jobs default to
    loopback)."""
    hosts = [h for h in env.get("MXTPU_WORKER_HOSTS").split(",")
             if h]
    host = hosts[rank] if rank < len(hosts) else "127.0.0.1"
    if host in ("localhost",):
        host = "127.0.0.1"
    return host, _cmd_port(rank)


def _recv_exact(conn, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("command peer closed mid-message")
        buf += chunk
    return buf


def _send_msg(conn, obj) -> None:
    payload = json.dumps(obj).encode()
    conn.sendall(struct.pack(">I", len(payload)) + payload)


def _recv_msg(conn):
    (n,) = struct.unpack(">I", _recv_exact(conn, 4))
    return json.loads(_recv_exact(conn, n).decode())


def _handle_command(head: str, body: str) -> str:
    """Dispatch one remote command; returns the reply payload.

    Heads mirror KVStoreServerProfilerCommand (kvstore.h:49):
    profiler.set_config <- kSetConfig, profiler.state <- kState,
    profiler.pause/resume <- kPause, profiler.dump/dumps <- kDump.
    """
    from . import profiler
    if head == "profiler.set_config":
        profiler.set_config(**json.loads(body or "{}"))
        return ""
    if head == "profiler.state":
        profiler.set_state(body or "stop")
        return ""
    if head == "profiler.pause":
        profiler.pause()
        return ""
    if head == "profiler.resume":
        profiler.resume()
        return ""
    if head == "profiler.dump":
        # write the chrome-trace file on the remote side AND return it,
        # so the controller collects the trace without a shared fs
        profiler.dump()
        with open(profiler._config["filename"]) as f:
            return f.read()
    if head == "profiler.dumps":
        return profiler.dumps()
    raise ValueError(f"unknown worker command {head!r}")


def _serve(sock) -> None:
    while True:
        try:
            conn, _ = sock.accept()
        except OSError:
            return
        with conn:
            try:
                req = _recv_msg(conn)
                if req.get("token", "") != _cmd_token():
                    raise PermissionError("bad or missing command token")
                payload = _handle_command(req.get("head", ""),
                                          req.get("body", ""))
                _send_msg(conn, {"ok": True, "payload": payload})
            except Exception as e:  # reply, don't kill the server thread
                try:
                    _send_msg(conn, {"ok": False, "error": str(e)})
                except Exception:
                    pass


def _cmd_token() -> str:
    """Shared job token (MXTPU_CMD_TOKEN, set by tools/launch.py): every
    command must carry it. Without a token the endpoint binds LOOPBACK
    only — an unauthenticated 0.0.0.0 listener whose set_config can point
    the dump at an arbitrary path would hand remote control to any
    network peer."""
    return env.get("MXTPU_CMD_TOKEN")


def start_command_server():
    """Bind this worker's command endpoint (idempotent). Returns the
    bound port, or None when no distributed env / port is configured."""
    global _cmd_server
    with _cmd_lock:
        if _cmd_server is not None:
            return _cmd_server[1]
        rank = int(env.get("MXTPU_WORKER_ID"))
        port = _cmd_port(rank)
        if port <= 0:
            return None
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind(("" if _cmd_token() else "127.0.0.1", port))
        sock.listen(8)
        t = threading.Thread(target=_serve, args=(sock,), daemon=True,
                             name="mxtpu-cmd-server")
        t.start()
        _cmd_server = (sock, port, t)
        return port


def send_command(rank: int, head: str, body: str = "",
                 timeout: float = 30.0) -> str:
    """Send one command to worker `rank`'s endpoint; returns its reply
    payload (raises MXNetError on a remote error).

    Connect refusals are retried until `timeout`: a peer that returned
    from the jax.distributed rendezvous may not have bound its endpoint
    yet (start_command_server runs just after initialize())."""
    import time
    from .base import MXNetError
    host, port = worker_command_address(rank)
    deadline = time.monotonic() + timeout
    while True:
        try:
            conn = socket.create_connection((host, port), timeout=timeout)
            break
        except (ConnectionRefusedError, socket.timeout):
            # only the documented bind race retries; unreachable hosts /
            # DNS errors (other OSErrors) fail fast
            if time.monotonic() >= deadline:
                raise
            time.sleep(0.1)
    with conn:
        _send_msg(conn, {"head": head, "body": body,
                         "token": _cmd_token()})
        rep = _recv_msg(conn)
    if not rep.get("ok"):
        raise MXNetError(f"worker {rank} command {head!r} failed: "
                         f"{rep.get('error')}")
    return rep.get("payload", "")


class KVStoreServer:
    """(ref: kvstore_server.py KVStoreServer) — compatibility shell."""

    def __init__(self, kvstore):
        self.kvstore = kvstore

    def run(self) -> None:
        # no serving loop: collectives have no server side
        pass


def _init_kvstore_server_module() -> None:
    init_distributed()
