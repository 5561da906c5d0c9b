"""Zygote worker spawner: fork pre-warmed workers in milliseconds.

Analog of ray's prestarted idle worker pool (ray: worker_pool.cc
PrestartWorkers / the pool keeping warm processes ahead of demand) —
taken one step further for slow-import hosts: instead of N cold
`python -m worker_main` interpreters (~2s of imports EACH, serialized on
a small host), the agent keeps ONE warm "zygote" process that has paid
the import cost once and `os.fork()`s a worker per request.  A 24-actor
burst then costs 24 forks (~ms each) instead of 24 interpreter boots.

Protocol (unix socket, one persistent connection from the agent; JSON
lines):
  agent -> zygote: {"id": n, "env": {...}, "stdout": path, "stderr": path}
  zygote -> agent: {"id": n, "pid": p}        fork reply
                   {"exit": pid, "code": c}   child reaped (async)

Safety rules: the zygote stays single-threaded and never initializes a
jax backend or creates sockets/loops beyond the one listener — fork then
inherits nothing that breaks.  Children close the zygote's fds, redirect
stdio to their log files, update os.environ, and enter worker_main.main()
exactly as a fresh interpreter would.  Worker liveness: children watch
the AGENT's pid (RAY_TPU_AGENT_PID), not their direct parent — a zygote
restart must not take live actors down with it.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import struct
import sys

_MSG = struct.Struct("!I")


def _send(conn: socket.socket, obj: dict) -> None:
    raw = json.dumps(obj).encode()
    conn.sendall(_MSG.pack(len(raw)) + raw)


def _recv(conn: socket.socket) -> dict | None:
    hdr = b""
    while len(hdr) < _MSG.size:
        chunk = conn.recv(_MSG.size - len(hdr))
        if not chunk:
            return None
        hdr += chunk
    (n,) = _MSG.unpack(hdr)
    raw = b""
    while len(raw) < n:
        chunk = conn.recv(n - len(raw))
        if not chunk:
            return None
        raw += chunk
    return json.loads(raw)


def _child_enter(req: dict, inherited: list) -> None:
    """Post-fork child: detach from the zygote, become a worker."""
    for fd in inherited:
        try:
            os.close(fd)
        except OSError:
            pass
    # Per-worker log files (the agent tails these).
    for path, fileno in ((req.get("stdout"), 1), (req.get("stderr"), 2)):
        if path:
            fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                         0o644)
            os.dup2(fd, fileno)
            os.close(fd)
    os.environ.update(req["env"])
    # The zygote pre-imported the runtime, so import-time env hooks
    # never saw THIS worker's env: re-sync what depends on it.  update()
    # cannot REMOVE keys, so a spec the agent has since cleared would
    # survive in the zygote's stale env and re-arm disarmed sites —
    # drop it explicitly when the spawn env carries none.
    from ray_tpu._private import failpoints

    if failpoints.ENV_VAR not in req["env"]:
        os.environ.pop(failpoints.ENV_VAR, None)
    failpoints.reload_from_env()
    signal.signal(signal.SIGCHLD, signal.SIG_DFL)
    from ray_tpu._private import worker_main

    worker_main.main()
    os._exit(0)


# ----------------------------------------------------------- agent side
class ZygoteProc:
    """Popen-shaped handle for a zygote-forked worker (the agent's
    reaper/OOM-killer only need poll/terminate/kill/returncode)."""

    def __init__(self, pid: int, spawner: "ZygoteSpawner"):
        self.pid = pid
        self._spawner = spawner

    @property
    def returncode(self):
        return self._spawner.exit_codes.get(self.pid)

    def poll(self):
        rc = self._spawner.exit_codes.get(self.pid)
        if rc is not None:
            return rc
        try:
            os.kill(self.pid, 0)
            return None
        except ProcessLookupError:
            # Gone without a reaper report (zygote itself died).
            self._spawner.exit_codes.setdefault(self.pid, -1)
            return -1
        except PermissionError:
            return None

    def terminate(self):
        try:
            os.kill(self.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass

    def kill(self):
        try:
            os.kill(self.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    def wait(self, timeout: float | None = None):
        import time

        deadline = None if timeout is None else time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(f"pid {self.pid} still running")
            time.sleep(0.02)
        return self.returncode


class ZygoteSpawner:
    """Agent-side handle: boots the zygote subprocess in the background,
    then serves ~ms spawn() calls.  Any failure → spawn() returns None
    and the caller cold-spawns (never worse than the classic path)."""

    def __init__(self, temp_dir: str):
        import subprocess
        import tempfile
        import threading

        self.exit_codes: dict[int, int] = {}
        self._lock = threading.Lock()
        self._pending: dict[int, tuple[threading.Event, dict]] = {}
        self._next_id = 1
        self._conn: socket.socket | None = None
        self._ready = threading.Event()
        self._failed = False
        os.makedirs(temp_dir, exist_ok=True)
        self.sock_path = tempfile.mktemp(prefix="raytpu_zygote_",
                                         suffix=".sock", dir=temp_dir)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.zygote",
             "--socket", self.sock_path],
            stdout=subprocess.PIPE, env={**os.environ,
                                         "JAX_PLATFORMS": "cpu"})
        threading.Thread(target=self._boot, daemon=True,
                         name="raytpu-zygote-boot").start()

    def _boot(self) -> None:
        import threading

        try:
            line = self.proc.stdout.readline()
            if b"READY" not in line:
                raise RuntimeError(f"zygote announced {line!r}")
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.connect(self.sock_path)
            self._conn = conn
            threading.Thread(target=self._reader, daemon=True,
                             name="raytpu-zygote-read").start()
            self._ready.set()
        except Exception:  # noqa: BLE001 - fall back to cold spawns
            self._failed = True
            self._ready.set()

    def _reader(self) -> None:
        while True:
            try:
                msg = _recv(self._conn)
            except OSError:
                msg = None
            if msg is None:
                self._failed = True
                # Unblock any waiter.
                with self._lock:
                    for ev, _slot in self._pending.values():
                        ev.set()
                return
            if "exit" in msg:
                self.exit_codes[msg["exit"]] = msg["code"]
                continue
            with self._lock:
                entry = self._pending.pop(msg.get("id"), None)
            if entry is not None:
                ev, slot = entry
                slot.update(msg)
                ev.set()

    def spawn(self, env: dict, stdout: str | None, stderr: str | None,
              timeout: float = 15.0) -> ZygoteProc | None:
        import threading

        if self._failed:
            return None
        if not self._ready.wait(timeout):
            return None
        if self._failed or self._conn is None:
            return None
        ev, slot = threading.Event(), {}
        with self._lock:
            req_id = self._next_id
            self._next_id += 1
            self._pending[req_id] = (ev, slot)
            try:
                _send(self._conn, {"id": req_id, "env": env,
                                   "stdout": stdout, "stderr": stderr})
            except OSError:
                self._pending.pop(req_id, None)
                self._failed = True
                return None
        if not ev.wait(timeout) or "pid" not in slot:
            with self._lock:
                self._pending.pop(req_id, None)
            return None
        return ZygoteProc(slot["pid"], self)

    def close(self) -> None:
        try:
            if self._conn is not None:
                self._conn.close()
        except OSError:
            pass
        if self.proc.poll() is None:
            self.proc.terminate()
        try:
            os.unlink(self.sock_path)
        except OSError:
            pass


def main() -> None:
    sock_path = sys.argv[sys.argv.index("--socket") + 1]
    agent_pid = os.getppid()

    # Pre-warm: pay the import bill once, fork it for free afterwards.
    # Imports only — no backend init, no sockets, no threads.  NOT jax:
    # eagerly importing it here taxed EVERY agent boot ~2s (each test
    # cluster pays it), while plain workers don't import jax at boot at
    # all anymore (worker_main._pin_jax_platform defers to the env var
    # when jax isn't loaded) — a child only pays the import when its
    # actor actually uses jax.
    import ray_tpu._private.worker_main  # noqa: F401
    import ray_tpu._private.worker  # noqa: F401
    # Pre-freeze the warmed import graph: children inherit the permanent
    # generation, so their own tune_gc() collect walks only post-fork
    # objects.
    import gc

    gc.collect()
    gc.freeze()

    listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        os.unlink(sock_path)
    except FileNotFoundError:
        pass
    listener.bind(sock_path)
    listener.listen(1)

    # Self-pipe: SIGCHLD wakes the select loop to reap + report.
    rpipe, wpipe = os.pipe()
    os.set_blocking(wpipe, False)

    def _on_chld(_sig, _frm):
        try:
            os.write(wpipe, b"x")
        except OSError:
            pass

    signal.signal(signal.SIGCHLD, _on_chld)
    print("READY", flush=True)

    # An agent that died before it connected must not leave this
    # process waiting in accept() for ever.
    listener.settimeout(1.0)
    while True:
        if os.getppid() != agent_pid:
            os._exit(0)
        try:
            conn, _ = listener.accept()
            break
        except socket.timeout:
            continue
    conn.settimeout(None)
    import select

    children: set[int] = set()
    while True:
        if os.getppid() != agent_pid:
            os._exit(0)                 # agent died; children self-watch
        readable, _, _ = select.select([conn, rpipe], [], [], 1.0)
        if rpipe in readable:
            os.read(rpipe, 4096)
            while True:
                try:
                    pid, status = os.waitpid(-1, os.WNOHANG)
                except ChildProcessError:
                    break
                if pid == 0:
                    break
                children.discard(pid)
                code = os.waitstatus_to_exitcode(status)
                try:
                    _send(conn, {"exit": pid, "code": code})
                except OSError:
                    pass
        if conn in readable:
            req = _recv(conn)
            if req is None:
                os._exit(0)             # agent closed the socket
            store = req["env"].get("RAY_TPU_STORE_NAME")
            if store:
                # Pre-fork arena warm: map + prefault the node store ONCE
                # here so every child inherits the populated mapping
                # (native_store.preheat_for_fork; fork carries VMAs and
                # PTEs along).  Best-effort — children fall back to their
                # own lazy map.
                try:
                    from ray_tpu._private import native_store

                    native_store.preheat_for_fork(store)
                except Exception:  # noqa: BLE001
                    pass
            pid = os.fork()
            if pid == 0:
                _child_enter(req, [conn.fileno(), listener.fileno(),
                                   rpipe, wpipe])
            children.add(pid)
            try:
                _send(conn, {"id": req["id"], "pid": pid})
            except OSError:
                os._exit(0)


if __name__ == "__main__":
    main()
