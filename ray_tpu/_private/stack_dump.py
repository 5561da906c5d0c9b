"""Live stack dumps for every runtime process (`ray-tpu stack`).

Analog of ray: `ray stack` (python/ray/scripts/scripts.py), which
py-spy-attaches to local worker processes.  py-spy is not in this
environment; instead every runtime process installs ONE SIGUSR1 handler
at startup that appends all-thread stacks and the coroutine stacks of
its event loops to a per-pid file under /tmp/ray_tpu_stacks/.  The CLI
signals every live runtime process and prints the fresh dumps — the
"what is everyone doing right now" debugging tool for hangs.
"""
from __future__ import annotations

import os
import signal
import sys
import time

STACK_DIR = "/tmp/ray_tpu_stacks"

_LOOPS = None          # weakref.WeakSet of event loops to introspect


def proc_stat(pid: int) -> list[str] | None:
    """Fields 3.. of /proc/<pid>/stat ([0] the state, [19] the start
    time), or None when the process is gone."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            # comm (field 2) may hold spaces and parentheses.
            return f.read().rsplit(")", 1)[1].split()
    except (OSError, IndexError):
        return None


def _start_time(pid: int) -> str | None:
    """The process's start time in clock ticks since boot: with the pid
    it names ONE process, where the pid alone names whichever process
    drew it last."""
    stat = proc_stat(pid)
    return stat[19] if stat and len(stat) > 19 else None


def _header_start(path: str) -> str | None:
    """The start time a pid file's header line was written with."""
    try:
        with open(path) as f:
            words = f.readline().split()
    except OSError:
        return None
    return next((w[6:] for w in words if w.startswith("start=")), None)


def unregister(pid: int) -> None:
    """Remove every pid file of `pid` (the agent's reaper calls this for
    a worker it reaps; install() for files an earlier owner of its own
    pid left)."""
    try:
        names = os.listdir(STACK_DIR)
    except OSError:
        return
    for name in names:
        if name.split("_", 1)[0] == str(pid):
            try:
                os.unlink(os.path.join(STACK_DIR, name))
            except OSError:
                pass


def register_loop(loop) -> None:
    """Make an event loop's COROUTINE stacks visible to `ray-tpu stack`.
    faulthandler sees only threads; a runtime wedged inside a pending
    await (an un-replied RPC, a lost fill) shows every thread idle in
    poll/select — the round-5 10k-args wedge was invisible until this.
    Called by controller/agent/worker loop startup."""
    global _LOOPS
    import weakref

    if _LOOPS is None:
        _LOOPS = weakref.WeakSet()
    _LOOPS.add(loop)


def _dump_loop_tasks(loop, fileobj) -> None:
    """Coroutine stacks for one loop — runs ON that loop (scheduled via
    call_soon_threadsafe), so task state isn't raced and a wedged MAIN
    thread can't block the dump."""
    import asyncio

    try:
        tasks = asyncio.all_tasks(loop)
        fileobj.write(f"\n--- asyncio tasks: {len(tasks)} "
                      f"(loop {id(loop):#x}) ---\n")
        for t in tasks:
            try:
                fileobj.write(f"task {t.get_name()}: {t.get_coro()!r}\n")
                for fr in t.get_stack(limit=16):
                    fileobj.write(
                        f"  at {fr.f_code.co_filename}:{fr.f_lineno} "
                        f"in {fr.f_code.co_name}\n")
            except Exception as e:  # noqa: BLE001
                fileobj.write(f"  <stack unavailable: {e!r}>\n")
        fileobj.flush()
    except Exception:  # noqa: BLE001
        pass


def _dump_asyncio_tasks(fileobj) -> None:
    """Write a synchronous task-count summary (best-effort — racing the
    loop is acceptable for one line), then schedule the full per-task
    dump ONTO each registered loop so it runs loop-side."""
    import asyncio

    for loop in list(_LOOPS or ()):
        try:
            n = len(asyncio.all_tasks(loop))
            fileobj.write(f"\n[tasks] loop {id(loop):#x}: {n} tasks; "
                          "full stacks follow when the loop runs\n")
            fileobj.flush()
            loop.call_soon_threadsafe(_dump_loop_tasks, loop, fileobj)
        except Exception:  # noqa: BLE001
            continue


def install(role: str) -> None:
    """Register this process's SIGUSR1 handler (all-thread stacks, then
    the coroutine stacks) and publish its pid file.  Called on the main
    thread from controller/agent/worker/client-host/client-proxy
    startup.

    ONE signal and a Python-level handler: it dumps under the GIL, so
    no thread's frames move while they are read.  The two handlers this
    replaces (`faulthandler.register` on SIGUSR1, whose dump runs in the
    signal handler itself beside running threads, and a Python handler
    on SIGUSR2) ended a process signalled with both at once with
    SIGSEGV: controllers, agents and workers of a cluster under a 1 Hz
    `collect()` died with code -11 (PERF.md section 7 item 13a).  A
    runtime process's main thread sits in its event loop or in an
    Event.wait, both of which a signal wakes, so the handler runs at
    once.

    The pid file appears (via rename) only AFTER the handler is
    registered: the collector signals exactly the processes that have
    one, and SIGUSR1's default disposition is Term — a half-registered
    process must stay invisible.  The header carries `start=` (this
    process's start time), so the collector never signals a LATER
    process that drew the pid."""
    import faulthandler

    tmp = None
    try:
        os.makedirs(STACK_DIR, exist_ok=True)
        unregister(os.getpid())
        path = os.path.join(STACK_DIR, f"{os.getpid()}_{role}.txt")
        tmp = path + ".reg"
        f = open(tmp, "w", buffering=1)   # noqa: SIM115 - held for life

        def _on_usr1(signum, frame):
            try:
                faulthandler.dump_traceback(file=f, all_threads=True)
                _dump_asyncio_tasks(f)
            except Exception:  # noqa: BLE001
                pass

        signal.signal(signal.SIGUSR1, _on_usr1)
        f.write(f"# {role} pid={os.getpid()} "
                f"start={_start_time(os.getpid())} argv={sys.argv[:3]}\n")
        os.replace(tmp, path)
    except (OSError, ValueError, AttributeError):
        # No handler (signal.signal off the main thread raises
        # ValueError): stay invisible to collect().
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass


def collect(timeout_s: float = 3.0, only=None) -> str:
    """Signal every REGISTERED runtime process and return the fresh
    dumps (driver side of `ray-tpu stack`).  A pid file registers the
    process it was written for, not its pid: a process is signalled
    only when its live start time equals the header's (a process that
    merely drew an old pid has no handler yet, and SIGUSR1's default
    disposition is Term), and a file whose process has gone is removed.
    `only` narrows the set to the given pids: STACK_DIR is one per
    machine, and a caller that shares the machine with other clusters
    (a test beside other tests) leaves theirs alone."""
    t_signal = time.time()
    try:
        names = sorted(os.listdir(STACK_DIR))
    except OSError:
        names = []
    pids, live_names = [], []
    for name in names:
        try:
            pid = int(name.split("_", 1)[0])
        except ValueError:
            continue
        if not name.endswith(".txt") or (
                only is not None and pid not in only):
            continue
        path = os.path.join(STACK_DIR, name)
        start = _header_start(path)
        if start is not None and start == _start_time(pid):
            try:
                os.kill(pid, signal.SIGUSR1)
                pids.append(pid)
                live_names.append(name)
                continue
            except (ProcessLookupError, PermissionError):
                pass
        # Its process is gone (whoever holds the pid now): clean up.
        try:
            os.unlink(path)
        except OSError:
            pass
    time.sleep(min(timeout_s, 0.2 + 0.05 * len(pids)))
    chunks = [f"signalled {len(pids)} runtime processes: {pids}"]
    for name in live_names:
        path = os.path.join(STACK_DIR, name)
        try:
            if os.path.getmtime(path) < t_signal - 1.0:
                continue                      # no fresh dump arrived
            size = os.path.getsize(path)
            with open(path) as f:
                if size > 8192:
                    f.seek(size - 8192)
                content = f.read()
        except OSError:
            continue
        chunks.append(f"===== {name} =====\n" + content)
    return "\n".join(chunks)
