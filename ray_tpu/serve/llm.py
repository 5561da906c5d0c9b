"""Continuous-batched LLM inference engine + Serve deployment.

`LLMEngine` decodes a fixed array of lanes over one paged KV pool, for
whichever model `ray_tpu.models.serving_model` knows; `LLMServer` is the
Serve replica around it (streaming, KV migration between pools, adapters,
live weight sync).  The analog is vLLM on Ray Serve, rebuilt TPU-first
rather than ported.

TPU-native shape (SURVEY §7 "Serve continuous batching on TPU"):
  - ONE jitted decode program over a fixed [max_batch] slot array —
    sequences join/leave slots between steps; shapes never change, so XLA
    compiles exactly one decode program (plus one prefill program per
    prompt-length bucket).
  - KV cache is a donated jit argument: decode updates alias in place
    (no per-step cache copy in HBM).
  - Prompt lengths are bucketed to powers of two; padding rows produce
    garbage K/V that the decode mask never admits (the model's prefill).
  - Sampling (greedy / temperature) happens on device; only the [B]
    next-token vector crosses to the host per step.

Paged KV memory is managed by serve/kv_blocks.py (refcounted blocks,
radix prefix cache, COW) — this file owns the SCHEDULER on top of it:
  - admission matches each prompt's longest cached prefix and prefills
    only the suffix (`prefill_from`);
  - blocks are allocated lazily, one decode window ahead; when the pool
    runs dry the NEWEST request is preempted (blocks committed to the
    prefix cache + released, request re-queued for recompute);
  - sampling keys are per-request (fold_in(engine key, request seed,
    token index)), so a preempted-and-recomputed request draws the same
    tokens it would have drawn uninterrupted — preemption is
    deterministic under seeded sampling, hence testable.
Kill switches: RAY_TPU_PREFIX_CACHE=0 disables prefix matching,
RAY_TPU_KV_PREEMPT=0 restores full-span up-front allocation with FIFO
head-of-line blocking (the pre-block-manager admission semantics).

The engine loop runs on one thread inside the replica actor; requests
arrive via a thread-safe queue and resolve concurrent.futures.Futures,
so the Serve router's async path and the engine's step loop compose.
"""
from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import gc
import json
import os
import queue
import resource
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from ray_tpu import memledger
from ray_tpu import tracing
from ray_tpu.exceptions import AdapterLoadError
from ray_tpu.serve import slo
from ray_tpu.serve.kv_blocks import BlockManager
from ray_tpu.serve.prefill_plan import (FLOOR_TOKENS, floor_positions,
                                        plan_wave, programs_under)


def _buckets_for(max_len: int, smallest: int = 32) -> list[int]:
    out, b = [], smallest
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


from ray_tpu.serve.kv_router import env_on as _env_on


def _pool(cache: dict) -> dict:
    """The page pool of a model's cache (`init_paged_cache`): every
    entry but the lanes' positions and their state, each a list of
    [n_pages, heads, rows, width] leaves, one a layer that keeps rows.
    A leaf holds a row a token (`rows` = the page size: K, V, a latent
    row) or a row a GROUP of positions (`rows` = page size / positions a
    row: a pooled index key): `_per_row` reads which from the shape, and
    the leaf's tail and its merge follow.  The engine takes the leaves'
    names and shapes from here and from nowhere else."""
    return {name: leaves for name, leaves in cache.items()
            if name not in ("pos", "state")}


def _per_row(leaf, page: int) -> int:
    """Positions that share ONE row of a pool leaf whose pages cover
    `page` positions (1: a row a token)."""
    return page // leaf.shape[2]


def _check_pool_role(role: str, decode_deployment) -> None:
    """The pool-role combination rules, shared by LLMServer.__init__
    and reconfigure (the declarative schema enforces the same rules at
    config time — ENGINE_ROLES is its source of truth)."""
    from ray_tpu.serve.schema import ENGINE_ROLES

    if role not in ENGINE_ROLES:
        raise ValueError(
            f"engine role must be one of {list(ENGINE_ROLES)}, "
            f"got {role!r}")
    if role == "prefill" and decode_deployment is None:
        raise ValueError(
            "role='prefill' requires decode_deployment (the decode "
            "pool this replica ships KV to) — a prefill pool with no "
            "decode pool cannot serve")
    if role != "prefill" and decode_deployment is not None:
        raise ValueError(
            f"decode_deployment only applies to role='prefill' (got "
            f"role={role!r}) — a dangling decode target would "
            "silently serve unified")


def _check_paged(paged: bool) -> None:
    """`paged` is kept for the callers that still pass `paged=True`
    (ROADMAP D13); the page pool is the only KV layout."""
    if not paged:
        raise ValueError(
            "paged=False: the dense KV layout was removed; the engine "
            "is always paged (drop the argument)")


def _pow2(n: int) -> int:
    """Smallest power of two >= n: the shared width-bucketing rule of
    the COW / import / export padding paths (one copy — the compile
    count and pad waste must never diverge between them)."""
    m = 1
    while m < n:
        m *= 2
    return m


_METRICS = None
_METRICS_LOCK = threading.Lock()

# Every program JAX builds in this process, whoever asked for it: the
# three stages JAX itself times, by the events it reports them under.
# PROCESS-WIDE (one replica holds one engine), cumulative since the
# first LLMEngine was constructed, on with RAY_TPU_TRACE=0 too.
_BUILD_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_BUILDS = {"program_builds": 0,         # compile stages
           "program_build_s": 0.0,      # outermost stages' seconds
           "program_cache_misses": 0}   # compile stages the cache missed
_BUILDS_LOCK = threading.Lock()
_BUILD_OPEN = threading.local()     # .depth: stages open on this thread
_build_listening = False            # under _BUILDS_LOCK
_build_root = None                  # under _BUILDS_LOCK: see _programs_root
# (t0, t1) of the newest outermost stages, and the start of each thread's
# outermost stage still open: a stall's `build_ms`
_BUILD_RECENT: collections.deque = collections.deque(maxlen=1024)
_BUILD_OPEN_AT: dict = {}
# a stage that runs INSIDE another (a jitted helper traced while its
# caller is) and ends under this gets no span: one program's build must
# not flood the ring.  Its seconds are its caller's already.
_BUILD_SPAN_MIN_S = 1e-3


def _on_build_stage_start(event: str, start: float, **_kw) -> None:
    if event in _BUILD_STAGES:
        _BUILD_OPEN.depth = getattr(_BUILD_OPEN, "depth", 0) + 1
        _BUILD_OPEN.cache = "off"
        if _BUILD_OPEN.depth == 1:
            _BUILD_OPEN_AT[threading.get_ident()] = start


def _on_cache_event(event: str, **_kw) -> None:
    # inside the compile stage, on its thread: asked, then maybe hit
    if event == _CACHE_ASKED:
        _BUILD_OPEN.cache = "miss"
    elif event == _CACHE_HIT:
        _BUILD_OPEN.cache = "hit"


def _on_build_stage_end(event: str, t0: float, t1: float,
                        fun_name: str = "", **_kw) -> None:
    """One stage of one program's build ended: JAX's own start and end,
    on `time.time()`, the flight recorder's clock."""
    stage = _BUILD_STAGES.get(event)
    if stage is None:
        return
    depth = _BUILD_OPEN.depth = max(0, getattr(_BUILD_OPEN, "depth", 1) - 1)
    attrs = {"fun": fun_name, "stage": stage, "depth": depth,
             "thread": threading.current_thread().name}
    with _BUILDS_LOCK:
        if depth == 0:      # nested stages' seconds lie inside this one's
            _BUILDS["program_build_s"] += t1 - t0
            _BUILD_RECENT.append((t0, t1))
            _BUILD_OPEN_AT.pop(threading.get_ident(), None)
        if stage == "compile":
            attrs["cache"] = getattr(_BUILD_OPEN, "cache", "off")
            _BUILDS["program_builds"] += 1
            _BUILDS["program_cache_misses"] += attrs["cache"] == "miss"
    if tracing.ENABLED and (depth == 0 or stage == "compile"
                            or t1 - t0 >= _BUILD_SPAN_MIN_S):
        tracing.emit("llm.program_build", t0, t1, ctx=_build_ctx(),
                     attrs=attrs)


def _build_ctx() -> tuple | None:
    """What a build's span hangs off: the traced request it was built
    inside, if any, else this process's one zero-length `llm.programs`
    root (as the loop's phases hang off `llm.engine`).  Never a trace of
    its own: `tracing.slowest` and `attribution` would rank every stage
    of every build as a request."""
    return tracing.current() or _programs_root()


def _programs_root() -> tuple | None:
    """This process's one zero-length `llm.programs` span: what the
    builds outside any request and the collector's pauses hang off."""
    global _build_root
    with _BUILDS_LOCK:
        if _build_root is None:
            now = time.time()
            _build_root = tracing.emit("llm.programs", now, now,
                                       ctx=(tracing.new_id(), ""))
        return _build_root


def _listen_for_program_builds() -> None:
    """Register the listeners, once a process.  Called by LLMEngine's
    constructor and not at import: this module imports no JAX, and a
    driver that imports it stays off JAX."""
    global _build_listening
    from jax import monitoring

    with _BUILDS_LOCK:
        if _build_listening:
            return
        _build_listening = True
        monitoring.register_scalar_listener(_on_build_stage_start)
        monitoring.register_event_listener(_on_cache_event)
        monitoring.register_event_time_span_listener(_on_build_stage_end)


# The cyclic collector's pauses, PROCESS-wide like the builds: whichever
# thread starts a collection holds the interpreter for its whole length,
# and the engine thread then stands with no CPU of its own.  No lock:
# collections do not nest (the interpreter runs one at a time, its
# callbacks inside it), and one that starts on a thread inside
# `with _BUILDS_LOCK` must not wait for that lock.
_GC = {"gc_pauses": 0, "gc_pause_s": 0.0}
_GC_BY_GENERATION = {g: [0, 0.0] for g in range(3)}    # [pauses, seconds]
# (t0, t1) of the newest pauses: a stall's `gc_ms`
_GC_RECENT: collections.deque = collections.deque(maxlen=1024)
_GC_SPAN_MIN_S = 1e-3       # a shorter pause is counted and has no span
_gc_open = None             # (wall, perf_counter) at a collection's start
_gc_listening = False       # under _BUILDS_LOCK


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        _gc_open = (time.time(), time.perf_counter())
        return
    if _gc_open is None:
        return
    (w0, p0), _gc_open = _gc_open, None
    dur = time.perf_counter() - p0
    _GC["gc_pauses"] += 1
    _GC["gc_pause_s"] += dur
    row = _GC_BY_GENERATION[info["generation"]]
    row[0], row[1] = row[0] + 1, row[1] + dur
    _GC_RECENT.append((w0, w0 + dur))
    root = _build_root      # read, never made here: see the lock above
    if dur >= _GC_SPAN_MIN_S and tracing.ENABLED and root is not None:
        tracing.emit("llm.gc_pause", w0, w0 + dur, ctx=root, attrs={
            "generation": info["generation"],
            "collected": info["collected"],
            "uncollectable": info["uncollectable"],
            "thread": _thread_row(threading.current_thread().name)})


def _listen_for_gc_pauses() -> None:
    """Register the collector's callback, once a process, beside the
    program-build listeners, and make the root its spans hang off (every
    call: the recorder may have been off at the first).  Nothing here
    changes what the collector does."""
    global _gc_listening
    _programs_root()
    with _BUILDS_LOCK:
        if _gc_listening:
            return
        _gc_listening = True
        gc.callbacks.append(_on_gc)


# The ledger's rows.  A thread is filed under the first of these its
# name starts with (a pool's threads are "<prefix>_<n>", an actor's
# executors "actor-<id>[-<group>]_<n>") and any other thread under
# "other": the rows, and with them the labels of
# serve_llm_thread_cpu_seconds, are a fixed set however many
# connections, actors and default-named threads come and go.
_THREAD_ROWS = ("llm-engine", "llm-kv-export", "llm-stall-watch",
                "serve-call", "actor", "task-exec", "raytpu-io",
                "raytpu-putcopy", "asyncio", "MainThread")


def _thread_row(name: str) -> str:
    return next((r for r in _THREAD_ROWS if name.startswith(r)), "other")


def _thread_cpu_ledger() -> dict:
    """CPU seconds of this PROCESS's live Python threads by row
    (`_THREAD_ROWS`), beside the process's and the wall clock at the
    reading (two readings give a rate).  Over an interval, what the rows
    other than `llm-engine` gained is what could have held the GIL while
    the engine thread stood; `process_cpu_s` less the sum is the native
    threads (the XLA runtime, transfers) and Python threads that have
    ended."""
    by: dict = {}
    for t in threading.enumerate():
        if t.native_id is None:
            continue
        try:
            # the kernel's CPU clock of thread `native_id`, the id glibc's
            # pthread_getcpuclockid computes, without handing glibc the
            # pthread_t of a thread that may have ended meanwhile
            cpu = time.clock_gettime((~t.native_id << 3) | 6)
        except OSError:     # ended since enumerate()
            continue
        row = _thread_row(t.name)
        by[row] = by.get(row, 0.0) + cpu
    return {"wall_s": time.time(), "process_cpu_s": time.process_time(),
            "by_name": by}


# A stall of the engine loop (the watcher thread `llm-stall-watch`, one
# an engine: LLMEngine._watch).  Constants, not options.
WATCH_S = 0.05          # the watcher's sleep; how LATE it wakes is the
#                         measurement of an interpreter or a process that
#                         did not run
WATCH_LEDGER_EVERY = 4  # wakes between two readings of the thread ledger
#                         and the process's CPU (`_watch_reading`: a
#                         system call a thread); the others read
#                         `perf_counter` alone
HOST_STALL_S = 0.2      # a host phase open this long (ISSUE 50 asked 0.1:
#                         one generation-1 collection a run takes
#                         0.10-0.11 s, in `deliver` when the engine thread
#                         trips it; the longest sound host phase is
#                         `decode_dispatch` at 32.5 ms: PERF.md section 6)
LATE_WAKE_S = 0.4       # the watcher woken this late: twice the latest
#                         wake of a sound run (0.10-0.19 s while the
#                         engine thread loads a warmed program and keeps
#                         the interpreter: PERF.md section 6)
SYNC_STALL_S = 1.0      # a `_sync` phase open this long
SYNC_STALL_X = 3.0      # and this many times the longest seen close on time
# `prefill_dispatch` is in neither list: its last line
# (`_cur_dev.at[slots].set(nxt)`) waits for the wave's programs on the
# device (0.15-0.39 s a wave of the dense model, 1.2 s the ramp's first
# wave of granite, seconds a wave of 1 x 8192 programs: PERF.md section
# 6, PR 50), and the longest wave of a run is the ramp's first, so no
# constant and no multiple of what warm-up showed clears it: only a late
# wake of the watcher opens a stall there
_HOST_PHASES = ("admit", "fund", "decode_dispatch", "deliver")
_SYNC_PHASES = ("prefill_sync", "decode_sync")
# every thread's stack goes to stderr for the first stalls of a process
# with work waiting that no program build explains; then the span and
# the counters only
_STALL_BLOCKS = 16
_stall_blocks = 0


def _watch_reading() -> dict:
    """The watcher's full reading, every WATCH_LEDGER_EVERY wakes and at
    a stall's close: the thread ledger, and the process's page faults and
    context switches (`resource.getrusage`), which tell a page-fault
    storm from a process the machine did not run when a stall's `held`
    is `process`."""
    out = _thread_cpu_ledger()
    out["ru"] = resource.getrusage(resource.RUSAGE_SELF)
    return out


def _overlap_s(intervals, t0: float, t1: float) -> float:
    """Seconds of [t0, t1] the (start, end) pairs cover, summed."""
    return sum(max(0.0, min(t1, b) - max(t0, a)) for a, b in list(intervals))


def _frame_lines(frame, limit: int | None = None) -> list[str]:
    """`dir/file.py:line func` of a thread's frames, innermost first."""
    out = []
    while frame is not None and (limit is None or len(out) < limit):
        code = frame.f_code
        out.append("%s:%d %s" % ("/".join(code.co_filename.split("/")[-2:]),
                                 frame.f_lineno, code.co_name))
        frame = frame.f_back
    return out


def _stall_held(trigger: str, stood_ms: float, late_wake_ms: float,
                process_cpu_ms: float) -> str:
    """Who held the engine thread, from the stall's own numbers and
    nothing else.  The watcher on time: only the engine thread waited,
    for the `device` inside a `_sync` phase, else behind a lock, a queue
    or a blocking call of its own (`engine`: its stack names the line).
    The watcher late (by more than HOST_STALL_S, the shortest stall of a
    host phase): nothing of the interpreter ran; if the process
    burned CPU meanwhile some thread or the collector held the
    `interpreter`, and under a tenth of the wall nobody ran at all
    (`process`: the machine, a page-fault storm, a runtime call that
    sleeps holding the interpreter)."""
    if late_wake_ms <= HOST_STALL_S * 1e3:
        return "device" if trigger == "sync" else "engine"
    return "process" if process_cpu_ms < 0.1 * stood_ms else "interpreter"


# Latency-histogram bucket upper bounds in ms: sub-ms router picks
# through prefills up to pathological multi-second p99s the flight
# recorder exists to attribute.
_MS_BUCKETS = (1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
               1000.0, 2500.0, 5000.0, 10000.0, 30000.0)
# TPOT lives in a much narrower band (17-28 ms on a v5e, PERF.md): the
# general boundaries put every reading into (10, 25].
_TPOT_MS_BUCKETS = (1.0, 2.0, 5.0, 10.0, 15.0, 20.0, 25.0, 30.0, 40.0,
                    50.0, 75.0, 100.0, 250.0, 1000.0)


# The engine loop's own work counters, name -> help text.  A served
# model's rows (`ServingSpec.counters`) join them in the engine's ONE
# table (`LLMEngine.work`): every row is a key of stats()["loop"] and a
# Prometheus counter `serve_llm_<name>`.  Ratios an operator reads: pad
# factor = prefill_padded_tokens / prefill_tokens; live lanes per decode
# step = lane_steps_live / decode_steps; attn_steps / attn_steps_dense =
# the share of a grid over every lane and table column that held work.
_LOOP_WORK = {
    "decode_steps": "Decode steps dispatched (K per sync window)",
    "lane_steps_live": "Decode steps x lanes holding a request",
    "attn_steps": "Grid steps of a paged_attn call, summed over sync "
                  "windows: a step a page of a lane holding a request",
    "attn_steps_dense": "Lanes x (table columns + 1), summed over sync "
                        "windows",
    "attn_ctx_rows": "Cached rows the decode attention kernel had to "
                     "attend, summed over live lanes, steps and sync "
                     "windows",
    "prefill_padded_tokens": "Token positions the dispatched prefill "
                             "programs computed (width bucket x length "
                             "bucket)",
    "prefill_programs": "Prefill programs dispatched (one or more a wave: "
                        "serve/prefill_plan.py)",
    "prefill_programs_at_floor": "Prefill programs charged the planner's "
                                 "floor: a pass over the weights and no "
                                 "more",
    "prefill_programs_capped": "Prefill waves a ceiling of the planner "
                               "split (serve/prefill_plan."
                               "PREFILL_MAX_TOKENS, "
                               "PREFILL_MAX_STATE_BYTES)",
    "prefill_waves": "Prefill waves dispatched (one an admission)",
    "prefill_waves_split": "Prefill waves planned as more programs than "
                           "arrival-order chunks",
}


def _engine_metrics(work=_LOOP_WORK):
    """Process-wide serve-LLM metrics (utils.metrics registry → flushed
    to the controller KV → dashboard /metrics Prometheus endpoint), with
    a counter for every row of `work` (an engine's table).  Tagged per
    engine so replicas don't clobber each other."""
    global _METRICS
    with _METRICS_LOCK:
        from ray_tpu.utils import metrics as um

        tk = ("engine",)
        if _METRICS is None:
            _METRICS = {
                "prefill_tokens": um.get_or_create(
                    um.Counter, "serve_llm_prefill_tokens",
                    "Prompt tokens actually prefilled on device", tk),
                "prefix_hit_tokens": um.get_or_create(
                    um.Counter, "serve_llm_prefix_hit_tokens",
                    "Prompt tokens served from the KV prefix cache", tk),
                "decode_tokens": um.get_or_create(
                    um.Counter, "serve_llm_decode_tokens",
                    "Tokens decoded", tk),
                "prefill_floor_positions": um.get_or_create(
                    um.Gauge, "serve_llm_prefill_floor_positions",
                    "Token positions the planner charges a prefill "
                    "program at least (serve/prefill_plan.FLOOR_TOKENS "
                    "x parameters streamed / multiplied a position)", tk),
                # beside phase_s["decode_sync"]: bytes a second the
                # prefix store's demotion fetched off the device
                "demote_bytes": um.get_or_create(
                    um.Counter, "serve_llm_demote_bytes",
                    "Host bytes of KV pages demoted to the prefix "
                    "store (fetched off the device, 2 x layers pieces "
                    "a page)", tk),
                # a phase's wall seconds are stats()["loop"]["phase_s"];
                # what they have more than these is the time the engine
                # thread stood, and thread_cpu_s says who ran meanwhile
                "phase_cpu_s": um.get_or_create(
                    um.Counter, "serve_llm_phase_cpu_seconds",
                    "CPU seconds of the engine thread in each phase of "
                    "its loop (time.thread_time)", ("engine", "phase")),
                "thread_cpu_s": um.get_or_create(
                    um.Counter, "serve_llm_thread_cpu_seconds",
                    "CPU seconds of the replica process's live Python "
                    "threads by a fixed set of rows (a pool's prefix, "
                    "or other); read when stats() is",
                    ("engine", "thread")),
                "program_builds": um.get_or_create(
                    um.Counter, "serve_llm_program_builds",
                    "Programs the process compiled or loaded from the "
                    "compile cache: rising after warm-up is a "
                    "recompile", tk),
                "program_build_s": um.get_or_create(
                    um.Counter, "serve_llm_program_build_seconds",
                    "Seconds the process spent tracing, lowering and "
                    "compiling (or loading) programs", tk),
                "stalls": um.get_or_create(
                    um.Counter, "serve_llm_stalls",
                    "Stalls of the engine loop with work waiting: a host "
                    "phase open over 0.2 s, the watcher thread woken over "
                    "0.4 s late, or a _sync phase open over 1 s and three "
                    "times its longest (the llm.stall span names the "
                    "phase and who held it)", tk),
                "stall_s": um.get_or_create(
                    um.Counter, "serve_llm_stall_seconds",
                    "Seconds the engine loop stood in those stalls", tk),
                "gc_pause_s": um.get_or_create(
                    um.Counter, "serve_llm_gc_pause_seconds",
                    "Seconds the process's cyclic garbage collector held "
                    "the interpreter, by generation",
                    ("engine", "generation")),
                "preemptions": um.get_or_create(
                    um.Counter, "serve_llm_preemptions",
                    "Requests preempted for KV blocks", tk),
                "evictions": um.get_or_create(
                    um.Counter, "serve_llm_kv_evictions",
                    "Cached KV blocks LRU-evicted", tk),
                "completed": um.get_or_create(
                    um.Counter, "serve_llm_requests_completed",
                    "Requests completed", tk),
                "occupancy": um.get_or_create(
                    um.Gauge, "serve_llm_batch_occupancy",
                    "Active slots / max_batch", tk),
                "queue_depth": um.get_or_create(
                    um.Gauge, "serve_llm_queue_depth",
                    "Requests waiting for a batch slot (the telemetry "
                    "timeline's engine-queue series)", tk),
                "free_blocks": um.get_or_create(
                    um.Gauge, "serve_llm_kv_free_blocks",
                    "Free KV blocks in the pool", tk),
                "hit_rate": um.get_or_create(
                    um.Gauge, "serve_llm_prefix_hit_rate",
                    "Prefix-cache hit tokens / prompt tokens", tk),
                "weight_version": um.get_or_create(
                    um.Gauge, "serve_llm_weight_version",
                    "Policy weight version currently decoding (online "
                    "RLHF live weight sync)", tk),
                "weight_updates": um.get_or_create(
                    um.Counter, "serve_llm_weight_updates",
                    "Live weight swaps applied between decode syncs", tk),
                # Request-latency histograms (scraped as proper
                # Prometheus histogram families — _bucket/_sum/_count —
                # by the dashboard /metrics exposition).
                "ttft": um.get_or_create(
                    um.Histogram, "serve_request_ttft_ms",
                    "Time to first token per request (ms)", tk,
                    boundaries=_MS_BUCKETS),
                "tpot": um.get_or_create(
                    um.Histogram, "serve_request_tpot_ms",
                    "Time per output token after the first (ms)", tk,
                    boundaries=_TPOT_MS_BUCKETS),
                "stage": um.get_or_create(
                    um.Histogram, "serve_request_stage_ms",
                    "Per-request stage latency breakdown "
                    "(queue/prefill/decode, ms)", ("engine", "stage"),
                    boundaries=_MS_BUCKETS),
            }
        for name, text in work.items():
            if name not in _METRICS:
                _METRICS[name] = um.get_or_create(
                    um.Counter, "serve_llm_" + name, text, tk)
    return _METRICS


@dataclass
class _Request:
    prompt: list[int]
    max_new_tokens: int
    temperature: float
    eos_id: int | None
    future: concurrent.futures.Future
    submitted_at: float = field(default_factory=time.perf_counter)
    first_token_at: float | None = None
    tokens: list[int] = field(default_factory=list)
    slot: int = -1
    # Optional thread-safe sink for token streaming: every decoded token
    # is pushed as produced; None marks end-of-stream.
    token_queue: Any = None
    # KV blocks owned by this request, in table order (block i covers
    # positions [i*page, (i+1)*page)); released at finish/preempt.
    pages: list[int] = field(default_factory=list)
    # Per-request sampling identity: token at generation index g is
    # drawn from fold_in(fold_in(engine_key, sample_seed), g) — timing,
    # batching and preemption cannot change a request's sample stream.
    sample_seed: int = 0
    # First prompt position this admission actually prefills (everything
    # below it came from the prefix cache; 0 = full prefill).
    prefill_from: int = 0
    # False for warmup traffic: never match or populate the prefix
    # cache (warmup must compile the full-prefill bucket programs).
    cache_ok: bool = True
    preempted: int = 0
    # Prefill-pool mode: finish after the first sampled token and
    # attach the request's KV pages (device → host) to the result so
    # the server can migrate them to a decode replica (kv_export).
    prefill_only: bool = False
    # Migrated-KV admission (kv_import): [2, L, n, kvh, page, hd] host
    # array scattered into freshly-allocated pool pages at admission
    # instead of running prefill.  Cleared right after the scatter —
    # this may be a pinned arena view and must not outlive its use.
    import_kv: Any = None
    import_len: int = 0          # valid KV positions in import_kv
    # Prefix-cache generation at admission: a live weight swap bumps
    # the engine's generation and flushes the radix tree; a request
    # admitted under an older generation must NOT commit its blocks
    # (its KV was computed under the old policy).
    cache_gen: int = 0
    # Flight-recorder context captured at submission ((trace_id,
    # span_id) or None — the engine loop replays it when emitting this
    # request's queue/prefill/decode-window spans) plus the wall-clock
    # stamps those spans need (submitted_at/first_token_at are
    # perf_counter, a different basis).
    trace: Any = None
    t0_wall: float = field(default_factory=time.time)
    admitted_at: float = 0.0       # perf_counter at slot assignment
    admitted_wall: float = 0.0
    # Multi-LoRA identity (serve/lora.py): the adapter this request
    # decodes under (None = base model), resolved at ADMISSION to a
    # device bank slot (0 = the all-zeros base row) plus the KV salt
    # keying its radix/prefix-store entries per (adapter, version).
    model_id: str | None = None
    lora_slot: int = 0
    salt: int = 0

    def emit(self, tok: int | None) -> None:
        if self.token_queue is not None:
            self.token_queue.put(tok)


# The engine thread's timeline: the phases of one loop iteration, in
# order (`llm.loop.<phase>` spans, `stats()["loop"]["phase_s"]` keys).
_LOOP_PHASES = ("admit", "prefill_dispatch", "prefill_sync", "fund",
                "decode_dispatch", "decode_sync", "deliver", "idle")


class LLMEngine:
    """Continuous-batching decode engine over the params of whichever
    model module serves `cfg` (`ray_tpu.models.serving_model`: the
    engine names no model, and reads the module's ONE declaration,
    `models/serving.ServingSpec`).  What a model lacks of the optional
    capabilities (`caps`) the engine refuses at construction; a model
    whose lanes carry state no KV page holds (`lane_state_layers`) is
    served with the prefix cache, suffix prefill and the prefix
    store's demotion off, and `stats()["lane_state"]` says so.  That
    state may be a few rows a lane or gigabytes (a state-space layer
    keeps a matrix a head a lane): it is allocated once, donated through
    the scatter and decode programs, updated in place by the model's own
    kernel, and never copied or selected over whole; the engine never
    looks inside.  The page pool is whatever leaves the model's
    `init_paged_cache` returns (`_pool`): `stats()["cache"]` says what
    it holds."""

    def __init__(self, cfg, params=None, *, max_batch: int = 8,
                 max_len: int | None = None, seed: int = 0,
                 steps_per_sync: int = 8, paged: bool = True,
                 page_size: int = 512, kv_pages: int | None = None,
                 prefix_cache: bool | None = None,
                 kv_preempt: bool | None = None,
                 lora_slots: int = 0, lora_rank: int = 0,
                 lora_targets: tuple | None = None,
                 name: str = "llm"):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import serving_model

        _check_paged(paged)
        _listen_for_program_builds()    # before the engine's own programs
        _listen_for_gc_pauses()
        model = self._model = serving_model(cfg)
        # The model's ONE declaration (models/serving.ServingSpec), read
        # here and nowhere else.  Per-lane state beside the page pool (a
        # convolution's last rows, a state-space layer's matrices): the
        # prefill program returns it taken at each row's TRUE length, the
        # scatter program writes it into the lane where the lanes' state
        # lies (the cache is donated), the decode scan carries it and the
        # model's step updates it in place; the engine never looks
        # inside.
        spec = self._spec = model.serving_spec(cfg)
        caps = spec.caps
        stateful = spec.lane_state_layers > 0
        if lora_slots and "lora" not in caps:
            raise ValueError(
                f"{model.__name__} has no LoRA hooks: lora_slots must "
                "be 0")
        if "prefix" not in caps:
            # A radix hit restores KV pages only: lane state (or, for a
            # model without prefill_with_prefix, the suffix program)
            # cannot follow it.
            why = ("a lane's state is held by no KV page, so a radix "
                   "prefix hit cannot restore it" if stateful else
                   f"{model.__name__} has no prefill_with_prefix")
            if prefix_cache:
                raise ValueError(f"prefix_cache=True refused: {why}")
            prefix_cache = False
        self.cfg = cfg
        self.name = name
        self.max_batch = max_batch
        self.max_len = max_len or cfg.max_seq
        # Decode steps per device→host sync.  Scanning K steps inside
        # ONE compiled program amortizes the sync — the multi-step
        # scheduling discipline of TPU LLM servers.  EOS / admission
        # are checked every K tokens; overshoot is trimmed.  The value
        # is unmeasured on today's chip.
        self.steps_per_sync = max(1, steps_per_sync)
        self.params = params if params is not None else model.init_params(
            jax.random.PRNGKey(seed), cfg)
        self._prefix_cache = (
            prefix_cache if prefix_cache is not None
            else _env_on("RAY_TPU_PREFIX_CACHE"))
        self._preempt_on = (
            kv_preempt if kv_preempt is not None
            else _env_on("RAY_TPU_KV_PREEMPT"))
        # Shared page pool (ops/paged_attention.py): HBM holds the
        # page budget, NOT max_len x slots — max_len can be 32k+
        # while the pool is sized to the expected live footprint.
        # Page 0 is the trash page (idle slots point at it).
        self.page = page_size
        self._maxp = -(-self.max_len // page_size)
        if kv_pages is None:
            kv_pages = 1 + max_batch * (
                -(-min(self.max_len, 4096) // page_size))
        self.n_pages = kv_pages
        self.cache = model.init_paged_cache(cfg, max_batch,
                                            kv_pages, page_size)
        self._cache_info = self._cache_stats()   # shapes: fixed from here
        self._lane_info = self._lane_state_stats()
        # Host-side accounting: refcounted blocks + radix prefix
        # index over pool ids 1..n_pages-1 (serve/kv_blocks.py).
        self._mgr = BlockManager(kv_pages - 1, page_size,
                                 prefix_cache=self._prefix_cache)
        self._table = np.zeros((max_batch, self._maxp), np.int32)
        self._buckets = _buckets_for(self.max_len)
        # Prefill sub-wave cap: a full-width wave serializes the whole
        # burst's forward in front of EVERY first-token fetch (64x128
        # prefill ≈ 40ms compute on a v5e); <=32-wide chunks let the
        # first chunk's tokens reach the host while later chunks are
        # still computing (the fetches overlap via copy_to_host_async).
        self._chunk = min(16, max_batch)
        wide = {1, 8, self._chunk}
        # What the planner charges a program at least, and the programs
        # it plans over (serve/prefill_plan.py).  A model whose every
        # weight multiplies every position: FLOOR_TOKENS and widths
        # {1, 8, chunk} x every bucket.  One that says its programs
        # stream more than a position multiplies: a floor that many
        # times further out, under which two rows cost what one does, so
        # widths 2 and 4 beside them and only the programs that floor
        # leaves distinct.
        streams = spec.prefill_params
        self._prefill_floor = (FLOOR_TOKENS if streams is None
                               else floor_positions(*streams))
        narrow = frozenset() if streams is None else frozenset({2, 4}) - wide
        self._width_buckets = sorted(w for w in wide | narrow
                                     if w <= max_batch)
        self._prefill_programs = None if streams is None else programs_under(
            self._prefill_floor, self._width_buckets, self._buckets, narrow)
        # Per-request sampling base key (see _Request.sample_seed).
        self._base_key = jax.random.PRNGKey(seed + 1)

        # Multi-LoRA device banks (serve/lora.py): per-target stacked
        # [L, n_slots, din, r] / [L, n_slots, r, dout] arrays that a
        # per-request int32 slot index gathers inside the ONE jitted
        # decode/prefill program (models/llama._lora_proj) — adapters
        # swap by bank-row writes, never by retrace.  Slot 0 is the
        # all-zeros base row (y + 0.0 == y exactly), so base and
        # adapter requests mix freely within a batch.
        self.lora_slots = max(0, int(lora_slots))
        self.lora_rank = int(lora_rank) if self.lora_slots else 0
        if self.lora_slots:
            if self.lora_rank < 1:
                raise ValueError(
                    "lora_slots > 0 requires lora_rank >= 1 (bank "
                    "shapes are static — the XLA invariants)")
            dims = model.lora_target_dims(cfg)
            tgts = tuple(lora_targets or model.LORA_TARGETS)
            bad = [t for t in tgts if t not in dims]
            if bad:
                raise ValueError(
                    f"unknown lora targets {bad}; valid: {sorted(dims)}")
            ns = self.lora_slots + 1
            self._lora_banks = {
                t: {"a": jnp.zeros((cfg.n_layers, ns, dims[t][0],
                                    self.lora_rank), cfg.dtype),
                    "b": jnp.zeros((cfg.n_layers, ns, self.lora_rank,
                                    dims[t][1]), cfg.dtype)}
                for t in tgts}
            self._lora_free = list(range(1, ns))
        else:
            self._lora_banks = None
            self._lora_free = []
        # Slot-resolution state: model_id -> bank slot + metadata, the
        # per-lane slot indices the decode program gathers with, and
        # the ONE lock covering evict-choose + map-update AND the
        # admission-time resolution.  load_adapter runs on CALLER
        # threads; the banks dict swaps atomically and jax arrays are
        # immutable, so in-flight dispatches keep the tree they
        # captured.
        self._lora_lock = threading.Lock()
        self._lora_map: dict[str, int] = {}
        self._lora_meta: dict[str, dict] = {}
        self._adapters = np.zeros((max_batch,), np.int32)
        self.adapter_loads = 0
        self.adapter_evictions = 0

        def _sample_rows(logits, temps, keys):
            """Per-row sampling: each row draws from ITS OWN key — the
            sample stream belongs to the request, not to the batch."""
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            sampled = jax.vmap(
                lambda k_, l_, t_: jax.random.categorical(
                    k_, l_ / jnp.maximum(t_, 1e-6)))(
                        keys, logits, temps).astype(jnp.int32)
            return jnp.where(temps > 0, sampled, greedy)

        def _first_token(params, last_h, temps, seeds, starts):
            last = model.project_logits(params, last_h).astype(jnp.float32)
            keys = jax.vmap(
                lambda s, t: jax.random.fold_in(
                    jax.random.fold_in(self._base_key, s), t))(seeds,
                                                               starts)
            return _sample_rows(last, temps, keys)

        # Compiled K-step decode programs, one per sync-window size.
        # K is baked into the scan at trace time (jit caches on
        # argument shapes, never on closure attributes), so the
        # overload ladder's "shrink the sync window" knob needs a
        # factory: each window size compiles once and stays cached.
        def _make_decode(K):
            def _decode_k_paged(params, cache, tokens, temps, table,
                                seeds, starts, lora):
                """Pages stay OUT of the scan carry (read-only during
                the block; a carried write would copy the whole pool
                every step); new rows ride a small dense tail, merged
                into the pages once at block end
                (ops/paged_attention.py).  The lanes' state (whatever
                the model keeps beside the pool: nothing, a few rows a
                lane, or gigabytes of state matrices) rides the carry:
                the cache is donated, the scan's carry is updated where
                it lies, and a model with large state writes it through
                a kernel that aliases its input (`ops/ssm.ssm_update`),
                so no step copies or selects over a leaf of it
                (tests/test_chip_compile.py reads the compiled
                program).  The routed layers' counts
                ([routed layers, 4]: experts hit, largest load,
                assignments, visits; summed over the K steps) come back
                beside `seq`, fetched in the same sync.  The table and the
                block-start positions stand still for the K steps, so
                the attention kernel's work list is built here, once,
                for every layer's call of every step."""
                from ray_tpu.ops.paged_attention import (attention_plan,
                                                         merge_tail_pages)

                ts = cache["pos"]
                # the page pool, by whatever leaves the model's
                # init_paged_cache gave it (a K and a V pool a layer,
                # one latent pool a layer, ...): every leaf is
                # [n_pages, heads, rows, width]; its tail is the same
                # with the rows K positions can complete a lane (K where
                # a leaf holds a row a token)
                pages = _pool(cache)
                with jax.named_scope("attn_plan"):
                    plan = attention_plan(table, ts, self.page)
                tails = jax.tree.map(
                    lambda pool: jnp.zeros(
                        (max_batch, pool.shape[1],
                         -(-K // _per_row(pool, self.page)),
                         pool.shape[3]), pool.dtype), pages)
                lane_keys = jax.vmap(
                    lambda s: jax.random.fold_in(self._base_key,
                                                 s))(seeds)

                def step(carry, j):
                    tails, state, pos, toks = carry
                    logits, tails, state, cnt = model.serve_decode_step(
                        params, pages, tails, state, toks, pos, ts, j,
                        table, cfg, lora, plan)
                    keys = jax.vmap(jax.random.fold_in)(lane_keys,
                                                        starts + j)
                    nxt = _sample_rows(logits, temps, keys)
                    return (tails, state, pos + 1, nxt), (nxt, cnt)

                # a step's counts are the model's own, columns and all:
                # summed over the window, never read here
                (tails, state, pos, last), (seq, counts) = jax.lax.scan(
                    step, (tails, cache["state"], ts, tokens),
                    jnp.arange(K))
                counts = counts.sum(axis=0)
                merged = jax.tree.map(
                    lambda pool, tail: merge_tail_pages(
                        pool, tail, table, ts, K,
                        _per_row(pool, self.page)),
                    pages, tails)
                return seq, last, {**merged, "pos": pos,
                                   "state": state}, counts

            return jax.jit(_decode_k_paged, donate_argnums=(1,))

        self._make_decode = _make_decode
        self._decode_fns = {self.steps_per_sync:
                            _make_decode(self.steps_per_sync)}
        # Live sync-window size: the loop decodes this many steps per
        # host round trip.  Shrunk under sustained overload (smaller
        # windows = more admission points = bounded queued-TTFT at a
        # throughput cost), restored on recovery — see set_sync_window.
        self._k_live = self.steps_per_sync
        self.sync_window_shrinks = 0

        # Wave prefill: ONE forward admits a whole wave of requests.
        # Waves are padded by duplicating the last row (same slot written
        # twice with identical data — harmless), so there is one compile
        # per (width, prompt-length) bucket, not per wave size.  It is
        # SPLIT into two programs: (A) forward + first-token sample,
        # (B) the KV page scatter.  The first-token fetch depends only
        # on A, so its device→host sync overlaps B's per-layer page
        # writes AND later chunks' forwards instead of queueing behind
        # them (round-5 serve-TTFT rework; unmeasured on today's chip).
        def _prefill_fwd_only(params, tokens, true_lens, slots, temps,
                              seeds, starts, lora):
            W = tokens.shape[0]
            # + each row's lane state AT ITS TRUE LENGTH (rows are
            # padded to a length bucket) and the routed layers' counts
            hidden, ks, vs, state, counts = model.serve_prefill(
                params, tokens, cfg, true_lens, lora)
            # Project only the W last-position rows through lm_head (the
            # full [W, P, vocab] logits tensor would be GBs at serving
            # shapes).  Duplicate padding rows carry the same
            # (seed, start), so they draw the SAME sample — cur-token
            # and recorded token can't diverge under temperature.
            last_h = hidden[jnp.arange(W), true_lens - 1]
            nxt = _first_token(params, last_h, temps, seeds, starts)
            return nxt, ks, vs, state, counts

        self._prefill_fwd = jax.jit(_prefill_fwd_only)

        # Prefix-cache suffix prefill (program A'): forward ONLY the
        # tokens the radix cache didn't cover, attending the cached
        # prefix through the page pool (llama.prefill_with_prefix).
        # Same split as above: the scatter rides program B.
        def _prefill_suffix_fwd(params, kp, vp, tokens, pos0, prefix_t,
                                last_idx, temps, seeds, starts, lora):
            W = tokens.shape[0]
            hidden, ks, vs = model.prefill_with_prefix(
                params, tokens, pos0, cfg, kp, vp, prefix_t, lora)
            last_h = hidden[jnp.arange(W), last_idx]
            nxt = _first_token(params, last_h, temps, seeds, starts)
            return nxt, ks, vs

        self._prefill_suffix = jax.jit(_prefill_suffix_fwd)

        self._scatter_pages = jax.jit(
            lambda cache, ks, vs, state, page_ids, rows, slots, true_lens:
            model.serve_scatter(cache, ks, vs, state, page_ids, rows,
                                slots, true_lens),
            donate_argnums=(0,))
        # Suffix scatters start mid-span (prefill_from), so the
        # page-aligned fast paths don't apply — force the coordinate
        # form (see scatter_prefill_pages).
        self._scatter_pages_coord = jax.jit(
            lambda cache, ks, vs, state, page_ids, rows, slots, true_lens:
            model.serve_scatter(cache, ks, vs, state, page_ids, rows,
                                slots, true_lens, aligned=False),
            donate_argnums=(0,))
        # KV migration surface (prefill/decode disaggregation).  Export
        # gathers a request's pages into ONE stacked [2, L, n, kvh,
        # page, hd] array (a single host fetch, a single object-plane
        # put); import scatters such an array into freshly-allocated
        # pages and seeds the slot's pos/current-token — together they
        # let a decode engine resume exactly where a prefill engine
        # stopped.  Widths are padded to powers of two (pad ids target
        # the trash page 0, whose content is garbage by contract) so
        # the compile count stays logarithmic.
        def _gather_kv_fn(ks, vs, ids):
            return jnp.stack([jnp.stack([k[ids] for k in ks]),
                              jnp.stack([v[ids] for v in vs])])

        self._gather_kv = jax.jit(_gather_kv_fn)

        # Prefix-store demotion reads ONE page a call, whatever the
        # path's depth (a path of depth n is n calls): the page's K and
        # V of every layer as 2·L separate [kvh, page, hd] arrays, so
        # the export thread can fetch them one piece at a time and
        # nothing the size of a path ever stands between the decode
        # window's tokens and the host.  ONE program, one shape,
        # compiled when a demotion callback is installed
        # (set_prefix_store) and never after: `_gather_page` is the
        # compiled executable, which cannot meet a new shape.
        def _gather_page_fn(ks, vs, pid):
            return (tuple(k[pid] for k in ks)
                    + tuple(v[pid] for v in vs))

        self._gather_page_jit = jax.jit(_gather_page_fn)
        self._gather_page = None

        def _import_kv_fn(cache, cur, kv, ids, slot, kvlen, tok):
            k = [cache["k"][li].at[ids].set(kv[0, li])
                 for li in range(cfg.n_layers)]
            v = [cache["v"][li].at[ids].set(kv[1, li])
                 for li in range(cfg.n_layers)]
            pos = cache["pos"].at[slot].set(kvlen)
            return ({**cache, "k": k, "v": v, "pos": pos},
                    cur.at[slot].set(tok))

        self._import_pages = jax.jit(_import_kv_fn,
                                     donate_argnums=(0, 1))

        # Prefix-store graft: scatter a stored subtree's KV into fresh
        # pool blocks WITHOUT touching any slot (kv_import resumes a
        # request; a graft only re-warms the radix tree — the blocks
        # are committed+released right after, so the next admission
        # prefix-hits them).  Same pow-2 width padding as import.
        def _graft_kv_fn(cache, kv, ids):
            k = [cache["k"][li].at[ids].set(kv[0, li])
                 for li in range(cfg.n_layers)]
            v = [cache["v"][li].at[ids].set(kv[1, li])
                 for li in range(cfg.n_layers)]
            return {**cache, "k": k, "v": v}

        self._graft_pages = jax.jit(_graft_kv_fn, donate_argnums=(0,))

        # COW page copy: duplicate shared blocks before a writer touches
        # them.  Pairs are padded with (0, 0) — trash-to-trash is a
        # no-op — so the compile count stays at a few pad widths.
        self._copy_pages = jax.jit(
            lambda cache, src, dst: {
                **cache,
                **{name: [l.at[dst].set(l[src]) for l in leaves]
                   for name, leaves in _pool(cache).items()}},
            donate_argnums=(0,))

        # Slot state.  Current tokens live ON DEVICE between blocks: the
        # decode output feeds the next decode input directly, so the only
        # device→host sync per block is the token-sequence fetch.
        self._slots: list[_Request | None] = [None] * max_batch
        self._cur_dev = jnp.zeros((max_batch,), jnp.int32)
        self._temps = np.zeros((max_batch,), np.float32)
        self._seeds = np.zeros((max_batch,), np.int32)
        # Device copy of the page table, refreshed only when admission or
        # completion changed it.
        self._table_dev = jnp.asarray(self._table)
        self._table_dirty = False
        # Admission order: new submissions drain from the thread-safe
        # queue into this deque; preempted requests re-enter at the
        # FRONT (they keep their place — recompute, not starvation).
        # The front request is the head-of-line FIFO barrier when the
        # pool can't cover it yet.
        self._pending: collections.deque[_Request] = collections.deque()
        self._waiting: queue.Queue[_Request] = queue.Queue()
        self._error: BaseException | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._wake = threading.Event()
        # Submissions currently between submit() entry and the queue
        # put, plus the entry stamp of the newest one: _admit skips its
        # burst-coalescing grace when the queue is empty, nobody is
        # mid-submit, and nothing was submitted after the requests it
        # already holds — a lone request must never linger the grace
        # window ("idle requests never wait"), while a burst still
        # coalesces.
        self._inflight_lock = threading.Lock()
        self._inflight_submits = 0
        self._last_submit_t = 0.0
        self._next_seed = 0
        self.completed = 0
        self.preemptions = 0
        self.kv_exports = 0            # prefill-side page migrations out
        self.kv_imports = 0            # decode-side page migrations in
        # Export side-channel (created lazily by the loop thread on the
        # first prefill_only finish or demotion): the device→host fetch
        # of migrated or demoted KV runs on this thread (_export_loop).
        self._export_q: queue.Queue | None = None
        self._export_thread: threading.Thread | None = None
        self.prefill_tokens = 0        # tokens actually prefilled
        self.decode_tokens = 0
        # The engine thread's own timeline (stats()["loop"]): bumped on
        # the loop thread, cumulative since the engine was made, on with
        # RAY_TPU_TRACE=0 too.
        self._loop_trace: tuple | None = None  # (trace id, root span id)
        self._iter = 0                 # loop iterations: the spans' `iter`
        # The ONE table of work counters: the loop's own rows
        # (_LOOP_WORK) beside the model's (spec.counters, whose numbers
        # the model's own arithmetic returns: spec.decode_work a window,
        # spec.prefill_work a full-prompt program, spec.routed_work a
        # program's device-side counts).  stats()["loop"], the Prometheus
        # counters and `_count` read and write it by name.
        self.work = dict.fromkeys((*_LOOP_WORK, *spec.counters), 0)
        self.phase_s = dict.fromkeys(_LOOP_PHASES, 0.0)
        self.phase_cpu_s = dict.fromkeys(_LOOP_PHASES, 0.0)
        # The phase the loop is in, published for the stall watcher:
        # (key, perf_counter at entry, wall at entry, iter), None between
        # phases.  Stored at entry and cleared at exit, no lock.
        self._phase_now: tuple | None = None
        self._watch_thread: threading.Thread | None = None
        self._watch_stop = threading.Event()
        # stalls with work waiting (one in `idle` is a span and no count)
        # and their seconds; bumped on the watcher thread
        self.stalls = 0
        self.stall_s = 0.0
        # (device array, rows routed, rows of its shape) a prefill
        # program with routed layers: fetched with the wave's first tokens
        self._prefill_counts: list = []
        self._funded_blocks = 0        # pages _ensure_decode_blocks got
        self._demote_dispatched = 0    # candidates _maybe_demote took
        # What demotion moved off the device, cumulative, bumped on the
        # export thread: pages and host bytes fetched, and the seconds
        # that thread spent in the piece fetches.
        self.demote_pages = 0
        self.demote_bytes = 0
        self.demote_fetch_s = 0.0
        # Live weight sync (online RLHF): update_weights() stages a
        # fresh param tree here; the loop swaps it in BETWEEN decode
        # sync windows (never mid-block — the compiled program must see
        # one consistent tree), so decode continues uninterrupted and a
        # generation replica is never drained for a policy update.
        self._weights_lock = threading.Lock()
        self._staged_weights: tuple | None = None   # (version, tree, t)
        self._staged_version = 0
        self.weight_version = 0
        self.weight_updates = 0
        self.weight_syncs_skipped = 0
        self.last_weight_sync_ms = 0.0   # stage -> visible-to-decode
        # Prefix-cache generation: bumped (and the radix tree flushed)
        # at every weight swap — cached KV belongs to the policy that
        # computed it.
        self._cache_gen = 0
        # Tier-2 prefix store (serve/prefix_store.py): the owning
        # server installs a demotion callback via set_prefix_store;
        # the loop then demotes cold radix leaves into sealed arena
        # objects (scan in `fund`, one page gather a page dispatched
        # BEHIND the decode window, piecewise host fetch + publish on
        # the export thread) and applies queued grafts.  All no-ops
        # until a callback is installed.
        self._demote_cb = None
        self._demote_knobs: dict = {}
        self._demote_lock = threading.Lock()
        self._demote_inflight = 0
        self._demote_t = 0.0
        # Scanned (pinned) candidates whose gathers are not dispatched
        # yet: filled by _maybe_demote, emptied by _dispatch_demotes
        # later in the same iteration.
        self._demote_pending: list[dict] = []
        # Leaf hashes the store declined — skipped on rescans so a
        # disabled/full store doesn't re-gather the same leaves every
        # period.  Cleared on weight swaps with the tree flush.
        self._demote_skip: set[int] = set()
        self._graft_q: queue.Queue = queue.Queue()
        self.kv_grafts = 0
        self.graft_tokens = 0
        self.demote_published = 0
        self.demote_failures = 0
        # Recent per-request latency window (exact p99 over raw samples
        # — the controller's SLO loop consumes this via stats() →
        # replica_metrics; the histograms quantize, this doesn't).
        self._slo_window = slo.LatencyWindow()
        self._metrics_last: dict = {}
        self._metrics_t = 0.0
        # stats() flushes from replica threads while the loop flushes on
        # its own cadence; the delta bookkeeping must not double-count.
        self._metrics_lock = threading.Lock()

    # ------------------------------------------------------------- public
    def submit(self, prompt: list[int], max_new_tokens: int = 32,
               temperature: float = 0.0,
               eos_id: int | None = None,
               token_queue: "queue.Queue | None" = None,
               _cache_ok: bool = True,
               prefill_only: bool = False,
               model_id: str | None = None,
               ) -> concurrent.futures.Future:
        """Thread-safe; resolves to {tokens, ttft_s, total_s}.  With
        `token_queue`, every decoded token is ALSO pushed to the queue as
        produced (None = end) — the token-streaming hook.  With
        `prefill_only`, the result additionally carries
        `kv_export`: the request's KV pages as one host array plus the
        metadata kv_import() needs to resume decoding on ANOTHER engine
        (the prefill half of disaggregated serving).  With `model_id`,
        the request decodes under that LoRA adapter's bank slot (it
        must be resident — load_adapter — by ADMISSION time, or the
        future fails with AdapterLoadError) and its KV cache entries
        key on the adapter's salt."""
        if prefill_only:
            self._need_kv_transfer("prefill_only")
        if model_id is not None and self._lora_banks is None:
            raise AdapterLoadError(
                "engine has no adapter slots (set lora_slots)",
                model_id=model_id, deployment=self.name,
                reason="lora_slots=0")
        if len(prompt) >= self.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} >= max_len {self.max_len}")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len {self.max_len}; "
                "decode past the cache end would corrupt output")
        need = -(-(len(prompt) + max_new_tokens) // self.page)
        if need > self.n_pages - 1:
            raise ValueError(
                f"request needs {need} KV pages but the pool holds "
                f"{self.n_pages - 1}; raise kv_pages (admission "
                "would otherwise block forever)")
        if self._error is not None:
            raise RuntimeError(
                "LLM engine is dead after an earlier failure") \
                from self._error
        with self._inflight_lock:
            self._inflight_submits += 1
            self._last_submit_t = time.perf_counter()
            seed = self._next_seed
            self._next_seed += 1
        try:
            req = _Request(list(prompt), max_new_tokens, temperature,
                           eos_id, concurrent.futures.Future(),
                           token_queue=token_queue, sample_seed=seed,
                           cache_ok=_cache_ok, prefill_only=prefill_only,
                           model_id=model_id)
            if tracing.ENABLED:
                req.trace = tracing.capture()
            self._waiting.put(req)
            self._wake.set()
        finally:
            with self._inflight_lock:
                self._inflight_submits -= 1
        return req.future

    def generate(self, prompt: list[int], max_new_tokens: int = 32,
                 temperature: float = 0.0,
                 eos_id: int | None = None,
                 _cache_ok: bool = True) -> dict:
        """Blocking convenience wrapper."""
        self.start()
        return self.submit(prompt, max_new_tokens, temperature,
                           eos_id, _cache_ok=_cache_ok).result()

    def kv_import(self, prompt: list[int], tokens: list[int], kv,
                  *, kv_len: int, max_new_tokens: int = 32,
                  temperature: float = 0.0, eos_id: int | None = None,
                  sample_seed: int = 0,
                  token_queue: "queue.Queue | None" = None,
                  ) -> concurrent.futures.Future:
        """Resume a request whose prefill ran on ANOTHER engine: `kv` is
        that engine's `kv_export` array ([2, L, n, kvh, page, hd],
        gather_pages-compatible page layout), covering the first
        `kv_len` positions of prompt+tokens.  The pages are scattered
        into freshly-allocated pool blocks at admission; decode then
        continues from tokens[-1] exactly as if prefill had run here.
        With matching engine seeds and the exporter's `sample_seed`,
        the continued sample stream is bit-identical to an uninterrupted
        single-engine run (the migration-parity contract).  The future
        resolves like submit()'s — `tokens` in the result INCLUDES the
        ones passed in."""
        from ray_tpu import failpoints

        if failpoints.ACTIVE:
            failpoints.fire("serve.kv_import")
        self._need_kv_transfer("kv_import")
        if not tokens:
            raise ValueError("kv_import needs at least the first "
                             "generated token")
        if len(tokens) > max_new_tokens:
            # Under-reserving pages for a negative remaining budget
            # would blow up inside the jitted scatter ON THE ENGINE
            # LOOP (killing every tenant) — reject at the API edge like
            # every other misuse.
            raise ValueError(
                f"already have {len(tokens)} generated tokens but "
                f"max_new_tokens is {max_new_tokens}")
        if kv_len != len(prompt) + len(tokens) - 1:
            raise ValueError(
                f"kv_len {kv_len} != prompt+tokens-1 "
                f"({len(prompt) + len(tokens) - 1}): exported KV must "
                "cover every position but the newest token's")
        kv = np.asarray(kv)
        L = self.cfg.n_layers
        n_imp = -(-kv_len // self.page)
        want = (2, L, n_imp, self.cfg.n_kv_heads, self.page,
                self.cfg.head_dim)
        if kv.shape != want:
            raise ValueError(
                f"kv shape {kv.shape} does not match this engine "
                f"(expected {want}: page_size/config mismatch between "
                "prefill and decode pools?)")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len {self.max_len}")
        need = -(-(len(prompt) + max_new_tokens) // self.page)
        if need > self.n_pages - 1:
            raise ValueError(
                f"request needs {need} KV pages but the pool holds "
                f"{self.n_pages - 1}; raise kv_pages")
        if self._error is not None:
            raise RuntimeError(
                "LLM engine is dead after an earlier failure") \
                from self._error
        req = _Request(list(prompt), max_new_tokens, temperature,
                       eos_id, concurrent.futures.Future(),
                       token_queue=token_queue, sample_seed=sample_seed,
                       tokens=list(tokens), import_kv=kv,
                       import_len=kv_len)
        if tracing.ENABLED:
            req.trace = tracing.capture()
        self._waiting.put(req)
        self._wake.set()
        return req.future

    def _need_kv_transfer(self, what: str) -> None:
        if "kv_transfer" not in self._spec.caps:
            raise ValueError(
                f"{what}: {self._model.__name__} has no KV export/import "
                "(its lanes hold state that no page carries)")

    def set_prefix_store(self, publish_cb, *, min_idle: int = 256,
                         period_s: float = 0.25,
                         watermark_frac: float = 0.125,
                         limit: int = 2, max_inflight: int = 2) -> None:
        """Install (or, with None, remove) the tier-2 prefix-store
        demotion hook (serve/prefix_store.py).  `publish_cb(entry)`
        runs on the EXPORT thread with the demoted subtree's host KV
        ({tokens, kv, hashes, depth, page, weight_version}) and returns
        True once tier 2 holds it — only then is the tier-1 leaf
        evicted.  Knobs: a leaf demotes after `min_idle` LRU-clock
        ticks of disuse, or immediately when the free pool falls under
        `watermark_frac` (demote-before-evict: plain eviction would
        destroy KV the cluster could reuse); at most `limit` leaves per
        `period_s` scan and `max_inflight` unfinished demotions.
        Installing a callback compiles the one page-gather program
        demotion runs (start-up, never a decode window); an engine
        that never gets one, or has no prefix cache, builds none."""
        if (publish_cb is not None and self._prefix_cache
                and self._gather_page is None):
            self._gather_page = self._lower_page_gather().compile()
        self._demote_cb = publish_cb
        self._demote_knobs = dict(
            min_idle=max(0, int(min_idle)),
            period_s=max(0.01, float(period_s)),
            watermark=int(max(0.0, float(watermark_frac))
                          * (self.n_pages - 1)),
            limit=max(1, int(limit)),
            max_inflight=max(1, int(max_inflight)))
        with self._demote_lock:
            self._demote_skip.clear()

    def _lower_page_gather(self, sharding=None):
        """The page gather lowered for this engine's pools: from their
        shapes alone, so it is safe beside a running loop (the decode
        program donates the arrays themselves).  `sharding` places the
        arguments elsewhere than the pools lie (tests/
        test_chip_compile.py: a described chip)."""
        import jax
        import jax.numpy as jnp

        def spec(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=sharding or a.sharding)

        return self._gather_page_jit.lower(
            [spec(a) for a in self.cache["k"]],
            [spec(a) for a in self.cache["v"]],
            jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding))

    def kv_graft(self, tokens: list[int], kv, *, kv_len: int,
                 weight_version: int | None = None, salt: int = 0,
                 ) -> concurrent.futures.Future:
        """Graft a stored prefix's KV into this engine's pool: scatter
        `kv` (kv_export page layout, [2, L, n, kvh, page, hd]) into
        freshly-allocated blocks and COMMIT them into the radix tree
        under `tokens` — the next request matching the prefix hits
        tier 1 as if it had been computed here.  Full blocks only
        (kv_len must be a page multiple covering all of `tokens`).
        Applied on the engine loop between decode windows; the future
        resolves to {"grafted": n_blocks} or {"grafted": 0, "reason"}
        when skipped — a `weight_version` mismatch at application time
        NEVER grafts (stale-policy KV must not repollute a flushed
        cache).  `salt` keys the committed radix entry per (adapter,
        version) — see serve/lora.adapter_salt; 0 = base model."""
        import numpy as np

        self._need_kv_transfer("kv_graft")
        if kv_len <= 0 or kv_len % self.page != 0:
            raise ValueError(
                f"kv_len {kv_len} must be a positive multiple of the "
                f"page size {self.page} (the radix tree is "
                "block-granular)")
        if len(tokens) != kv_len:
            raise ValueError(
                f"tokens ({len(tokens)}) must cover exactly kv_len "
                f"({kv_len}) positions")
        kv = np.asarray(kv)
        n = kv_len // self.page
        want = (2, self.cfg.n_layers, n, self.cfg.n_kv_heads,
                self.page, self.cfg.head_dim)
        if kv.shape != want:
            raise ValueError(
                f"kv shape {kv.shape} does not match this engine "
                f"(expected {want}: page_size/config mismatch?)")
        if n > self.n_pages - 1:
            raise ValueError(
                f"graft needs {n} KV pages but the pool holds "
                f"{self.n_pages - 1}")
        if self._error is not None:
            raise RuntimeError(
                "LLM engine is dead after an earlier failure") \
                from self._error
        fut: concurrent.futures.Future = concurrent.futures.Future()
        self._graft_q.put((list(tokens), kv, n, weight_version,
                           int(salt), fut))
        self._wake.set()
        return fut

    # ------------------------------------------------------- multi-LoRA
    def _lora_args(self, idx) -> dict | None:
        """The per-call `lora` jit argument: None (the static base-path
        trace) when the engine has no banks, else {"idx": [W] int32
        bank-slot per lane, "banks": the resident stacks}.  Banks are
        jit ARGUMENTS, so a load_adapter bank swap changes data, never
        the compiled program."""
        if self._lora_banks is None:
            return None
        import jax.numpy as jnp

        return {"idx": jnp.asarray(np.asarray(idx, np.int32)),
                "banks": self._lora_banks}

    def load_adapter(self, model_id: str, adapter: dict, *,
                     version: int = 1) -> int:
        """Make an adapter device-resident: validate against THIS
        engine's config, pick a bank slot (free list, else LRU among
        slots no in-flight request decodes with), and scatter the
        [L, din, r]/[L, r, dout] stacks into the slot's bank rows.
        Functional `.at[:, slot].set()` writes and an atomic banks-dict
        swap mean this runs on the CALLER thread while decode continues
        (dispatched programs keep the immutable tree they captured).
        Re-loading a resident (model_id, version) is a no-op; a new
        version overwrites in place — its KV salt differs, so stale
        cached KV goes unreachable rather than corrupt.  Raises a typed
        AdapterLoadError when the weights don't fit this engine or no
        slot can be freed — reject early, never a wedged loop.  The
        `serve.adapter_swap` failpoint fires BEFORE an eviction mutates
        anything."""
        import jax.numpy as jnp

        from ray_tpu import failpoints
        from ray_tpu.serve import lora as lora_mod

        if self._lora_banks is None:
            raise AdapterLoadError(
                "engine has no adapter slots (set lora_slots)",
                model_id=model_id, deployment=self.name,
                reason="lora_slots=0")
        targets = (adapter or {}).get("targets") or {}
        dims = self._model.lora_target_dims(self.cfg)
        rank = 0
        for t, ab in targets.items():
            if t not in self._lora_banks:
                raise AdapterLoadError(
                    f"adapter targets {t!r} but this engine banks "
                    f"{sorted(self._lora_banks)}", model_id=model_id,
                    deployment=self.name, reason="bad_target")
            a, b = np.asarray(ab["a"]), np.asarray(ab["b"])
            din, dout = dims[t]
            if (a.ndim != 3 or b.ndim != 3
                    or a.shape[0] != self.cfg.n_layers
                    or a.shape[1] != din or b.shape[2] != dout
                    or a.shape[2] != b.shape[1]):
                raise AdapterLoadError(
                    f"adapter target {t!r} shapes {a.shape}/{b.shape} "
                    f"do not fit this engine (want "
                    f"[L={self.cfg.n_layers}, {din}, r] / "
                    f"[L, r, {dout}])", model_id=model_id,
                    deployment=self.name, reason="bad_shape")
            rank = max(rank, a.shape[2])
        if rank < 1:
            raise AdapterLoadError(
                "adapter has no targets", model_id=model_id,
                deployment=self.name, reason="empty")
        if rank > self.lora_rank:
            raise AdapterLoadError(
                f"adapter rank {rank} exceeds the engine's static bank "
                f"rank {self.lora_rank} (lora_rank)",
                model_id=model_id, deployment=self.name,
                reason="rank_overflow")
        with self._lora_lock:
            cur = self._lora_map.get(model_id)
            if cur is not None \
                    and self._lora_meta[model_id]["version"] == version:
                self._lora_meta[model_id]["last_used"] = time.monotonic()
                return cur
            if cur is not None:
                slot = cur                      # re-upload in place
            elif self._lora_free:
                slot = self._lora_free.pop(0)
            else:
                in_use = {int(s) for s in self._adapters if s}
                cands = [(self._lora_meta[mid]["last_used"], mid)
                         for mid, s in self._lora_map.items()
                         if s not in in_use]
                if not cands:
                    raise AdapterLoadError(
                        "every adapter slot has an in-flight request",
                        model_id=model_id, deployment=self.name,
                        reason="no_free_slot")
                if failpoints.ACTIVE:
                    # Pre-mutation: an injected fault here must leave
                    # the resident set exactly as it was.
                    failpoints.fire("serve.adapter_swap")
                _, victim = min(cands)
                slot = self._lora_map.pop(victim)
                del self._lora_meta[victim]
                self.adapter_evictions += 1
                if tracing.ENABLED:
                    tracing.emit(
                        "serve.adapter_swap", time.time(),
                        attrs={"deployment": self.name, "slot": slot,
                               "loaded": model_id, "evicted": victim})
            banks = {}
            for t, bank in self._lora_banks.items():
                ab = targets.get(t)
                if ab is None:
                    # Absent target: zero the slot row (no delta).
                    a = jnp.zeros_like(bank["a"][:, 0])
                    b = jnp.zeros_like(bank["b"][:, 0])
                else:
                    a = jnp.asarray(ab["a"], bank["a"].dtype)
                    b = jnp.asarray(ab["b"], bank["b"].dtype)
                    r = a.shape[2]
                    if r < self.lora_rank:
                        # Zero-pad narrow adapters into the static
                        # bank rank: the padded columns/rows contribute
                        # exactly zero to the delta.
                        a = jnp.concatenate(
                            [a, jnp.zeros(a.shape[:2]
                                          + (self.lora_rank - r,),
                                          a.dtype)], axis=2)
                        b = jnp.concatenate(
                            [b, jnp.zeros((b.shape[0],
                                           self.lora_rank - r)
                                          + b.shape[2:], b.dtype)],
                            axis=1)
                banks[t] = {"a": bank["a"].at[:, slot].set(a),
                            "b": bank["b"].at[:, slot].set(b)}
            self._lora_banks = banks
            self._lora_map[model_id] = slot
            self._lora_meta[model_id] = {
                "version": int(version),
                "salt": lora_mod.adapter_salt(model_id, version),
                "rank": rank, "last_used": time.monotonic()}
            self.adapter_loads += 1
            return slot

    def adapter_resident(self, model_id: str,
                         version: int | None = None) -> bool:
        """Residency probe (the server's per-request fast path): True
        when the adapter — at `version`, if given — holds a bank
        slot."""
        with self._lora_lock:
            meta = self._lora_meta.get(model_id)
            return (meta is not None
                    and (version is None or meta["version"] == version))

    def adapter_touch(self, model_id: str) -> None:
        """Stamp an adapter's LRU clock (the server's resident fast
        path calls this per request): eviction must rank by actual
        request traffic, not by load/swap times — a hot adapter that
        never reloads would otherwise look permanently stale."""
        with self._lora_lock:
            meta = self._lora_meta.get(model_id)
            if meta is not None:
                meta["last_used"] = time.monotonic()

    def adapter_salt_of(self, model_id: str | None) -> int:
        """KV salt of a RESIDENT adapter (0 = base / not resident) —
        the prefix-store miss path keys its directory lookup with
        this."""
        if model_id is None or self._lora_banks is None:
            return 0
        with self._lora_lock:
            meta = self._lora_meta.get(model_id)
            return meta["salt"] if meta else 0

    def _resolve_adapter(self, req: _Request, lane: int) -> bool:
        """Admission-time model_id → bank-slot resolution (loop
        thread).  Marks the LANE in _adapters under the lora lock
        BEFORE any block work, so a concurrent load_adapter can never
        evict the slot this admission is about to decode with (the
        mark is undone if block reservation fails).  A missing adapter
        — never loaded, or evicted since the server's residency check
        — fails the ONE request with AdapterLoadError: reject early,
        never wedge the loop."""
        with self._lora_lock:
            slot = self._lora_map.get(req.model_id)
            if slot is not None:
                meta = self._lora_meta[req.model_id]
                req.lora_slot = slot
                req.salt = meta["salt"]
                meta["last_used"] = time.monotonic()
                self._adapters[lane] = slot
                return True
        req.emit(None)
        if not req.future.done():
            req.future.set_exception(AdapterLoadError(
                "adapter not resident at admission",
                model_id=req.model_id, deployment=self.name,
                reason="not_resident"))
        return False

    def update_weights(self, refs, version: int | None = None) -> int:
        """Stage a fresh policy param tree for LIVE weight sync (the
        online-RLHF loop): the engine loop swaps `self.params` in
        BETWEEN decode sync windows — never mid-block, never draining a
        request — so generation replicas keep decoding while training
        advances the policy.  In-flight completions simply continue
        under the new weights from their next window (the bounded
        off-policy staleness the RLHF trainer's `max_weight_lag`
        accounts for).

        `refs` may be the param tree itself (host or device arrays), ONE
        ObjectRef to such a tree, or a list of ObjectRefs (the
        object-plane broadcast shapes) — resolved HERE on the caller's
        thread, never on the engine loop.  The tree must match the
        resident params' structure and leaf shapes (validated here, at
        the API edge — a mismatch inside the jitted decode would kill
        every tenant); leaves are cast to the resident dtypes at swap so
        the ONE compiled decode program stays valid.

        The swap also FLUSHES the radix prefix cache and generation-
        gates pending commits: every cached page holds KV computed
        under the old policy, and a post-swap prompt match against it
        would silently attend stale values (recurring RLHF prompts hit
        this constantly).  Group sharing within one rollout round is
        unaffected — leaders commit and followers match under the same
        generation.

        Thread-safe; latest staged version wins if the loop hasn't
        swapped yet.  Returns the staged version.  Kill switch
        RAY_TPU_RL_WEIGHT_SYNC=0 (read per call — same-run freeze-policy
        A/B) drops the update and returns the CURRENT version;
        `stats()["weight_version"]` is how callers observe propagation
        either way."""
        if not _env_on("RAY_TPU_RL_WEIGHT_SYNC"):
            with self._weights_lock:
                self.weight_syncs_skipped += 1
                return self.weight_version
        import jax

        tree = refs
        from ray_tpu.object_ref import ObjectRef

        if isinstance(tree, ObjectRef):
            import ray_tpu

            tree = ray_tpu.get(tree)
        elif (isinstance(tree, (list, tuple)) and tree
                and all(isinstance(r, ObjectRef) for r in tree)):
            import ray_tpu

            got = ray_tpu.get(list(tree))
            if len(got) == 1:
                tree = got[0]
            elif all(isinstance(g, dict) for g in got):
                # Sharded object-plane push: each ref carries a
                # disjoint top-level slice of the param dict (e.g.
                # embed / layers / lm_head as separate objects).
                tree = {}
                for g in got:
                    tree.update(g)
            else:
                raise ValueError(
                    "update_weights: a multi-ref push must resolve to "
                    "dict shards that merge into the param tree; got "
                    f"{[type(g).__name__ for g in got]}")
        new_leaves, new_def = jax.tree_util.tree_flatten(tree)
        cur_leaves, cur_def = jax.tree_util.tree_flatten(self.params)
        if new_def != cur_def:
            raise ValueError(
                "update_weights: param tree structure does not match "
                f"the engine's ({new_def} vs {cur_def})")
        for i, (a, b) in enumerate(zip(new_leaves, cur_leaves)):
            if tuple(getattr(a, "shape", ())) != tuple(b.shape):
                raise ValueError(
                    f"update_weights: leaf {i} shape "
                    f"{getattr(a, 'shape', ())} != resident {b.shape} "
                    "(wrong model config?)")
        with self._weights_lock:
            if version is None:
                version = max(self.weight_version,
                              self._staged_version) + 1
            # The stage timestamp travels WITH the staged tuple: a
            # concurrent re-stage must not corrupt the previous swap's
            # stage→visible latency measurement.
            self._staged_weights = (version, tree, time.perf_counter())
            self._staged_version = version
        self._wake.set()        # idle engines swap promptly too
        return version

    def _maybe_swap_weights(self) -> None:
        """Engine-loop half of update_weights: apply the newest staged
        tree, if any.  Runs at the top of every loop iteration — i.e.
        between decode sync windows — so an in-flight request's decode
        stalls at most one window behind a weight push."""
        with self._weights_lock:
            staged, self._staged_weights = self._staged_weights, None
        if staged is None:
            return
        import jax
        import jax.numpy as jnp

        version, tree, staged_t = staged
        # Cast to resident dtypes (bf16 engines fed fp32 learner
        # trees): the compiled decode program's signature must not
        # change under a swap.
        new_params = jax.tree.map(
            lambda new, old: jnp.asarray(new, old.dtype), tree,
            self.params)
        # Publish tree + version ATOMICALLY (params_snapshot takes the
        # same lock): a scorer must never label logprobs computed under
        # one tree with the other's version.
        with self._weights_lock:
            self.params = new_params
            self.weight_version = version
        self.weight_updates += 1
        # Cached KV belongs to the OLD policy: flush the radix tree
        # (refcount-0 pages free now; in-flight readers finish under
        # the documented staleness) and gate pending commits behind
        # a fresh generation.
        self._cache_gen += 1
        self._mgr.flush()
        with self._demote_lock:
            # Declined-leaf memory belongs to the flushed tree.
            self._demote_skip.clear()
        self.last_weight_sync_ms = (time.perf_counter()
                                    - staged_t) * 1000.0

    def params_snapshot(self):
        """Consistent (params, weight_version) pair for trajectory
        scoring: the swap publishes both under the weights lock, so a
        reader can never see the new tree labeled with the old version
        (or vice versa)."""
        with self._weights_lock:
            return self.params, self.weight_version

    def warmup(self, buckets: list[int] | None = None) -> None:
        """Pre-compile the decode program and prefill buckets so the first
        real request doesn't pay XLA compile time in its TTFT (the
        standard TPU-serving warmup discipline).  Warmup prompts are
        capped by the paged pool's capacity — a pool sized below one
        full max_len span (the very configurations paging enables) must
        not make warmup trip its own admission check.  Warmup traffic
        bypasses the prefix cache (_cache_ok=False): each bucket's
        ramp prompt is a prefix of the next one's, and matching it
        would compile the suffix programs instead of the full-prefill
        bucket programs warmup exists to build.  The prefix store's
        page gather is not built here: set_prefix_store compiles it
        when a callback is installed, traffic or no traffic."""
        cap = self.max_len - 1
        if getattr(self, "page", None):
            cap = min(cap, (self.n_pages - 1) * self.page - 1)
        for b in buckets or self._buckets:
            n = min(b, cap)
            if n >= 1:
                self.generate(list(range(1, n + 1)), max_new_tokens=1,
                              _cache_ok=False)

    def set_sync_window(self, k: int | None) -> int:
        """Set the live decode sync-window size (overload degradation:
        smaller windows admit/eos-check more often, bounding how long a
        queued request waits behind a running block, at some
        amortization cost).  None restores the configured
        steps_per_sync.  Takes effect at the next window boundary (the
        loop reads it between blocks); each distinct size compiles its
        own cached decode program.  Token streams are UNCHANGED by the
        window size — sampling keys fold in the per-request generation
        index, not the window phase."""
        k = self.steps_per_sync if not k \
            else max(1, min(int(k), self.steps_per_sync))
        if k != self._k_live:
            if k < self.steps_per_sync:
                self.sync_window_shrinks += 1
            self._k_live = k
            self._wake.set()
        return k

    # ------------------------------------------- the engine's timeline
    def _loop_ctx(self) -> tuple | None:
        """(trace id, root span id) of this engine's own trace: every
        `llm.loop.*` span is a child of one zero-length `llm.engine`
        span, so what caused a phase is the engine, not a request."""
        if self._loop_trace is None and tracing.ENABLED:
            now = time.time()
            self._loop_trace = tracing.emit(
                "llm.engine", now, now, ctx=(tracing.new_id(), ""),
                attrs={"engine": self.name, "max_batch": self.max_batch,
                       "steps_per_sync": self.steps_per_sync,
                       "page_size": self.page})
        return self._loop_trace

    @contextlib.contextmanager
    def _phase(self, key: str, **attrs):
        """One phase of the engine thread's timeline, and the only way
        one is recorded: `with self._phase("fund", iter=it) as ph:`.
        The block (1) adds its perf_counter duration to the cumulative
        `phase_s` counter and the thread's own CPU time in it
        (`time.thread_time`) to `phase_cpu_s`, always: the difference is
        the time the thread STOOD there, off the processor (waiting for
        the GIL, a lock, the device); (2) becomes one flight-recorder
        span `llm.loop.<key>`, carrying `cpu_ms`, when tracing is on;
        (3) runs under a
        `jax.profiler.TraceAnnotation`, so it is a host event, on the
        profiler's clock, in any profiler trace that is running; (4) is
        published in `_phase_now` while it is open, for the stall watcher
        (`_watch`).  `with`
        yields the span's attrs: the block adds what it learns (the
        annotation carries the attrs known at entry)."""
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("llm.loop." + key, **attrs):
            w0 = time.time() if tracing.ENABLED else 0.0
            t0 = time.perf_counter()
            c0 = time.thread_time()
            self._phase_now = (key, t0, w0, attrs.get("iter"))
            try:
                yield attrs
            finally:
                self._phase_now = None
                cpu = time.thread_time() - c0
                self.phase_s[key] += time.perf_counter() - t0
                self.phase_cpu_s[key] += cpu
                if w0 and tracing.ENABLED:
                    attrs["cpu_ms"] = round(cpu * 1e3, 3)
                    tracing.emit("llm.loop." + key, w0, time.time(),
                                 ctx=self._loop_ctx(), attrs=attrs)

    def _count(self, work: dict, shown: dict | None = None,
               ph: dict | None = None) -> None:
        """Add `work` ({row of the table: what to add}; a name the table
        lacks is an error, the model's or the loop's) and sum what is
        `shown` of it into the attributes `ph` of the phase's span."""
        for name, n in work.items():
            self.work[name] += n
        for name, n in (shown or {}).items():
            ph[name] = ph.get(name, 0) + n

    # ------------------------------------------------ the stall watcher
    def _watch(self) -> None:
        """The thread `llm-stall-watch`, alive from `start()` to `stop()`:
        every WATCH_S it reads the phase the loop published and notes how
        LATE it woke, by `perf_counter` alone; every WATCH_LEDGER_EVERY
        wakes it reads the CPU clocks too (`_watch_reading`).
        It opens a stall when (a) a host phase (`_HOST_PHASES`) has been
        open longer than HOST_STALL_S, (b) it woke itself more than
        LATE_WAKE_S late, whatever the phase: the interpreter was held
        or the process did not run, which is what shows inside a `_sync`
        phase, or (c) a `_sync` phase (`_SYNC_PHASES`) has been open
        longer than SYNC_STALL_S and SYNC_STALL_X times the longest
        instance of it the watcher saw close with itself on time (warm-up
        included; the instance that trips this raises the longest too, or
        a model whose sound prefill passes SYNC_STALL_S would report
        every wave).  One stall at a time; it ends when its phase closes,
        or for (b) when the watcher is on time again and no host phase it
        caught is still open.  See `_stall_open` and `_stall_close` for
        the record."""
        longest = dict.fromkeys(_SYNC_PHASES, 0.0)
        # the last reading of the CPU clocks, and the last before `seen`
        # was entered
        full = before = _watch_reading()
        seen = last = tainted = stall = None
        sync_age = 0.0      # how long `seen`, a _sync phase, was seen open
        wakes = 0
        due = time.perf_counter() + WATCH_S
        while not self._watch_stop.wait(max(0.0, due - time.perf_counter())):
            now = time.perf_counter()
            late, due = now - due, now + WATCH_S
            cur = self._phase_now
            if cur is not seen:
                # entered since the last wake, whose readings precede it
                if (seen is not None and seen is not tainted
                        and seen[0] in longest):
                    longest[seen[0]] = max(longest[seen[0]], sync_age)
                seen, before, sync_age = cur, full, 0.0
            key, age = (cur[0], now - cur[1]) if cur else (None, 0.0)
            if key in longest:
                sync_age = age
            if late > LATE_WAKE_S:
                tainted = cur
            if stall is not None:
                stall["late_s"] = max(stall["late_s"], late)
                if late > LATE_WAKE_S:
                    stall["late_t1"] = time.time()
            elif late > LATE_WAKE_S:
                stall = self._stall_open("late_wake", cur, last, late, full)
            elif key in _HOST_PHASES and age > HOST_STALL_S:
                stall = self._stall_open("host_phase", cur, last, late,
                                         before)
            elif key in longest and age > max(
                    SYNC_STALL_S, SYNC_STALL_X * longest[key]):
                stall = self._stall_open("sync", cur, last, late, before)
            if stall is not None:
                t1 = self._stall_end(stall, cur, late, now)
                if t1 is not None:
                    full = _watch_reading()     # the first after `t1`
                    self._stall_close(stall, t1, full)
                    stall, wakes = None, 0
            elif wakes % WATCH_LEDGER_EVERY == 0:
                full = _watch_reading()
            wakes += 1
            last = cur or last
        if stall is not None:       # stopped inside one: it ends here
            self._stall_close(stall, time.time(), _watch_reading())

    def _stall_open(self, trigger: str, rec: tuple | None,
                    last: tuple | None, late: float, r0: dict) -> dict:
        """What is taken ONCE a stall, at its trigger: every thread's
        stack, the device allocator's numbers, the queue and the lanes.
        `rec` is the phase record the stall waits for (None: a late wake
        between phases, filed under `last`, the phase seen before it),
        `r0` the last reading of the CPU clocks (`_watch_reading`) before
        the stall began: the phase's entry, or for a late wake the time
        the missed wake was due."""
        now_wall, now = time.time(), time.perf_counter()
        shown = rec or last
        # a phase's wall at entry is the recorder's when it is on
        entered = (rec[2] or now_wall - (now - rec[1])) if rec else None
        frames = sys._current_frames()
        names = {t.ident: t.name for t in threading.enumerate()}
        eng = self._thread
        try:
            import jax

            mem = jax.local_devices()[0].memory_stats() or {}
        except Exception:  # noqa: BLE001 - a backend without the call
            mem = {}
        t0 = now_wall - late if trigger == "late_wake" else entered
        return {
            "trigger": trigger, "rec": rec, "t0": t0, "r0": r0,
            "entered": entered,
            "phase": shown[0] if shown else "none",
            "iter": shown[3] if shown else None,
            "late_s": late, "late_t1": now_wall,
            "age": now - rec[1] if rec else 0.0,
            "phase_s0": self.phase_s[rec[0]] if rec else 0.0,
            "taken_ms": (now_wall - t0) * 1e3,
            "engine_frames": _frame_lines(
                frames.get(eng.ident if eng else None), 8),
            # for the block on stderr, which a stall in `idle` has none of
            "stacks": [(names.get(i, "?"), _frame_lines(f))
                       for i, f in frames.items()
                       if _stall_blocks < _STALL_BLOCKS
                       and shown and shown[0] != "idle"],
            "pending": len(self._pending),
            "lanes": sum(s is not None for s in self._slots),
            "mem": {k: mem.get(k) for k in (
                "bytes_in_use", "peak_bytes_in_use",
                "largest_free_block_bytes")}}

    def _stall_end(self, stall: dict, cur: tuple | None, late: float,
                   now: float) -> float | None:
        """The wall time the stall ended at, None while it lasts.  A
        phase's end is its entry plus its length, which is the smaller of
        what the watcher saw and what `phase_s` gained since the trigger
        (later instances of the phase are in that too)."""
        rec = stall["rec"]

        def phase_end():
            return stall["entered"] + max(stall["age"], min(
                now - rec[1], self.phase_s[rec[0]] - stall["phase_s0"]))

        if stall["trigger"] != "late_wake":
            return None if cur is rec else phase_end()
        if late > LATE_WAKE_S:
            return None
        if rec is None or rec[0] not in _HOST_PHASES:
            return stall["late_t1"]
        return None if cur is rec else max(stall["late_t1"], phase_end())

    def _stall_close(self, stall: dict, t1: float, r1: dict) -> None:
        """ONE span `llm.stall` on the engine's own trace, the counters
        (none for a stall in `idle`: no work waited), and every thread's
        stack on stderr under the span's id.  `r1` is the first reading
        of the CPU clocks after `t1`: every CPU number and the faults are
        gains over `ledger_ms`, which begins up to WATCH_LEDGER_EVERY
        wakes before the stall, so `held` reads `process` only where even
        that longer stretch's CPU is under a tenth of the stall."""
        global _stall_blocks
        t0, r0 = stall["t0"], stall["r0"]
        stood_ms = max(0.0, t1 - t0) * 1e3
        gain = {n: (c - r0["by_name"].get(n, 0.0)) * 1e3
                for n, c in r1["by_name"].items()}
        process_ms = (r1["process_cpu_s"] - r0["process_cpu_s"]) * 1e3
        late_ms = stall["late_s"] * 1e3
        build_ms = _overlap_s([*_BUILD_RECENT, *(
            (at, t1) for at in list(_BUILD_OPEN_AT.values()))], t0, t1) * 1e3
        held = _stall_held(stall["trigger"], stood_ms, late_ms, process_ms)
        rivals = sorted(((n, round(v, 3)) for n, v in gain.items()
                         if n != "llm-engine"), key=lambda kv: -kv[1])[:5]
        counted = stall["phase"] != "idle"
        if counted:
            self.stalls += 1
            self.stall_s += stood_ms / 1e3
        attrs = {
            "phase": stall["phase"], "trigger": stall["trigger"],
            "held": held,
            # which of `loop.stalls` this is (0: in `idle`, not counted):
            # the watcher counts a stall at its first wake after it
            # ended, which may be after a reading of `stats` it preceded
            "nth": self.stalls if counted else 0,
            "stood_ms": round(stood_ms, 3),
            "engine_cpu_ms": round(gain.get("llm-engine", 0.0), 3),
            "late_wake_ms": round(late_ms, 3),
            "process_cpu_ms": round(process_ms, 3),
            "by_thread_cpu_ms": json.dumps(rivals),
            "native_cpu_ms": round(process_ms - sum(gain.values()), 3),
            "ledger_ms": round((r1["wall_s"] - r0["wall_s"]) * 1e3, 3),
            "gc_ms": round(_overlap_s(_GC_RECENT, t0, t1) * 1e3, 3),
            "build_ms": round(build_ms, 3),
            "engine_frames": " | ".join(stall["engine_frames"]),
            "faults_major": r1["ru"].ru_majflt - r0["ru"].ru_majflt,
            "faults_minor": r1["ru"].ru_minflt - r0["ru"].ru_minflt,
            "switches_involuntary": r1["ru"].ru_nivcsw - r0["ru"].ru_nivcsw,
            "mem_in_use": stall["mem"]["bytes_in_use"],
            "mem_largest_free": stall["mem"]["largest_free_block_bytes"],
            "pending": stall["pending"], "lanes": stall["lanes"]}
        if stall["iter"] is not None:       # the `idle` phase has none
            attrs["iter"] = stall["iter"]
        ctx = (tracing.emit("llm.stall", t0, t1, ctx=self._loop_ctx(),
                            attrs=attrs) if tracing.ENABLED else None)
        # the stacks are kept for stalls with work waiting that no build
        # explains (a build names itself)
        if build_ms >= 0.5 * stood_ms or not stall["stacks"]:
            return
        _stall_blocks += 1
        head = {k: v for k, v in attrs.items() if k != "engine_frames"}
        lines = ["llm.stall %s engine=%s %s mem_peak=%s stacks_taken_ms=%.1f"
                 % (ctx[1] if ctx else "-", self.name, json.dumps(head),
                    stall["mem"]["peak_bytes_in_use"], stall["taken_ms"])]
        for name, frames in stall["stacks"]:
            lines.append("  thread %s [%s]" % (name, _thread_row(name)))
            lines += ["    " + ln for ln in frames]
        print("\n".join(lines), file=sys.stderr, flush=True)

    def start(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._loop_ctx()
            self._thread = threading.Thread(
                target=self._loop, name="llm-engine", daemon=True)
            self._thread.start()
            if self._watch_thread is None \
                    or not self._watch_thread.is_alive():
                self._watch_stop.clear()
                self._watch_thread = threading.Thread(
                    target=self._watch, name="llm-stall-watch", daemon=True)
                self._watch_thread.start()
            self._register_memledger_provider()

    def _register_memledger_provider(self) -> None:
        """Attach this engine's resident HBM KV pool to the cluster
        memory harvest (tier "hbm" rows next to the arena tiers): used
        bytes = non-free pool blocks x bytes per page, from the same
        BlockManager accounting the radix cache runs on."""
        import jax

        try:
            pool_bytes = int(sum(
                x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(self.cache)))
        except Exception:  # noqa: BLE001 - exotic cache leaves
            pool_bytes = 0
        per_page = pool_bytes // max(1, self.n_pages)
        lora_bytes = 0
        if self._lora_banks is not None:
            lora_bytes = int(sum(
                x.size * x.dtype.itemsize
                for t in self._lora_banks.values() for x in t.values()))
        self._memledger_provider = f"llm:{self.name}:{id(self):x}"

        def _rows():
            st = self._mgr.stats()
            used = st["n_blocks"] - st["free"]
            rows = [{"object_id": f"kvpool:{self.name}",
                     "size": used * per_page, "tag": "hbm_kv",
                     "tier": "hbm",
                     "callsite": f"serve/llm.py engine {self.name}",
                     "pool_bytes": pool_bytes,
                     "blocks_used": used,
                     "blocks_total": st["n_blocks"],
                     "blocks_cached": st["cached"]}]
            if lora_bytes:
                rows.append({
                    "object_id": f"lorabanks:{self.name}",
                    "size": lora_bytes, "tag": "lora_banks",
                    "tier": "hbm",
                    "callsite": f"serve/llm.py engine {self.name}",
                    "slots": self.lora_slots, "rank": self.lora_rank})
            return rows

        memledger.register_provider(self._memledger_provider, _rows)

    def stop(self) -> None:
        if getattr(self, "_memledger_provider", None):
            memledger.unregister_provider(self._memledger_provider)
            self._memledger_provider = None
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
        self._watch_stop.set()
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=10.0)
            self._watch_thread = None
        if self._export_thread is not None:
            # Sentinel AFTER the loop stopped: pending exports drain in
            # order, then the thread exits.
            self._export_q.put(None)
            self._export_thread.join(timeout=10.0)
            self._export_thread = None
            self._export_q = None

    def abort_pending(self, exc: BaseException) -> None:
        """Fail every queued and in-flight request (call AFTER stop():
        the loop thread must not be racing the slot table).  A stopped
        engine would otherwise hang their futures forever — the replica
        reconfigure path swaps engines mid-traffic."""
        self._drain_requests(exc)

    def _drain_requests(self, exc: BaseException) -> None:
        # Queued grafts hang their callers' 60s waits if the loop dies
        # with them unapplied — fail them like every pending request.
        while True:
            try:
                *_rest, fut = self._graft_q.get_nowait()
            except queue.Empty:
                break
            if not fut.done():
                fut.set_exception(exc)
        for req in list(self._pending):
            req.emit(None)
            if not req.future.done():
                req.future.set_exception(exc)
        self._pending.clear()
        for i, req in enumerate(self._slots):
            if req is not None:
                req.emit(None)
                if not req.future.done():
                    req.future.set_exception(exc)
            self._slots[i] = None
        while True:
            try:
                req = self._waiting.get_nowait()
            except queue.Empty:
                break
            req.emit(None)
            if not req.future.done():
                req.future.set_exception(exc)

    # -------------------------------------------------------------- engine
    def _apply_grafts(self) -> None:
        """Engine-loop half of kv_graft: allocate, scatter, commit,
        release.  Runs right after the weight swap so the version check
        sees the tree the commit would land in.  A failed graft (the
        serve.prefix_graft failpoint, pool pressure) fails ITS future
        only — the loop and every tenant survive."""
        import jax.numpy as jnp

        from ray_tpu import failpoints

        while True:
            try:
                tokens, kv, n, wv, salt, fut = \
                    self._graft_q.get_nowait()
            except queue.Empty:
                return
            try:
                if failpoints.ACTIVE:
                    failpoints.fire("serve.prefix_graft")
                if not self._prefix_cache:
                    out = {"grafted": 0, "reason": "no_prefix_cache"}
                elif wv is not None and wv != self.weight_version:
                    # Stored KV from another policy version: grafting
                    # it would silently attend stale values.
                    out = {"grafted": 0, "reason": "stale_version"}
                else:
                    blocks = self._mgr.allocate(n)
                    if blocks is None:
                        out = {"grafted": 0, "reason": "no_blocks"}
                    else:
                        m = _pow2(n)
                        ids = list(blocks) + [0] * (m - n)
                        if m > n:
                            pad = np.zeros(
                                kv.shape[:2] + (m - n,) + kv.shape[3:],
                                kv.dtype)
                            kv = np.concatenate([kv, pad], axis=2)
                        self.cache = self._graft_pages(
                            self.cache, jnp.asarray(kv),
                            jnp.asarray(ids, jnp.int32))
                        # Commit BEFORE release: the blocks become
                        # cached-evictable instead of freed (the
                        # _release_slot discipline).
                        self._mgr.commit(tokens, blocks, salt=salt)
                        self._mgr.release(blocks)
                        self.kv_grafts += 1
                        self.graft_tokens += n * self.page
                        out = {"grafted": n, "tokens": n * self.page}
            except BaseException as e:  # noqa: BLE001 - injected faults
                if not fut.done():
                    fut.set_exception(e)
                continue
            if not fut.done():
                fut.set_result(out)

    def _ensure_export_thread(self) -> queue.Queue:
        if self._export_q is None:
            self._export_q = queue.Queue()
            self._export_thread = threading.Thread(
                target=self._export_loop, name="llm-kv-export",
                daemon=True)
            self._export_thread.start()
        return self._export_q

    def _maybe_demote(self) -> None:
        """Loop-side demotion scan (tier-1 → tier-2), host arithmetic
        only: pick cold refcount-0 radix leaves
        (BlockManager.demote_scan pins each path root..leaf) and leave
        them in `_demote_pending`.  The device work is left to
        _dispatch_demotes, which the caller runs BEHIND the decode
        window of the same iteration.  Throttled by period and
        in-flight cap; no-op until a server installs the callback, and
        gated per scan by the RAY_TPU_PREFIX_STORE kill switch."""
        cb = self._demote_cb
        if cb is None or not self._prefix_cache:
            return
        knobs = self._demote_knobs
        now = time.monotonic()
        if now - self._demote_t < knobs["period_s"]:
            return
        self._demote_t = now
        with self._demote_lock:
            if self._demote_inflight >= knobs["max_inflight"]:
                return
            budget = knobs["max_inflight"] - self._demote_inflight
            exclude = set(self._demote_skip)
        from ray_tpu.serve.kv_router import prefix_store_on

        if not prefix_store_on():
            return
        cands = self._mgr.demote_scan(
            limit=min(knobs["limit"], budget),
            min_idle=knobs["min_idle"], watermark=knobs["watermark"],
            exclude=exclude)
        with self._demote_lock:
            self._demote_inflight += len(cands)
        self._demote_dispatched += len(cands)
        self._demote_pending += cands

    def _dispatch_demotes(self) -> int:
        """Dispatch the pending candidates' device work: one call of
        the warmed page gather a page of each path, reading the cache
        the last program RETURNED (the pages are pinned and sealed: the
        same bytes before or after a window), and hand the pieces to
        the export thread.  Nothing is fetched here.  Returns the pages
        gathered."""
        if not self._demote_pending:
            return 0
        q = self._ensure_export_thread()
        gen, wv = self._cache_gen, self.weight_version
        k, v = self.cache["k"], self.cache["v"]
        pages = 0
        for c in self._demote_pending:
            q.put(("demote", c,
                   [self._gather_page(k, v, np.int32(b))
                    for b in c["blocks"]], gen, wv))
            pages += c["depth"]
        self._demote_pending.clear()
        return pages

    def _fetch_pages(self, pages: list) -> "np.ndarray":
        """Export-thread fetch of one demoted path: `pages` holds, a
        page, the gather's 2·L device arrays [kvh, page, hd] (every
        layer's K, then every layer's V).  The entry's host array
        [2, L, depth, kvh, page, hd] is allocated once and filled piece
        by piece, each piece awaited before the next is asked for: at
        most ONE piece (1 MB at Mistral widths, 32 a page) is on its
        way at any time, so that is the most a decode window's token
        fetch can queue behind, and the GIL is held for one piece's
        copy."""
        n_l = len(pages[0]) // 2
        host = np.empty((2, n_l, len(pages)) + tuple(pages[0][0].shape),
                        pages[0][0].dtype)
        for p in range(len(pages)):
            for i, piece in enumerate(pages[p]):
                host[i // n_l, i % n_l, p] = self._fetch_piece(piece)
            pages[p] = None          # the page's device copy goes now
        return host

    @staticmethod
    def _fetch_piece(piece) -> "np.ndarray":
        """One blocking device→host fetch (the seam tier-1 stubs)."""
        return np.asarray(piece)

    def _demote_one(self, c: dict, pages: list, gen: int,
                    wv: int) -> None:
        """Export-thread half of one demotion: fetch the path's pages
        to the host (_fetch_pages), publish to the store, then finish
        the manager-side accounting (pins released either way; the
        tier-1 leaf drops only when tier 2 really holds the entry AND
        no weight swap invalidated the KV mid-flight)."""
        published = False
        n_pieces = sum(len(p) for p in pages)
        t0 = time.perf_counter()
        try:
            host = self._fetch_pages(pages)
        except BaseException:  # noqa: BLE001 - device fault
            self.demote_failures += 1
            host = None
        fetch_s = time.perf_counter() - t0
        self.demote_fetch_s += fetch_s
        if host is not None:
            self.demote_pages += c["depth"]
            self.demote_bytes += host.nbytes
        if host is not None and gen == self._cache_gen:
            from ray_tpu import failpoints

            try:
                if failpoints.ACTIVE:
                    # The mid-demotion fault window: a crash here dies
                    # BETWEEN the KV fetch and the store registration
                    # — the chaos shape the accounting must survive.
                    failpoints.fire("serve.prefix_demote")
                published = bool(self._demote_cb(dict(
                    tokens=c["tokens"], kv=host, hashes=c["hashes"],
                    depth=c["depth"], page=self.page,
                    weight_version=wv, salt=c.get("salt", 0),
                    pieces=n_pieces,
                    fetch_ms=round(fetch_s * 1e3, 3))))
            except BaseException:  # noqa: BLE001 - injected faults
                self.demote_failures += 1
            if not published:
                with self._demote_lock:
                    self._demote_skip.add(c["hash"])
                    if len(self._demote_skip) > 4096:
                        self._demote_skip.clear()
        self._mgr.demote_finish(
            c["leaf"], c["blocks"],
            drop=published and gen == self._cache_gen)
        if published:
            self.demote_published += 1
        with self._demote_lock:
            self._demote_inflight -= 1
        self._wake.set()

    def _reserve_blocks(self, req: _Request,
                        copies: list[tuple[int, int]]) -> bool:
        """Admission-time block reservation: match the longest cached
        prefix, then allocate enough fresh blocks to cover the prompt
        plus one decode window (the full remaining span when preemption
        is off — the legacy admission contract).  Returns False with no
        net state change when the pool can't cover it."""
        mgr = self._mgr
        seq = req.prompt + req.tokens       # resume includes generated
        total = len(seq)
        remaining = req.max_new_tokens - len(req.tokens)
        # Imported-KV requests never match the local cache: their pages
        # arrive by scatter and must be fresh private blocks.
        matched = mgr.match(seq, salt=req.salt) \
            if (req.cache_ok and req.import_kv is None) else []
        matched_tokens = len(matched) * self.page
        cover = total + (min(remaining, self._k_live)
                         if self._preempt_on else remaining)
        need = max(0, -(-cover // self.page) - len(matched))
        fresh = mgr.allocate(need)
        if fresh is None:
            mgr.release(matched)
            return False
        pages = matched + fresh
        if matched_tokens >= total:
            # Whole prompt cached: recompute only the LAST token (its
            # logits seed the first sample).  That one write lands in
            # the block holding position total-1 — the final MATCHED
            # block, shared and sealed — so fork it first (COW).
            li = (total - 1) // self.page
            nb, copied = mgr.cow(pages[li])
            if nb < 0:
                mgr.release(pages)
                return False
            if copied:
                copies.append((pages[li], nb))
                pages[li] = nb
            req.prefill_from = total - 1
        else:
            req.prefill_from = matched_tokens
        req.pages = pages
        return True

    def _admit(self, ph: dict) -> list:
        """Form a wave of waiting requests (the host half of admission:
        queue pops, adapters, block reservation, KV imports, COW copies)
        and return the (slot, request) pairs `_prefill_wave` prefills.
        `ph` is the admit phase's attrs."""
        import jax.numpy as jnp

        linger_s = 0.0     # this wave's burst-coalescing waits
        while True:        # drain arrivals behind any preempted requests
            try:
                self._pending.append(self._waiting.get_nowait())
            except queue.Empty:
                break
        wave: list[tuple[int, _Request]] = []    # (slot, request)
        copies: list[tuple[int, int]] = []       # COW (src, dst) pages
        grace_deadline = None
        while True:
            free = next((i for i, s in enumerate(self._slots)
                         if s is None), None)
            if free is None:
                break
            if not self._pending:
                # Burst coalescing: submissions race admission, and a
                # wave that launches a beat early strands the rest of
                # the burst behind a full prefill+sync round.  Once at
                # least one request is in hand, linger a few ms so the
                # whole burst rides ONE wave; idle requests never
                # wait (no linger on an empty wave).
                try:
                    self._pending.append(self._waiting.get_nowait())
                    continue
                except queue.Empty:
                    pass
                if not wave:
                    break
                if grace_deadline is None:
                    with self._inflight_lock:
                        busy = self._inflight_submits > 0
                        last_t = self._last_submit_t
                    if not busy and last_t <= max(
                            r.submitted_at for _, r in wave):
                        # Lone request(s): nobody is mid-submit and
                        # nothing arrived after the requests already
                        # in hand — launch NOW instead of lingering
                        # the full grace ("idle requests never
                        # wait"); bursts still coalesce because a
                        # racing submit moves _last_submit_t.
                        break
                    grace_deadline = time.perf_counter() + 0.005
                t_linger = time.perf_counter()
                rem = grace_deadline - t_linger
                if rem <= 0:
                    break
                try:
                    self._pending.append(self._waiting.get(timeout=rem))
                except queue.Empty:
                    break
                finally:
                    linger_s += time.perf_counter() - t_linger
                continue
            req = self._pending[0]
            if req.model_id is not None \
                    and not self._resolve_adapter(req, free):
                # Unknown/evicted adapter: fail THIS request early and
                # keep admitting — an adapter miss must never become a
                # head-of-line barrier.
                self._pending.popleft()
                continue
            # The block pool is the admission control: the FRONT
            # request blocks FIFO when free + evictable can't cover
            # it (vLLM-style KV backpressure; nothing skips past).
            if not self._reserve_blocks(req, copies):
                if req.lora_slot:
                    # Undo the lane's slot mark — the request is
                    # NOT decoding; its adapter stays evictable.
                    with self._lora_lock:
                        self._adapters[free] = 0
                break
            self._table[free, :] = 0
            self._table[free, :len(req.pages)] = req.pages
            self._table_dirty = True
            self._pending.popleft()
            req.slot = free
            req.admitted_at = time.perf_counter()
            req.admitted_wall = time.time()
            req.cache_gen = self._cache_gen
            self._slots[free] = req
            self._temps[free] = req.temperature
            self._seeds[free] = req.sample_seed
            wave.append((free, req))
        ph.update(admitted=len(wave),
                  linger_ms=round(linger_s * 1e3, 3),
                  waiting=self._waiting.qsize() + len(self._pending))
        if not wave:
            return wave
        # Migrated-KV admissions scatter their imported pages instead of
        # prefilling; their first token was already produced (and
        # delivered) by the exporting engine, so they skip the
        # first-token fetch below entirely.
        imports = [(s, r) for s, r in wave if r.import_kv is not None]
        wave = [(s, r) for s, r in wave if r.import_kv is None]
        for slot, req in imports:
            t_imp0 = time.time()
            kv_len = req.import_len
            self._apply_import(slot, req)
            if req.first_token_at is None:
                req.first_token_at = time.perf_counter()
            if tracing.ENABLED and req.trace is not None:
                tracing.emit("llm.queue", req.t0_wall, req.admitted_wall,
                             ctx=req.trace)
                tracing.emit("llm.kv_import", t_imp0, ctx=req.trace,
                             attrs={"kv_len": kv_len,
                                    "pages": len(req.pages)})
            if self._done(req):
                self._finish(slot)
        if not wave:
            return wave
        if copies:
            # Materialize COW copies before any prefill reads/writes the
            # forked pages (ordering rides the donated-cache dependency).
            pairs = copies + [(0, 0)] * (_pow2(len(copies))
                                         - len(copies))
            self.cache = self._copy_pages(
                self.cache, jnp.asarray([s for s, _ in pairs], jnp.int32),
                jnp.asarray([d for _, d in pairs], jnp.int32))
        return wave

    def _prefill_wave(self, wave: list, it: int) -> None:
        """Prefill a whole wave of admitted requests through the cheapest
        (width, length) programs the engine has (serve/prefill_plan.py);
        one batched fetch materializes their first tokens."""
        # Dispatch every program's forward (and its separate
        # scatter program) back-to-back, shortest first, THEN fetch
        # first tokens — program 1's round trip overlaps program 2's
        # compute, so a big burst's p50 TTFT tracks one RTT plus HALF
        # the total prefill instead of all of it.
        pending_waves = []        # (chunk, nxt_device, dispatch wall t)
        with self._phase("prefill_dispatch", iter=it,
                         rows=len(wave)) as ph:
            true0 = self.prefill_tokens
            lengths = [len(r.prompt) + len(r.tokens) - r.prefill_from
                       for _, r in wave]
            plan, capped = plan_wave(lengths, self._width_buckets,
                                     self._buckets, self._chunk,
                                     self._spec.prefill_state_bytes,
                                     self._prefill_floor,
                                     self._prefill_programs)
            for rows, w, b in plan:
                chunk = [wave[i] for i in rows]
                t_disp = time.time()
                if any(r.prefill_from > 0 for _, r in chunk):
                    nxt = self._prefill_chunk_suffix(chunk, w, b)
                else:
                    nxt = self._prefill_chunk_full(chunk, w, b, ph)
                pending_waves.append((chunk, nxt, t_disp))
            padded = sum(w * b for _, w, b in plan)
            self._count({
                "prefill_padded_tokens": padded,
                "prefill_waves": 1,
                "prefill_programs": len(plan),
                # charged the floor: a pass over the weights and no more
                "prefill_programs_at_floor": sum(
                    w * b <= self._prefill_floor for _, w, b in plan),
                # more programs than arrival-order chunks of _chunk rows
                "prefill_waves_split":
                len(plan) > -(-len(wave) // self._chunk),
                "prefill_programs_capped": capped})
            # the buckets are those of the widest / longest program
            ph.update(width_bucket=max(w for _, w, _ in plan),
                      len_bucket=max(b for _, _, b in plan),
                      true_tokens=self.prefill_tokens - true0,
                      padded_tokens=padded, chunks=len(plan),
                      plan=",".join(f"{w}x{b}" for _, w, b in plan))
        with self._phase("prefill_sync", iter=it, rows=len(wave)):
            counts, self._prefill_counts = self._prefill_counts, []
            for a in ([nxt for _, nxt, _t in pending_waves]
                      + [c for c, *_ in counts]):
                try:
                    a.copy_to_host_async()
                except AttributeError:
                    pass
            for chunk, nxt, t_disp in pending_waves:
                firsts = np.asarray(nxt)[:len(chunk)]
                now = time.perf_counter()
                now_wall = time.time()
                for (slot, req), first in zip(chunk, firsts):
                    if req.first_token_at is None:
                        req.first_token_at = now
                    req.tokens.append(int(first))
                    req.emit(int(first))
                    if self._done(req):
                        self._finish(slot)
                if not tracing.ENABLED:
                    continue
                for slot, req in chunk:
                    if req.trace is None:
                        continue
                    # The request's engine-side TTFT anatomy: queue
                    # (submit → slot), prefill (chunk dispatch → first
                    # tokens on host; chunk-mates share the device call,
                    # so they share the window), first-token marker.
                    tracing.emit("llm.queue", req.t0_wall,
                                 req.admitted_wall, ctx=req.trace)
                    attrs = {"prompt_tokens": len(req.prompt),
                             "prefill_from": req.prefill_from,
                             "cached_tokens": req.prefill_from}
                    if req.model_id is not None:
                        # this request's decode gathers bank slot
                        # `lora_slot` from here on
                        attrs.update(model_id=req.model_id,
                                     slot=req.lora_slot)
                    tracing.emit("llm.prefill", t_disp, now_wall,
                                 ctx=req.trace, attrs=attrs)
                    tracing.emit(
                        "llm.first_token", now_wall, now_wall,
                        ctx=req.trace,
                        attrs={"ttft_ms": round(
                            (req.first_token_at - req.submitted_at)
                            * 1000, 1)})
            for c, rows, shape in counts:  # on the host since the tokens
                self._count(*self._spec.routed_work(
                    np.asarray(c), 1, rows, shape, True))

    def _prefill_chunk_full(self, chunk, padded_w: int, bucket: int,
                            ph: dict):
        """Full-prompt prefill (no cached prefix anywhere in the chunk)
        in the (padded_w, bucket) program the wave's plan chose; what
        the model counts of it goes to the table and the span `ph`.
        Returns the first tokens (on device)."""
        import jax.numpy as jnp

        W = len(chunk)
        # Pad by duplicating the last row: the duplicate writes the
        # same slot with the same data, so correctness is
        # unaffected.  Width is BUCKETED (1 / 8 / _chunk), not
        # always max_batch: an idle single request padded to a
        # 64-wide wave paid 64x the prefill FLOPs it needed — the
        # round-3 idle-TTFT regression.  Few widths × few length
        # buckets keeps the compile count small.
        tokens = np.zeros((padded_w, bucket), np.int32)
        true_lens = np.ones((padded_w,), np.int32)
        slots = np.zeros((padded_w,), np.int32)
        temps = np.zeros((padded_w,), np.float32)
        seeds = np.zeros((padded_w,), np.int32)
        starts = np.zeros((padded_w,), np.int32)
        lidx = np.zeros((padded_w,), np.int32)
        for j in range(padded_w):
            slot, req = chunk[min(j, W - 1)]
            seq = req.prompt + req.tokens   # resume: recompute full seq
            tokens[j, :len(seq)] = seq
            true_lens[j] = len(seq)
            slots[j] = slot
            temps[j] = req.temperature
            seeds[j] = req.sample_seed
            starts[j] = len(req.tokens)
            lidx[j] = req.lora_slot
        for _, req in chunk:
            self.prefill_tokens += len(req.prompt) + len(req.tokens)
        self._count(*self._spec.prefill_work(true_lens, bucket), ph)
        slots_dev = jnp.asarray(slots)
        lens_dev = jnp.asarray(true_lens)
        cols = np.arange(bucket) // self.page
        page_ids = self._table[slots][:, cols]  # [padded_w, bkt]
        rows = np.tile(
            np.arange(bucket, dtype=np.int32) % self.page,
            (padded_w, 1))
        nxt, ks, vs, state, counts = self._prefill_fwd(
            self.params, jnp.asarray(tokens), lens_dev,
            slots_dev, jnp.asarray(temps), jnp.asarray(seeds),
            jnp.asarray(starts), self._lora_args(lidx))
        self.cache = self._scatter_pages(
            self.cache, ks, vs, state, jnp.asarray(page_ids),
            jnp.asarray(rows), slots_dev, lens_dev)
        if self._spec.routed_layers:
            # fetched with the wave's first tokens; beside them the rows
            # the program routed (padding rows of the width repeat one)
            self._prefill_counts.append(
                (counts, int(true_lens.sum()), padded_w * bucket))
        # Duplicate padding rows target the same slot + same token.
        self._cur_dev = self._cur_dev.at[slots_dev].set(nxt)
        return nxt

    def _prefill_chunk_suffix(self, chunk, padded_w: int, bucket: int):
        """Prefix-cache prefill: forward only each request's uncached
        SUFFIX, attending the cached prefix through the page pool; the
        suffix KV scatters at its absolute positions (prefill_from is a
        page multiple — or the COW'd private page for a full match — so
        shared pages are never written)."""
        import jax.numpy as jnp

        W = len(chunk)
        tokens = np.zeros((padded_w, bucket), np.int32)
        pos0 = np.zeros((padded_w,), np.int32)
        last_idx = np.zeros((padded_w,), np.int32)
        true_lens = np.ones((padded_w,), np.int32)
        slots = np.zeros((padded_w,), np.int32)
        temps = np.zeros((padded_w,), np.float32)
        seeds = np.zeros((padded_w,), np.int32)
        starts = np.zeros((padded_w,), np.int32)
        lidx = np.zeros((padded_w,), np.int32)
        for j in range(padded_w):
            slot, req = chunk[min(j, W - 1)]
            seq = req.prompt + req.tokens
            suffix = seq[req.prefill_from:]
            tokens[j, :len(suffix)] = suffix
            pos0[j] = req.prefill_from
            last_idx[j] = len(suffix) - 1
            true_lens[j] = len(seq)
            slots[j] = slot
            temps[j] = req.temperature
            seeds[j] = req.sample_seed
            starts[j] = len(req.tokens)
            lidx[j] = req.lora_slot
        for _, req in chunk:
            self.prefill_tokens += (len(req.prompt) + len(req.tokens)
                                    - req.prefill_from)
        # Scatter coordinates at ABSOLUTE positions: suffix token p of
        # slot b lands at pos0[b] + p; positions past the allocated
        # span resolve to the trash page via the zeroed table columns.
        apos = np.minimum(pos0[:, None] + np.arange(bucket)[None, :],
                          self._maxp * self.page - 1)
        cols = (apos // self.page).astype(np.int64)
        page_ids = np.take_along_axis(self._table[slots], cols, axis=1)
        rows = (apos % self.page).astype(np.int32)
        slots_dev = jnp.asarray(slots)
        nxt, ks, vs = self._prefill_suffix(
            self.params, self.cache["k"], self.cache["v"],
            jnp.asarray(tokens), jnp.asarray(pos0),
            jnp.asarray(self._table[slots]), jnp.asarray(last_idx),
            jnp.asarray(temps), jnp.asarray(seeds), jnp.asarray(starts),
            self._lora_args(lidx))
        self.cache = self._scatter_pages_coord(
            self.cache, ks, vs, self.cache["state"], jnp.asarray(page_ids),
            jnp.asarray(rows), slots_dev, jnp.asarray(true_lens))
        self._cur_dev = self._cur_dev.at[slots_dev].set(nxt)
        return nxt

    def _apply_import(self, slot: int, req: _Request) -> None:
        """Scatter a migrated request's KV pages into its freshly
        reserved blocks and seed the slot's position/current token —
        the admission-time half of kv_import().  The (possibly
        arena-view) payload is dropped immediately after the device
        copy so a migrated object's pin never outlives its single
        read."""
        import jax.numpy as jnp

        n_imp = -(-req.import_len // self.page)
        ids = req.pages[:n_imp]
        kv = req.import_kv
        m = _pow2(n_imp)
        if m > n_imp:
            # Pad ids with the trash page (writes there are garbage by
            # contract) so import widths compile per power of two.
            pad = np.zeros(kv.shape[:2] + (m - n_imp,) + kv.shape[3:],
                           kv.dtype)
            kv = np.concatenate([kv, pad], axis=2)
            ids = list(ids) + [0] * (m - n_imp)
        self.cache, self._cur_dev = self._import_pages(
            self.cache, self._cur_dev, jnp.asarray(kv),
            jnp.asarray(ids, jnp.int32),
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(req.import_len, jnp.int32),
            jnp.asarray(req.tokens[-1], jnp.int32))
        req.import_kv = None
        self.kv_imports += 1

    def _finish_export(self, slot: int, req: _Request) -> None:
        """Finish a prefill_only request: dispatch the page gather for
        migration and hand the HOST FETCH to the export thread — a
        synchronous device→host read here would stall the engine loop
        (and every co-resident request's admission) for the full
        device→host fetch per migration.  The covered blocks are
        export-pinned (BlockManager.export_blocks) so the
        commit/release in _release_slot — which must run on THIS
        thread, it owns the slot table — cannot free them before the
        fetch lands; refcounted pins also make them eviction-proof."""
        import jax.numpy as jnp

        from ray_tpu import failpoints

        ids = None
        try:
            kv_len = len(req.prompt) + len(req.tokens) - 1
            ids = self._mgr.export_blocks(req.pages, kv_len)
            # The failpoint models a fault INSIDE the pinned window —
            # the hard case: the export pins must be dropped on the
            # way out or the pool silently shrinks per failed export.
            if failpoints.ACTIVE:
                failpoints.fire("serve.kv_export")
            n = len(ids)
            m = _pow2(n)
            ids_p = list(ids) + [0] * (m - n)
            # Async dispatch + async copy: the loop moves on while the
            # device computes and the bytes stream to the host.
            arr = self._gather_kv(self.cache["k"], self.cache["v"],
                                  jnp.asarray(ids_p, jnp.int32))
            try:
                arr.copy_to_host_async()
            except AttributeError:
                pass
        except BaseException as e:  # noqa: BLE001 - injected faults
            # A failed export (serve.kv_export failpoint, OOM on the
            # gather) must not kill the engine loop NOR leak anything:
            # drop the export pins AND the request's own refs, fail the
            # one future, and let the server fall back to serving
            # locally.
            if ids is not None:
                self._mgr.release(ids)
            self._release_slot(slot, req)
            req.emit(None)
            if not req.future.done():
                req.future.set_exception(e)
            return
        self._release_slot(slot, req)
        # The prefill engine produced the request's REAL first token —
        # observe its TTFT here (the export early-return in _finish
        # skips the unified-path observation, and the decode side must
        # not re-observe a near-zero one).
        self._observe_done(req, time.perf_counter())
        self._ensure_export_thread().put(
            ("export", req, arr, ids, kv_len, n))

    def _export_loop(self) -> None:
        """Materializes device→host payloads off the engine loop: KV
        migrations (kv_export) and prefix-store demotions both fetch
        here, so the engine thread never CALLS a page fetch.  That
        alone does not keep it running: whatever this thread does
        under the GIL for the length of a page (a copy, a pickle) the
        engine thread stands for, with the device idle behind it
        (39–160 ms a demotion before PR 33, PERF.md section 6).
        Demotion fetches piece by piece (_fetch_pages) and publishes
        an array the serializer carries out of band; kv_export still
        fetches one stacked array."""
        while True:
            item = self._export_q.get()
            if item is None:
                return
            if item[0] == "demote":
                self._demote_one(*item[1:])
            else:
                self._export_one(*item[1:])

    def _export_one(self, req, arr, ids, kv_len: int, n: int) -> None:
        """One kv_export materialization: the stacked
        [2, L, n, kvh, page, hd] host array covers every position whose
        KV has been written (the newest token's hasn't — the importer
        recomputes it as its first decode step); resolves the request's
        future and drops the export pins."""
        t_exp0 = time.time()
        try:
            # Contiguous copy of the REAL payload: a bare slice
            # would pin the whole pow-2-padded buffer and force
            # put() to copy the non-contiguous view again.
            host = np.ascontiguousarray(np.asarray(arr)[:, :, :n])
        except BaseException as e:  # noqa: BLE001
            self._mgr.release(ids)
            req.emit(None)
            if not req.future.done():
                req.future.set_exception(e)
            return
        self._mgr.release(ids)
        self.kv_exports += 1
        if tracing.ENABLED and req.trace is not None:
            # The device→host KV fetch of one migration — the
            # export half of the kv_export→put→pull→kv_import leg.
            tracing.emit("llm.kv_export", t_exp0, ctx=req.trace,
                         attrs={"bytes": host.nbytes,
                                "kv_len": kv_len, "pages": n})
        now = time.perf_counter()
        req.emit(None)
        if not req.future.done():
            req.future.set_result({
                "tokens": req.tokens,
                "ttft_s": (req.first_token_at or now)
                - req.submitted_at,
                "total_s": now - req.submitted_at,
                "kv_export": {
                    "kv": host, "len": kv_len, "page": self.page,
                    "sample_seed": req.sample_seed,
                    "tokens": list(req.tokens)},
            })

    def _done(self, req: _Request) -> bool:
        return (len(req.tokens) >= req.max_new_tokens
                or (req.eos_id is not None
                    and req.tokens[-1] == req.eos_id))

    def _release_slot(self, slot: int, req: _Request) -> None:
        """Commit the request's computed full blocks into the prefix
        cache, then drop its references (cached blocks stay resident
        but evictable; private ones free).  KV is valid only below
        prompt+tokens-1: the newest token's K/V hasn't been written,
        and rows past a lane's early finish hold trimmed overshoot."""
        if not req.pages:
            return
        kv_valid = len(req.prompt) + len(req.tokens) - 1
        if req.cache_ok and req.cache_gen == self._cache_gen:
            # A request admitted before a weight swap computed (some
            # of) its KV under the OLD policy — committing it would
            # repollute the freshly-flushed cache with stale pages.
            self._mgr.commit(req.prompt + req.tokens,
                             req.pages[:kv_valid // self.page],
                             salt=req.salt)
        self._mgr.release(req.pages)
        req.pages = []
        # The freed slot's future (garbage) decode writes go to the
        # trash page once the zeroed table row reaches the device
        # (next _admit or dirty refresh — both before the pages can
        # be re-issued to a new request).
        self._table[slot, :] = 0
        self._table_dirty = True

    def _finish(self, slot: int) -> None:
        req = self._slots[slot]
        self._slots[slot] = None
        self._adapters[slot] = 0      # the lane's adapter is evictable
        self.completed += 1
        if req.prefill_only and req.pages \
                and not (req.eos_id is not None and req.tokens
                         and req.tokens[-1] == req.eos_id):
            # Export path: block release + table scrub happen here (the
            # loop owns both); the host fetch and future resolution ride
            # the export thread.  An eos-terminated request skips it —
            # generation is over, so gathering/fetching its KV would be
            # a full device→host fetch for a payload nobody consumes
            # (the server returns the tokens directly when kv_export is
            # absent).
            self._finish_export(slot, req)
            return
        self._release_slot(slot, req)
        now = time.perf_counter()
        self._observe_done(req, now)
        req.emit(None)
        if not req.future.done():
            req.future.set_result({
                "tokens": req.tokens,
                "ttft_s": (req.first_token_at or now) - req.submitted_at,
                "total_s": now - req.submitted_at,
            })

    def _observe_done(self, req: _Request, now: float) -> None:
        """Feed the request's latency into the TTFT/TPOT/stage
        histograms (→ controller KV → dashboard /metrics as proper
        Prometheus histogram families).  A migrated decode-side request
        (import_len > 0) skips the TTFT/queue/prefill observations: its
        first_token_at is the IMPORT application, not a real first
        token — the prefill engine that produced the token observed
        the true TTFT (see _finish_export)."""
        try:
            m = _engine_metrics()
        except Exception:  # noqa: BLE001 - metrics must not stop decode
            return
        ft = req.first_token_at
        if ft is None:
            return
        tags = {"engine": self.name}
        imported = req.import_len > 0
        if not imported:
            m["ttft"].observe((ft - req.submitted_at) * 1000.0, tags)
            self._slo_window.observe(
                "ttft_ms", (ft - req.submitted_at) * 1000.0)
        n = len(req.tokens)
        if n > 1 and now > ft:
            m["tpot"].observe((now - ft) * 1000.0 / (n - 1), tags)
        if req.admitted_at:
            st = m["stage"]
            if not imported:
                st.observe(
                    (req.admitted_at - req.submitted_at) * 1000.0,
                    {**tags, "stage": "queue"})
                st.observe((ft - req.admitted_at) * 1000.0,
                           {**tags, "stage": "prefill"})
                self._slo_window.observe(
                    "queue_ms",
                    (req.admitted_at - req.submitted_at) * 1000.0)
                self._slo_window.observe(
                    "prefill_ms", (ft - req.admitted_at) * 1000.0)
            if not req.prefill_only:
                # No decode ran on a prefill-only export — a ~0ms
                # sample here would drag the cross-engine decode
                # quantiles toward zero as migration volume grows.
                st.observe((now - ft) * 1000.0,
                           {**tags, "stage": "decode"})
                self._slo_window.observe("decode_ms",
                                         (now - ft) * 1000.0)

    def _preempt_slot(self, slot: int) -> None:
        """Evict a running request from its slot: its blocks go to the
        prefix cache (so recompute usually prefix-hits them if nobody
        claims the memory first) and it re-enters the pending queue at
        the FRONT.  Tokens already streamed stay valid — per-request
        sampling keys make the recomputed continuation identical."""
        req = self._slots[slot]
        self._slots[slot] = None
        self._temps[slot] = 0.0
        self._seeds[slot] = 0
        self._adapters[slot] = 0
        self._release_slot(slot, req)
        req.slot = -1
        req.preempted += 1
        self.preemptions += 1
        self._pending.appendleft(req)

    def _ensure_decode_blocks(self, k_win: int | None = None
                              ) -> list[int]:
        """Block-budget scheduling before each decode block: every
        active slot needs real pages under the next K merge positions.
        Oldest requests are funded first; when the pool (free +
        evictable) runs dry, the NEWEST active request is preempted and
        recomputed later — deterministic, and the oldest request can
        always make progress (its full span fits the pool by the
        submit-time check).  Returns the surviving active slots.
        `k_win` is the loop's snapshot of the sync window — funding and
        the decode call must agree on it (a concurrent set_sync_window
        between them must not leave the window underfunded)."""
        if k_win is None:
            k_win = self._k_live
        active = [i for i, s in enumerate(self._slots) if s is not None]
        if not active:
            return active
        for slot in sorted(active,
                           key=lambda i: self._slots[i].sample_seed):
            req = self._slots[slot]
            if req is None:                  # preempted this round
                continue
            total = len(req.prompt) + len(req.tokens)
            cover = min(total - 1 + k_win,
                        len(req.prompt) + req.max_new_tokens)
            need = -(-cover // self.page) - len(req.pages)
            if need <= 0:
                continue
            got = self._mgr.allocate(need)
            while got is None and self._preempt_on:
                victims = [i for i, s in enumerate(self._slots)
                           if s is not None]
                victim = max(victims,
                             key=lambda i: self._slots[i].sample_seed)
                self._preempt_slot(victim)
                if victim == slot:
                    break
                got = self._mgr.allocate(need)
            if got is None or self._slots[slot] is None:
                continue
            req.pages.extend(got)
            self._funded_blocks += len(got)
            self._table[slot, :len(req.pages)] = req.pages
            self._table_dirty = True
        return [i for i, s in enumerate(self._slots) if s is not None]

    def _loop(self) -> None:
        try:
            self._loop_inner()
        except BaseException as e:  # noqa: BLE001
            # Fail every in-flight and waiting request: a silent thread
            # death would hang their futures forever, and the donated
            # cache is invalid after a failed call anyway.
            self._error = e
            self._drain_requests(e)
            self._stop.set()
            raise

    def _loop_inner(self) -> None:
        while not self._stop.is_set():
            self._loop_once()

    def _loop_once(self) -> None:
        """One iteration of the engine thread.  Its phases (see _phase)
        partition the thread's time: none overlaps another, and nothing
        that takes time runs outside one.  There is no span over the
        whole iteration: `iter` ties its phases together."""
        import jax.numpy as jnp

        self._iter = it = self._iter + 1
        with self._phase("admit", iter=it) as ph:
            self._maybe_swap_weights()
            # Grafts apply right after the swap (the version check must
            # see the tree a commit would land in) and BEFORE admission
            # so the request that triggered the graft prefix-hits it.
            self._apply_grafts()
            wave = self._admit(ph)
        if wave:
            self._prefill_wave(wave, it)
        with self._phase("fund", iter=it) as ph:
            # ONE sync-window snapshot per iteration: funding and the
            # decode program must see the same K (set_sync_window may
            # race from a replica thread).
            k_win = self._k_live
            blocks0, pre0 = self._funded_blocks, self.preemptions
            demotes0 = self._demote_dispatched
            active = self._ensure_decode_blocks(k_win)
            self._maybe_demote()
            self._flush_metrics()
            if active and self._table_dirty:
                self._table_dev = jnp.asarray(self._table)
                self._table_dirty = False
            ph.update(blocks=self._funded_blocks - blocks0,
                      preempted=self.preemptions - pre0,
                      demotes=self._demote_dispatched - demotes0)
        if not active:
            self._idle_wait()
            return
        with self._phase("decode_dispatch", iter=it, lanes=len(active),
                         steps=k_win) as ph:
            starts = np.zeros((self.max_batch,), np.int32)
            lane_rows = []      # cached rows each live lane starts on
            for i in active:
                req = self._slots[i]
                starts[i] = len(req.tokens)
                lane_rows.append(min(len(req.prompt) + len(req.tokens) - 1,
                                     self._maxp * self.page))
            # the lanes' share of the attention kernel's grid
            # (ops/paged_attention.attention_plan): a step a page holding
            # rows below a lane's block-start position
            attn_steps = sum(max(-(-rows // self.page), 1)
                             for rows in lane_rows)
            work, shown = self._spec.decode_work(lane_rows, k_win, self.page,
                                                 self._maxp)
            ph.update(shown, attn_steps=attn_steps)
            win_traced = tracing.ENABLED and any(
                self._slots[i] is not None
                and self._slots[i].trace is not None for i in active)
            t_win0 = time.time() if win_traced else 0.0
            decode = self._decode_fns.get(k_win)
            if decode is None:
                decode = self._decode_fns.setdefault(
                    k_win, self._make_decode(k_win))
            out = decode(
                self.params, self.cache, self._cur_dev,
                jnp.asarray(self._temps), self._table_dev,
                jnp.asarray(self._seeds), jnp.asarray(starts),
                self._lora_args(self._adapters))
            seq, last, self.cache = out[:3]
            self._cur_dev = last                # stays on device
            if self._spec.routed_layers:
                out[3].copy_to_host_async()
            # the scan's page gathers queue BEHIND the window the lanes
            # wait for, on the cache it returned
            ph.update(demote_pages=self._dispatch_demotes())
            self._count({
                "decode_steps": k_win,
                "lane_steps_live": len(active) * k_win,
                "attn_steps": attn_steps,
                "attn_steps_dense": self.max_batch * (self._maxp + 1),
                # a lane's context at each of the K steps: its
                # block-start rows + the tail's j + 1
                "attn_ctx_rows": k_win * sum(lane_rows)
                + len(active) * k_win * (k_win + 1) // 2, **work})
        with self._phase("decode_sync", iter=it):
            seq = np.asarray(seq)               # the ONE sync per block
            # the routed layers' counts: a few hundred bytes of the same
            # program, on their way since dispatch; no second wait
            counts = (np.asarray(out[3]) if self._spec.routed_layers
                      else None)
            t_win1 = time.time() if win_traced else 0.0
        with self._phase("deliver", iter=it) as ph:
            tokens0, done0 = self.decode_tokens, self.completed
            if counts is not None:
                self._count(*self._spec.routed_work(
                    counts, k_win, len(active) * k_win, self.max_batch,
                    False), ph)
            if win_traced:
                # One K-step decode window per traced co-resident
                # request: the window (dispatch → host sync) is the
                # decode-side unit of TTFT/TPOT attribution.
                for i in active:
                    r = self._slots[i]
                    if r is not None and r.trace is not None:
                        tracing.emit(
                            "llm.decode_window", t_win0, t_win1,
                            ctx=r.trace,
                            attrs={"steps": k_win,
                                   "weight_version":
                                   self.weight_version})
            for i in active:
                req = self._slots[i]
                if req is None:
                    continue
                for tok in seq[:, i]:
                    req.tokens.append(int(tok))
                    self.decode_tokens += 1
                    req.emit(int(tok))
                    if self._done(req):
                        # Trim K-step overshoot past EOS/max_new_tokens.
                        self._finish(i)
                        break
            ph.update(tokens=self.decode_tokens - tokens0,
                      finished=self.completed - done0)

    def _idle_wait(self) -> None:
        """No lane is live: wait for work under ONE `idle` phase, however
        many times the wait times out.  The phase ends when something
        woke the loop or waits to be admitted, and the next iteration's
        admit phase takes it from there; until then only the periodic
        housekeeping runs, inside the phase."""
        with self._phase("idle", pending=len(self._pending)):
            self._dispatch_demotes()    # no window to queue behind
            while not self._stop.is_set():
                # With a head-of-line request waiting on blocks and no
                # active decode to free them, only finished-and-cached
                # blocks can help — _admit retries (allocate evicts
                # refcount-0 leaves), so just avoid a busy spin.
                woke = self._wake.wait(
                    timeout=0.002 if self._pending else 0.05)
                self._wake.clear()
                if (woke or self._pending or not self._waiting.empty()
                        or not self._graft_q.empty()
                        or self._staged_weights is not None):
                    return
                self._maybe_demote()
                self._dispatch_demotes()
                self._flush_metrics()

    def _flush_metrics(self, force: bool = False,
                       threads: dict | None = None) -> None:
        """Export engine/cache counters as process metrics (→ controller
        KV → dashboard /metrics).  Counters flush as deltas against the
        last snapshot; throttled to ~1 Hz so the loop never stalls on
        the registry lock.  `threads`: the CPU ledger's rows, where the
        caller (`stats`) has just read them."""
        now = time.monotonic()
        if not force and now - self._metrics_t < 1.0:
            return
        try:
            m = _engine_metrics({**_LOOP_WORK, **self._spec.counters})
        except Exception:  # noqa: BLE001 - metrics must never stop decode
            return
        tags = {"engine": self.name}
        cur = {**self.work,
               "prefill_tokens": self.prefill_tokens,
               "decode_tokens": self.decode_tokens,
               "demote_bytes": self.demote_bytes,
               "preemptions": self.preemptions,
               "completed": self.completed,
               "weight_updates": self.weight_updates,
               "prefix_hit_tokens": self._mgr.hit_tokens,
               "evictions": self._mgr.evictions}
        with _BUILDS_LOCK:
            cur["program_builds"] = _BUILDS["program_builds"]
            cur["program_build_s"] = _BUILDS["program_build_s"]
        cur["stalls"], cur["stall_s"] = self.stalls, self.stall_s
        # (counter, tag it is split by, its rows)
        split = [("phase_cpu_s", "phase", dict(self.phase_cpu_s)),
                 ("thread_cpu_s", "thread", threads or {}),
                 ("gc_pause_s", "generation",
                  {str(g): row[1] for g, row in _GC_BY_GENERATION.items()})]
        with self._metrics_lock:
            self._metrics_t = now
            for key, val in cur.items():
                delta = val - self._metrics_last.get(key, 0)
                if delta > 0:
                    m[key].inc(delta, tags)
                self._metrics_last[key] = val
            for key, tag, rows in split:
                for row, val in rows.items():
                    delta = val - self._metrics_last.get((key, row), 0.0)
                    if delta > 0:
                        m[key].inc(delta, {**tags, tag: row})
                    self._metrics_last[key, row] = val
        m["occupancy"].set(
            sum(s is not None for s in self._slots) / self.max_batch,
            tags)
        m["queue_depth"].set(
            self._waiting.qsize() + len(self._pending), tags)
        m["weight_version"].set(float(self.weight_version), tags)
        m["free_blocks"].set(self._mgr.free_count(), tags)
        m["prefill_floor_positions"].set(self._prefill_floor, tags)
        seen = self._mgr.hit_tokens + self.prefill_tokens
        m["hit_rate"].set(
            self._mgr.hit_tokens / seen if seen else 0.0, tags)

    def kv_check(self) -> dict:
        """Assert the block-state partition (test/ops probe): raises if
        any KV block is leaked or double-booked.  Shared by the serve
        replica's kv_check RPC and the RLHF rollout workers' post-chaos
        leak checks."""
        self._mgr.check()
        return {"ok": True, "free": self._mgr.free_count(),
                "available": self._mgr.available()}

    def _cache_stats(self) -> dict:
        """The page pool by what it holds: a word for it ("kv": a K and
        a V pool, whatever grouped rows ride beside them; else its first
        entry's name), the bytes a token's rows
        take in one layer as stored, the layers that keep any, and the
        pool's bytes."""
        pool = _pool(self.cache)
        by_leaf = {
            name: {"row_bytes": int(v[0].shape[1] * v[0].shape[3]
                                    * v[0].dtype.itemsize),
                   "positions_per_row": _per_row(v[0], self.page),
                   "layers": len(v),
                   "pool_bytes": int(sum(a.size * a.dtype.itemsize
                                         for a in v))}
            for name, v in pool.items()}
        return {"kind": ("kv" if list(pool)[:2] == ["k", "v"]
                         else next(iter(pool))),
                # a TOKEN's bytes: a row shared by g positions counts 1/g
                "row_bytes": int(sum(
                    b["row_bytes"] // b["positions_per_row"]
                    for b in by_leaf.values())),
                "layers": max(b["layers"] for b in by_leaf.values()),
                "pool_bytes": sum(b["pool_bytes"]
                                  for b in by_leaf.values()),
                "by_leaf": by_leaf}

    def _lane_state_stats(self) -> dict:
        """The lanes' state beside the pool, from its own leaves: bytes
        in all and by kind (the keys of a state that is a dict: memory
        divided between the pool, `stats()["cache"]`, and these)."""
        import jax

        def nbytes(tree):
            return int(sum(a.size * a.dtype.itemsize
                           for a in jax.tree.leaves(tree)))

        state = self.cache["state"]
        kinds = state if isinstance(state, dict) else {"rows": state}
        return {"layers": self._spec.lane_state_layers,
                "bytes": nbytes(state),
                "by_kind": {k: nbytes(v) for k, v in kinds.items()},
                "prefix_cache": "off: lane state"}

    def stats(self) -> dict:
        out = {"completed": self.completed,
               "active": sum(s is not None for s in self._slots),
               "waiting": self._waiting.qsize() + len(self._pending),
               "max_batch": self.max_batch,
               "max_len": self.max_len,
               "preemptions": self.preemptions,
               "prefill_tokens": self.prefill_tokens,
               "decode_tokens": self.decode_tokens,
               "prefix_cache": self._prefix_cache,
               "kv_preempt": self._preempt_on,
               "kv_exports": self.kv_exports,
               "kv_imports": self.kv_imports,
               "kv_grafts": self.kv_grafts,
               "graft_tokens": self.graft_tokens,
               "demote_published": self.demote_published,
               "demote_failures": self.demote_failures,
               "weight_version": self.weight_version,
               "weight_updates": self.weight_updates,
               "weight_syncs_skipped": self.weight_syncs_skipped,
               "last_weight_sync_ms": round(self.last_weight_sync_ms,
                                            3),
               # SLO loop inputs (serve/slo.py): recent-request latency
               # percentiles + the live sync window.
               "slo": self._slo_window.snapshot(),
               "sync_window": self._k_live,
               "sync_window_shrinks": self.sync_window_shrinks,
               # The engine thread's timeline, cumulative: every row of
               # the table of work counters (_LOOP_WORK for the ratios
               # an operator reads off the loop's own; the model's rows
               # are documented where it declares them) beside the
               # phases' seconds.
               "loop": {
                   **self.work,
                   "phase_s": dict(self.phase_s),
                   # the thread's own CPU seconds in each phase: what
                   # phase_s has more is the time it stood there
                   "phase_cpu_s": dict(self.phase_cpu_s),
                   # the prefix store's demotion: pages and host bytes
                   # fetched off the device, and the export thread's
                   # seconds in those fetches (bytes a second demoted,
                   # beside phase_s["decode_sync"])
                   "demote_pages": self.demote_pages,
                   "demote_bytes": self.demote_bytes,
                   "demote_fetch_s": round(self.demote_fetch_s, 6),
                   "prefill_true_tokens": self.prefill_tokens,
                   "prefill_floor_positions": self._prefill_floor},
               "cache": dict(self._cache_info)}
        with _BUILDS_LOCK:
            # every program this PROCESS built since its first engine
            # was made; the ledger of threads is the process's too
            out["loop"].update(
                _BUILDS, program_build_s=round(_BUILDS["program_build_s"], 6))
        # stalls of this engine's loop with work waiting (`llm.stall`
        # spans say which phase stood and who held it), and the
        # PROCESS's collector pauses (`llm.gc_pause` from 1 ms up)
        out["loop"].update(
            stalls=self.stalls, stall_s=round(self.stall_s, 6),
            gc_pauses=_GC["gc_pauses"],
            gc_pause_s=round(_GC["gc_pause_s"], 6),
            gc_by_generation={
                str(g): {"pauses": n, "pause_s": round(sec, 6)}
                for g, (n, sec) in _GC_BY_GENERATION.items()})
        out["threads"] = _thread_cpu_ledger()
        if self._spec.lane_state_layers:
            out["lane_state"] = dict(self._lane_info)
        if self._lora_banks is not None:
            with self._lora_lock:
                now = time.monotonic()
                out["lora"] = {
                    "slots": self.lora_slots,
                    "rank": self.lora_rank,
                    "free": len(self._lora_free),
                    "loads": self.adapter_loads,
                    "evictions": self.adapter_evictions,
                    # Residency export → replica_metrics → the handle's
                    # summary poll → kv_router.choose: the salt lets
                    # the router score salted prompt hashes per
                    # candidate; age drives its LRU reasoning.
                    "resident": {
                        mid: {"salt": m["salt"],
                              "version": m["version"],
                              "age": round(now - m["last_used"], 3)}
                        for mid, m in self._lora_meta.items()},
                }
        kv = self._mgr.stats()
        out["kv"] = kv
        out["prefix_hits"] = kv["hits"]
        out["prefix_misses"] = kv["misses"]
        out["prefix_hit_tokens"] = kv["hit_tokens"]
        out["evictions"] = kv["evictions"]
        out["cow_copies"] = kv["cow_copies"]
        self._flush_metrics(force=True, threads=out["threads"]["by_name"])
        return out


class LLMServer:
    """Serve deployment body: one engine per replica.

    serve.deployment(LLMServer).options(...) — requests carry token-id
    prompts; a tokenizer front can be composed as another deployment.
    Engine memory knobs (page_size / kv_pages / prefix_cache /
    kv_preempt) are operator-tunable through `engine_config` in the
    declarative deploy config (serve/schema.py) and through
    `reconfigure` (user_config), which rebuilds the engine in place.

    **Pool roles** (disaggregated prefill/decode, DistServe/Mooncake
    shape): `role="prefill"` replicas run ONLY the prompt pass — the
    finished KV pages are sealed into an arena object and shipped to a
    replica of the `decode_deployment` pool, whose engine imports them
    (`kv_decode`) and owns the whole decode phase.  Prefill compute
    thus never steals decode batch slots, and the KV transfer rides the
    object plane (same-host moves take the direct-shm pull, cross-node
    the streaming-write path).  `decode_deployment` is the decode
    pool's deployment name (declarative config) or its bound
    Application/handle (Python composition).  Both pools should share
    the engine `seed` so a migrated continuation draws the same sample
    stream an unsplit engine would.  Kill switch RAY_TPU_PD_DISAGG=0
    (or per-request {"disagg": false}) serves unified on the prefill
    replica itself — same-run A/B.
    """

    # Adapter requests re-page + resubmit this many times total when a
    # concurrent tenant's load evicts their adapter between the
    # server's page-in and engine-loop admission (slots thrash when
    # adapters >> slots).  Admission precedes block/lane allocation and
    # the first token, so a resubmit is invisible to the client.
    _LORA_ADMIT_RETRIES = 3

    def __init__(self, model: str = "debug", *, max_batch: int = 8,
                 max_len: int | None = None, params=None, seed: int = 0,
                 warmup: bool = False, paged: bool = True,
                 page_size: int = 512, kv_pages: int | None = None,
                 prefix_cache: bool | None = None,
                 kv_preempt: bool | None = None,
                 steps_per_sync: int = 8,
                 role: str = "unified",
                 decode_deployment=None,
                 prefix_store: dict | None = None,
                 lora_slots: int = 0, lora_rank: int = 0,
                 lora_directory=None):
        from ray_tpu.models import named_config, serving_model

        _check_pool_role(role, decode_deployment)
        _check_paged(paged)
        cfg = named_config(model) if isinstance(model, str) else model
        served_by = serving_model(cfg)
        if (role != "unified"
                and "kv_transfer" not in served_by.serving_spec(cfg).caps):
            raise ValueError(
                f"role={role!r} needs KV export/import, which "
                f"{served_by.__name__} lacks (what its lanes or its "
                "pool hold is no K and V page): serve it unified")
        name = "llm"
        self._app_name = None
        try:
            from ray_tpu.serve import replica as _replica

            ctx = _replica.get_current_context()
            if ctx is not None and ctx.deployment:
                name = ctx.deployment
                self._app_name = ctx.app_name
        except Exception:  # noqa: BLE001 - outside a replica
            pass
        self._engine_kwargs = dict(
            max_batch=max_batch, max_len=max_len, seed=seed, paged=paged,
            page_size=page_size, kv_pages=kv_pages,
            prefix_cache=prefix_cache, kv_preempt=kv_preempt,
            steps_per_sync=steps_per_sync, lora_slots=lora_slots,
            lora_rank=lora_rank, name=name)
        self._cfg = cfg
        self._params = params
        self._warmup = warmup
        self._role = role
        self._decode_dep = decode_deployment
        self._decode_handle = None
        self._decode_kv_handle = None
        # Migration observability (→ stats() → serve.replica_metrics):
        # bytes/ms through the object plane, split by side.  The pull
        # side mutates from the replica's thread POOL (kv_decode is a
        # sync method), so its counters take the lock; the put side
        # runs on the event loop and is naturally serialized.
        self._pd_lock = threading.Lock()
        self._migrations = 0
        self._pd_fallbacks = 0
        self._kv_migrate_bytes = 0
        self._kv_migrate_put_ms = 0.0
        self._kv_pull_bytes = 0
        self._kv_pull_ms = 0.0
        # Overload degradation ladder (serve/slo.py OverloadTracker,
        # pressure = engine queue depth): level 1 sheds PD-disagg to
        # unified serving (skip the migration round trips), level 2
        # also shrinks the decode sync window so queued requests admit
        # sooner.  Both restore on sustained recovery.  Kill switch
        # RAY_TPU_SERVE_DEGRADE=0.
        self._overload = slo.OverloadTracker(hi=max(4, 2 * max_batch))
        self._degraded_window = max(1, min(2, steps_per_sync))
        self._sheds = 0
        self._restores = 0
        # Tier-2 cluster prefix store (serve/prefix_store.py): the
        # client owns this replica's demoted arena objects and runs
        # the miss-path fetch/graft; config knobs ride the
        # `prefix_store` dict ({"enabled", "min_idle", "period_s",
        # "watermark_frac", "min_tokens", "migrate_ms", ...}).
        self._prefix_store_cfg = dict(prefix_store or {})
        self._prefix_client = None
        # Multi-LoRA page-in state (serve/lora.py): ONE in-flight load
        # per model_id (racing requests park on its future) + a short
        # TTL cache of the directory's (version) answer so the
        # resident-adapter fast path costs zero controller round
        # trips.  `lora_directory` injects an in-process
        # AdapterDirectory (tests / local mode).
        self._lora_client = None
        self._lora_directory = lora_directory
        self._lora_inflight: dict = {}
        self._lora_inflight_lock = threading.Lock()
        self._lora_seen: dict[str, tuple[float, int]] = {}
        self._lora_ttl = float(
            os.environ.get("RAY_TPU_LORA_TTL_S", "2.0") or 0.0)
        self.adapter_load_errors = 0
        self.adapter_admit_retries = 0
        self._closed = False
        self.engine = LLMEngine(cfg, params, **self._engine_kwargs)
        self._install_prefix_store()
        self.engine.start()
        if warmup:
            self.engine.warmup()

    def _install_prefix_store(self) -> None:
        """(Re)attach the prefix-store client + demotion hook to the
        current engine (constructor and every reconfigure rebuild).
        Disabled for prefix_cache=0 engines and explicitly via
        prefix_store={"enabled": False}."""
        from ray_tpu.serve import prefix_store as pstore

        if self._prefix_client is not None:
            self._prefix_client.close()
            self._prefix_client = None
        eng = self.engine
        cfg = self._prefix_store_cfg
        if not eng._prefix_cache or cfg.get("enabled", True) is False:
            eng.set_prefix_store(None)
            return
        rid = None
        try:
            from ray_tpu.serve import replica as _replica

            ctx = _replica.get_current_context()
            if ctx is not None:
                rid = ctx.replica_tag or None
        except Exception:  # noqa: BLE001 - outside a replica
            pass
        self._prefix_client = pstore.PrefixStoreClient(
            app=self._app_name or "default", deployment=eng.name,
            # Unique in-process fallback: several servers can share one
            # interpreter (tests, local mode) and a bare pid would make
            # one server's close() withdraw its siblings' entries.
            replica_id=rid or f"pid:{os.getpid()}-{os.urandom(3).hex()}",
            seed=self._engine_kwargs.get("seed", 0), page=eng.page,
            config=cfg, directory=cfg.get("directory"))
        eng.set_prefix_store(
            self._prefix_client.publish,
            min_idle=cfg.get("min_idle", 256),
            period_s=cfg.get("period_s", 0.25),
            watermark_frac=cfg.get("watermark_frac", 0.125),
            limit=cfg.get("limit", 2),
            max_inflight=cfg.get("max_inflight", 2))

    # -------------------------------------------------- multi-LoRA
    def _request_model_id(self, request) -> str | None:
        """The request's adapter identity, gated PER REQUEST by the
        RAY_TPU_LORA kill switch (off → every request serves the base
        model — the same-run A/B arm).  Absent {"model_id": ...} =
        base model, always."""
        from ray_tpu.serve import kv_router

        if not isinstance(request, dict):
            return None
        mid = request.get("model_id")
        if mid is None or not kv_router.lora_on():
            return None
        return mid

    def _ensure_adapter_sync(self, model_id: str,
                             trace_ctx=None) -> None:
        """Make `model_id` device-resident before submit (blocking —
        callers keep it off the event loop).  Fast path: resident at
        the version the directory reported within the last
        RAY_TPU_LORA_TTL_S seconds — zero controller round trips.
        Slow path: ONE in-flight load per model_id (racing requests
        park on its future): directory lookup → object-plane pull
        (same-host direct-shm / cross-node streaming — the normal get
        path) → engine.load_adapter.  Every failure surfaces as a
        typed AdapterLoadError BEFORE the request holds a batch slot;
        the `serve.adapter_load` failpoint fires at entry, so an
        injected fault degrades to reject-early, never a wedged
        engine loop."""
        from ray_tpu import failpoints
        from ray_tpu.serve import lora as lora_mod

        eng = self.engine
        if failpoints.ACTIVE:
            try:
                failpoints.fire("serve.adapter_load")
            except BaseException as e:  # noqa: BLE001 - typed reject
                self.adapter_load_errors += 1
                raise AdapterLoadError(
                    f"adapter load faulted: {type(e).__name__}: {e}",
                    model_id=model_id, deployment=eng.name,
                    reason="load_failed") from e
        if eng._lora_banks is None:
            raise AdapterLoadError(
                "deployment has no adapter slots (set engine_config "
                "lora_slots)", model_id=model_id, deployment=eng.name,
                reason="lora_slots=0")
        now = time.monotonic()
        seen = self._lora_seen.get(model_id)
        if seen and seen[0] > now \
                and eng.adapter_resident(model_id, seen[1]):
            eng.adapter_touch(model_id)
            return
        with self._lora_inflight_lock:
            fut = self._lora_inflight.get(model_id)
            owner = fut is None
            if owner:
                fut = concurrent.futures.Future()
                self._lora_inflight[model_id] = fut
        if not owner:
            fut.result(timeout=120.0)   # re-raises the owner's error
            return
        try:
            t0 = time.time()
            try:
                if self._lora_client is None:
                    self._lora_client = lora_mod.LoraClient(
                        directory=self._lora_directory)
                entry = self._lora_client.lookup(model_id)
                if entry is None:
                    raise AdapterLoadError(
                        "no such adapter published",
                        model_id=model_id, deployment=eng.name,
                        reason="not_published")
                if not eng.adapter_resident(model_id,
                                            entry["version"]):
                    adapter = lora_mod.resolve_entry(entry)
                    eng.load_adapter(model_id, adapter,
                                     version=entry["version"])
                    if tracing.ENABLED:
                        tracing.emit(
                            "serve.adapter_load", t0, time.time(),
                            ctx=trace_ctx,
                            attrs={"model_id": model_id,
                                   "deployment": eng.name,
                                   "version": entry["version"],
                                   "bytes": entry.get("nbytes", 0)})
                eng.adapter_touch(model_id)
                self._lora_seen[model_id] = (
                    time.monotonic() + self._lora_ttl,
                    entry["version"])
                fut.set_result(None)
            except BaseException as e:  # noqa: BLE001 - typed reject
                self.adapter_load_errors += 1
                err = e if isinstance(e, AdapterLoadError) \
                    else AdapterLoadError(
                        f"adapter load faulted: "
                        f"{type(e).__name__}: {e}",
                        model_id=model_id, deployment=eng.name,
                        reason="load_failed")
                fut.set_exception(err)
                raise err from (None if e is err else e)
        finally:
            with self._lora_inflight_lock:
                self._lora_inflight.pop(model_id, None)

    def _graft_eligible(self, request) -> bool:
        """ONE copy of the miss-path gate for the unary and streaming
        entry points (they must never diverge): a store-capable
        request is a dict with a real token prompt of at least one
        page, not opted out per request, with the env switch on."""
        from ray_tpu.serve import prefix_store as pstore

        eng = self.engine
        if (self._prefix_client is None or not isinstance(request, dict)
                or not request.get("prefix_store", True)
                or not eng._prefix_cache):
            return False
        prompt = request.get("prompt")
        if not isinstance(prompt, (list, tuple)) \
                or len(prompt) < eng.page:
            return False
        return pstore.prefix_store_on()

    def _maybe_graft_sync(self, request: dict) -> None:
        """Miss-path store consultation for one request (the tentpole
        leg; blocking — callers keep it off the event loop): compare
        the local radix match with the cluster directory and graft the
        deepest affordable stored prefix before submitting.
        Per-request kill switches: RAY_TPU_PREFIX_STORE=0 and
        {"prefix_store": false}.  Any failure degrades to a plain
        local prefill."""
        if not self._graft_eligible(request):
            return
        try:
            # Adapter requests graft under the adapter's salt: a tier-2
            # entry only matches KV computed by the SAME (adapter,
            # version) — the base model's cache and every other
            # adapter's hash to disjoint keys.
            mid = self._request_model_id(request)
            salt = self.engine.adapter_salt_of(mid) if mid else 0
            self._prefix_client.maybe_graft(
                self.engine, list(request["prompt"]), salt=salt)
        except Exception:  # noqa: BLE001 - degrade, never fail
            pass

    async def _maybe_graft_async(self, request: dict) -> None:
        import asyncio

        if not self._graft_eligible(request):
            return
        await asyncio.get_running_loop().run_in_executor(
            None, self._maybe_graft_sync, request)

    # ----------------------------------------------- overload ladder
    def _update_pressure(self) -> int:
        """Feed the engine's queue depth to the hysteresis tracker; on
        a level change apply/restore the sync-window knob and emit a
        flight-recorder span so a trace shows WHY service degraded.
        Kill switch RAY_TPU_SERVE_DEGRADE=0 pins level 0 (restoring a
        previously-shrunk window)."""
        eng = self.engine
        if not slo.degrade_on():
            if self._overload.level:
                self._overload.level = 0
                eng.set_sync_window(None)
            return 0
        depth = eng._waiting.qsize() + len(eng._pending)
        level, prev = self._overload.update(depth)
        if level != prev:
            eng.set_sync_window(
                self._degraded_window if level >= 2 else None)
            if level > prev:
                self._sheds += 1
            else:
                self._restores += 1
            if tracing.ENABLED:
                tracing.emit(
                    "serve.shed" if level > prev else "serve.restore",
                    time.time(),
                    attrs={"deployment": eng.name, "level": level,
                           "from": prev, "depth": depth,
                           "sync_window": eng._k_live})
        return level

    # ------------------------------------------------- prefill/decode
    def _disagg(self, request: dict) -> bool:
        from ray_tpu.serve import kv_router

        return (self._role == "prefill"
                and self._decode_dep is not None
                and kv_router.pd_disagg_on()
                and request.get("disagg", True)
                and request.get("max_new_tokens", 32) > 1)

    def _get_decode_handle(self):
        """The decode pool's handle pair, created once per server: the
        base handle (full-generate fallback) and its kv_decode-bound
        sibling (a .options() handle owns its own membership cache and
        router thread — per-request construction would cost a
        controller RT every call)."""
        if self._decode_handle is None:
            dd = self._decode_dep
            if isinstance(dd, str):
                from ray_tpu import serve as serve_api

                base = serve_api.get_deployment_handle(
                    dd, self._app_name or "default")
            else:
                # Bound composition: serve.run already substituted the
                # child Application with a DeploymentHandle.
                base = dd
            self._decode_kv_handle = base.options(
                method_name="kv_decode")
            self._decode_handle = base
        return self._decode_handle

    async def _local_generate(self, request: dict, t_start: float,
                              why: str) -> dict:
        import asyncio

        fut = self.engine.submit(
            request["prompt"],
            max_new_tokens=request.get("max_new_tokens", 32),
            temperature=request.get("temperature", 0.0),
            eos_id=request.get("eos_id"))
        out = await asyncio.wrap_future(fut)
        out["total_s"] = time.perf_counter() - t_start
        out["pd_fallback"] = why
        return out

    async def _prefill_decode(self, request: dict) -> dict:
        """The migration path: prefill here, seal the KV pages into an
        arena object, hand the refs to a decode replica.  Failure at
        any stage degrades, never fails the request: export error →
        serve unified locally; decode-pool error (a replica dying
        mid-migration, an import fault) → full re-prefill on a
        surviving decode replica, then locally as the last resort."""
        import asyncio

        import ray_tpu

        t_start = time.perf_counter()
        try:
            pre = await asyncio.wrap_future(self.engine.submit(
                request["prompt"], max_new_tokens=1,
                temperature=request.get("temperature", 0.0),
                eos_id=request.get("eos_id"), prefill_only=True))
        except Exception:  # noqa: BLE001 - export window faults
            self._pd_fallbacks += 1
            return await self._local_generate(request, t_start,
                                              "export_failed")
        exp = pre.get("kv_export")
        eos = request.get("eos_id")
        if exp is None or (eos is not None and pre["tokens"]
                           and pre["tokens"][-1] == eos):
            return {"tokens": pre["tokens"], "ttft_s": pre["ttft_s"],
                    "total_s": time.perf_counter() - t_start}
        loop = asyncio.get_running_loop()
        # Executor threads don't inherit the handler task's contextvars:
        # carry the request's trace into the put explicitly.
        trace_ctx = tracing.capture() if tracing.ENABLED else None

        def _put():
            t0 = time.perf_counter()
            with tracing.span("serve.kv_put", ctx=trace_ctx,
                              attrs={"bytes": exp["kv"].nbytes}), \
                    memledger.tag("kv_export",
                                  label="serve/llm.py kv_export"):
                r = ray_tpu.put(exp["kv"])
            return r, (time.perf_counter() - t0) * 1000.0

        # put() may block on arena allocation — keep it off the event
        # loop (same rule as every blocking framework call here).
        ref, put_ms = await loop.run_in_executor(None, _put)
        self._migrations += 1
        self._kv_migrate_bytes += exp["kv"].nbytes
        self._kv_migrate_put_ms += put_ms
        meta = {"prompt": list(request["prompt"]),
                "tokens": exp["tokens"], "kv_len": exp["len"],
                "page": exp["page"], "sample_seed": exp["sample_seed"],
                "max_new_tokens": request.get("max_new_tokens", 32),
                "temperature": request.get("temperature", 0.0),
                "eos_id": eos}
        # The arena now holds the KV; drop the host copy BEFORE the
        # decode await (seconds per request) or every in-flight
        # migration carries its prompt KV twice.
        pre.pop("kv_export", None)
        exp = None
        handle = self._get_decode_handle()
        try:
            out = await self._decode_kv_handle.remote(meta, ref)
            return {"tokens": out["tokens"], "ttft_s": pre["ttft_s"],
                    "total_s": time.perf_counter() - t_start,
                    "disagg": True}
        except Exception:  # noqa: BLE001 - decode pool failed
            self._pd_fallbacks += 1
            del ref            # free the orphaned KV object
            try:
                out = await handle.remote({**request, "disagg": False})
                out["pd_fallback"] = "full_reprefill"
                return out
            except Exception:  # noqa: BLE001 - decode pool gone
                return await self._local_generate(request, t_start,
                                                  "local")

    def kv_decode(self, meta: dict, kv_ref) -> dict:
        """Decode-pool entry point: pull the migrated KV object (the
        ref arrives nested in the request args, so the pull happens
        HERE — same-host via the direct-shm/arena-view path, cross-node
        via chunked streaming), import it into this engine's pool, and
        run the decode phase to completion."""
        import ray_tpu
        from ray_tpu.object_ref import ObjectRef

        t0 = time.perf_counter()
        with tracing.span("serve.kv_pull") as sp:
            blob = kv_ref
            if isinstance(blob, ObjectRef):
                blob = ray_tpu.get(blob)
            blob = np.asarray(blob)
            sp["bytes"] = blob.nbytes
        pull_ms = (time.perf_counter() - t0) * 1000.0
        fut = self.engine.kv_import(
            meta["prompt"], meta["tokens"], blob,
            kv_len=meta["kv_len"],
            max_new_tokens=meta.get("max_new_tokens", 32),
            temperature=meta.get("temperature", 0.0),
            eos_id=meta.get("eos_id"),
            sample_seed=meta.get("sample_seed", 0))
        with self._pd_lock:
            self._kv_pull_bytes += blob.nbytes
            self._kv_pull_ms += pull_ms
        del blob, kv_ref       # the engine holds the view until scatter
        out = fut.result()
        out["migrated"] = True
        return out

    def update_weights(self, refs, version: int | None = None) -> int:
        """Replica-side weight push (online RLHF): stage a fresh param
        tree on this replica's engine — decode keeps running; the swap
        lands between sync windows.  `refs` resolves exactly as
        LLMEngine.update_weights documents (tree / ObjectRef / list of
        refs).  Returns the staged (or, kill-switched, current)
        version."""
        v = self.engine.update_weights(refs, version)
        if self._prefix_client is not None:
            # Cached KV belongs to the policy that computed it — the
            # engine flushes tier 1; tier 2 invalidates here (lookup's
            # version filter already refuses stale entries, this
            # reclaims their arena bytes too).
            try:
                self._prefix_client.invalidate(v)
            except Exception:  # noqa: BLE001 - store is best-effort
                pass
        return v

    def kv_check(self) -> dict:
        """Assert the engine's block-state partition (test/ops probe):
        raises if any block is leaked or double-booked.  Also reports
        the tier-2 prefix objects this replica still owns, and — after
        shutdown — asserts that count is ZERO (demoted subtrees must
        be freed when the app is deleted)."""
        out = self.engine.kv_check()
        if self._prefix_client is not None:
            n = self._prefix_client.object_count()
            out["prefix_store_objects"] = n
            if self._closed and n:
                raise AssertionError(
                    f"{n} tier-2 prefix arena objects leaked after "
                    "shutdown (demoted subtrees must die with the app)")
        return out

    async def __call__(self, request: dict) -> dict:
        import asyncio

        # Degradation ladder: under sustained overload (level >= 1)
        # disaggregation SHEDS to unified serving on this replica —
        # same engine, same seed, token-identical output, minus the
        # migration round trips the overloaded pool can't afford.
        level = self._update_pressure()
        model_id = self._request_model_id(request)
        attempts = self._LORA_ADMIT_RETRIES if model_id is not None else 1
        for attempt in range(attempts):
            if model_id is not None:
                # Adapter page-in BEFORE the graft lookup: the radix /
                # store keys are salted per (adapter, version), and the
                # salt is only known once the directory's version is.
                await asyncio.get_running_loop().run_in_executor(
                    None, self._ensure_adapter_sync, model_id,
                    tracing.current())
            if level < 1 and attempt == 0:
                # Overloaded replicas (level >= 1) skip the store
                # entirely: a migration's extra bytes/RTs are exactly
                # what a drowning pool can't afford — the
                # degradation-ladder discipline.
                await self._maybe_graft_async(request)
            # Adapter requests serve unified: the KV export/import leg
            # would also have to ship adapter identity and the decode
            # pool re-page the weights — cost without benefit at LoRA
            # sizes.
            if level < 1 and model_id is None and self._disagg(request):
                return await self._prefill_decode(request)
            fut = self.engine.submit(
                request["prompt"],
                max_new_tokens=request.get("max_new_tokens", 32),
                temperature=request.get("temperature", 0.0),
                eos_id=request.get("eos_id"),
                model_id=model_id)
            try:
                return await asyncio.wrap_future(fut)
            except AdapterLoadError as e:
                if e.reason != "not_resident" or attempt >= attempts - 1:
                    raise
                # Evicted between page-in and admission by a concurrent
                # tenant's load (slots thrash when adapters >> slots).
                # The request held no blocks or lanes yet — admission
                # failed before any — so re-page and resubmit.
                self._lora_seen.pop(model_id, None)
                self.adapter_admit_retries += 1

    def stream(self, request: dict):
        """Token-streaming generator: yields each token id as the engine
        decodes it.  Consumed via handle.options(stream=True).remote(...)
        or the HTTP proxy's chunked path (x-serve-stream: 1)."""
        if isinstance(request, dict) and "prompt" not in request:
            request = request.get("body") or request
        # The ladder must track streaming traffic too: without this a
        # streaming-only workload could neither enter overload nor
        # restore a previously-shrunk sync window.
        level = self._update_pressure()
        model_id = self._request_model_id(request)
        attempts = self._LORA_ADMIT_RETRIES if model_id is not None else 1
        for attempt in range(attempts):
            if model_id is not None:
                # stream() runs on a pool thread — blocking is fine.
                self._ensure_adapter_sync(model_id, tracing.current())
            if level < 1 and attempt == 0:
                self._maybe_graft_sync(request)
            q: queue.Queue = queue.Queue()
            fut = self.engine.submit(
                request["prompt"],
                max_new_tokens=request.get("max_new_tokens", 32),
                temperature=request.get("temperature", 0.0),
                eos_id=request.get("eos_id"),
                token_queue=q,
                model_id=model_id)
            while True:
                tok = q.get()
                if tok is None:
                    break
                yield tok
            # The None sentinel is emitted just BEFORE the future
            # resolves; wait briefly so an engine failure can't silently
            # truncate the stream as a clean-looking completion.
            try:
                exc = fut.exception(timeout=5.0)
            except concurrent.futures.TimeoutError:
                exc = None
            if exc is None:
                return
            if (isinstance(exc, AdapterLoadError)
                    and exc.reason == "not_resident"
                    and attempt < attempts - 1):
                # Admission-time eviction race (see __call__): nothing
                # was streamed — admission precedes the first token —
                # so a re-paged resubmit is transparent to the consumer.
                self._lora_seen.pop(model_id, None)
                self.adapter_admit_retries += 1
                continue
            raise exc

    def stats(self) -> dict:
        out = self.engine.stats()
        out["pd"] = {
            "role": self._role,
            "migrations": self._migrations,
            "fallbacks": self._pd_fallbacks,
            "kv_migrate_bytes": self._kv_migrate_bytes,
            "kv_migrate_put_ms": round(self._kv_migrate_put_ms, 3),
            "kv_pull_bytes": self._kv_pull_bytes,
            "kv_pull_ms": round(self._kv_pull_ms, 3),
        }
        out["overload"] = {
            "level": self._overload.level,
            "sheds": self._sheds,
            "restores": self._restores,
        }
        out["prefix_store"] = (self._prefix_client.stats()
                               if self._prefix_client is not None
                               else {"enabled": False})
        if "lora" in out:
            out["lora"]["load_errors"] = self.adapter_load_errors
            out["lora"]["admit_retries"] = self.adapter_admit_retries
        return out

    def reconfigure(self, user_config: dict) -> None:
        """Apply engine knobs from a declarative config without a code
        change (serve/schema.py engine_config or user_config; the same
        key set, including the operator-facing `kv_blocks` name).
        Knobs that reshape device memory rebuild the engine; the old
        engine's thread is stopped FIRST (deterministic teardown, not
        GC) and any requests it still held fail with a clear error —
        the controller applies config-only changes without draining, so
        a silent stop would hang those futures forever."""
        if not user_config:
            return
        from ray_tpu.serve.schema import ENGINE_CONFIG_KEYS

        allowed = ENGINE_CONFIG_KEYS | {"kv_pages", "paged"}
        unknown = set(user_config) - allowed
        if unknown:
            raise ValueError(
                f"unknown engine_config keys {sorted(unknown)}; "
                f"valid: {sorted(allowed)}")
        cfg = dict(user_config)
        ps_given = cfg.pop("prefix_store", None)
        # Pool-role knobs live on the SERVER, not the engine: applying
        # them never costs an engine rebuild.  Validate the WHOLE new
        # configuration before mutating anything — a rejected
        # reconfigure must leave the server exactly as it was.
        new_role = cfg.pop("role", None) or self._role
        dd_given = cfg.pop("decode_deployment", None)
        new_dd = self._decode_dep if dd_given is None else dd_given
        if new_role != "prefill" and dd_given is None:
            # Moving away from prefill sheds an inherited decode
            # target (there is no explicit clear syntax); an EXPLICIT
            # target with a non-prefill role is still rejected below.
            new_dd = None
        _check_pool_role(new_role, new_dd)
        if "kv_blocks" in cfg:
            cfg["kv_pages"] = cfg.pop("kv_blocks")
        kwargs = {**self._engine_kwargs, **cfg}
        _check_paged(kwargs["paged"])

        def commit_roles():
            self._role = new_role
            if new_dd is not self._decode_dep:
                self._decode_dep = new_dd
                self._decode_handle = None
                self._decode_kv_handle = None

        if kwargs == self._engine_kwargs:
            commit_roles()
            if ps_given is not None:
                self._prefix_store_cfg = dict(ps_given)
                self._install_prefix_store()
            return
        old = self.engine
        old.stop()
        old.abort_pending(RuntimeError(
            "LLM engine rebuilt by reconfigure; resubmit the request"))
        self._engine_kwargs = kwargs
        # Role/handle state commits only once the rebuild succeeded: a
        # constructor failure must not leave a half-applied role on top
        # of the (unavoidably) stopped engine.
        self.engine = LLMEngine(self._cfg, self._params, **kwargs)
        # The fresh engine's banks are empty: drop the residency TTL
        # cache so the next adapter request re-pages rather than
        # trusting a stale "resident" answer.
        self._lora_seen.clear()
        commit_roles()
        if ps_given is not None:
            self._prefix_store_cfg = dict(ps_given)
        # The rebuilt engine needs the demotion hook re-attached (and
        # the old engine's published entries withdrawn — their KV may
        # no longer match the new memory shape).
        self._install_prefix_store()
        self.engine.start()
        if self._warmup:
            self.engine.warmup()

    def shutdown(self) -> None:
        """Explicit close hook: Replica.prepare_for_shutdown calls this
        on teardown/drain (serve reconfigure, rolling update, app
        delete), so the engine thread stops deterministically instead
        of at GC time.  Replica drain waits out in-flight requests
        first; anything still queued fails instead of hanging."""
        self.engine.stop()
        self.engine.abort_pending(
            RuntimeError("LLM engine shut down with the replica"))
        # AFTER engine.stop(): the export thread drains in-flight
        # demotions first, so a publish can't race the withdraw and
        # strand an arena object past app delete.
        self._closed = True
        if self._prefix_client is not None:
            try:
                self._prefix_client.close()
            except Exception:  # noqa: BLE001 - controller already gone
                pass

    def __del__(self):
        # GC backstop only — the deterministic path is shutdown().
        try:
            self.engine.stop()
        except Exception:  # noqa: BLE001
            pass
