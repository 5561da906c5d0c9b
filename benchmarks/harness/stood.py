"""Arithmetic of the readers that ask whether the engine thread RAN
where its timeline says it was, who ran beside it, and what was built
(PR 36).  `timeline.py` reads where the thread was; this file reads
what `LLMEngine._phase`, `stats()` and the program-build listener
record beside that.

The record keys read here, all the program's own (`serve/llm.py`):

- span attr `cpu_ms` on every `llm.loop.<phase>` span of `run["spans"]`:
  the engine thread's CPU time in the phase (`time.thread_time`).  A
  phase's STOOD time is its wall time less that: the thread was off the
  processor (waiting for the GIL, a lock, a blocking call).  A kernel
  may keep a thread's CPU time by the timer tick (the chip machine's
  moves in steps of 10 ms, so one span reads 0 or 10): only sums over
  many phases are read here, and the metric is a mean.
- `run["stats"][i]["loop"]["phase_cpu_s"]`: the same, cumulative, by
  phase, beside `phase_s` (printed, no metric of its own).
- `run["stats"][i]["threads"]`: `{"wall_s", "process_cpu_s", "by_name":
  {row: cpu_s}}`, the CPU ledger of the replica process's Python
  threads at the reading, by a fixed set of rows (a pool's prefix, or
  `other`).  Printed (`thread_cpu`), and NO metric: the harness takes
  its second reading after the profiler's `stop_trace` has run on a
  `serve-call` thread of the same process, so in the only runs that
  compute per-layer metrics the rivals' CPU is the profiler's.  A metric
  of it needs `serve_cell.py` to read `stats` at the measured window's
  two ends (ROADMAP B9).
- `run["stats"][i]["loop"]["program_builds"]`, `["program_build_s"]`,
  `["program_cache_misses"]`: compile stages, the seconds of every
  outermost trace / lower / compile-or-load stage, and compile stages
  the persistent cache missed, process-wide since the replica's engine
  was constructed.
- spans `llm.program_build` (`fun`, `stage` = trace | lower | compile,
  `depth` (0 = outermost: only those add to `program_build_s`),
  `cache` = hit | miss | off on the compile stage, `thread`), with
  JAX's own start and end on the recorder's clock.

`run["stats"]` are read before the ramp and after the drain: "the
window" of the counter metrics is that stretch, a few seconds longer
than the measured one, and `threads["wall_s"]` times it.  A
program without these records (the parent of PR 36) gives every reader
here nothing to read: None, never an exception.
"""
from __future__ import annotations

import json

from . import readers, stats, timeline

ENGINE_THREAD = "llm-engine"
BUILD_SPAN = "llm.program_build"
BUILD_STAGES = ("trace", "lower", "compile")
SYNC_PHASES = ("prefill_sync", "decode_sync")


def _log(**kv) -> None:
    print(json.dumps(kv), flush=True)


def stood_ms_per_window(run: dict) -> float | None:
    """Of the host time a decode window costs (`host_ms_per_window`'s
    iterations and phases), the milliseconds the engine thread did NOT
    run: over those iterations, the host phases' summed wall time less
    their summed `cpu_ms`, a window.  The MEAN, where its sibling is a
    median: a thread clock that ticks gives sums a meaning and single
    spans none.  Earlier lines give the mean wall / ran / stood ms by
    phase with the wall's p50, p90 and max, the CPU of the two `_sync`
    phases (a wait that burns CPU is a busy wait), and the thread
    ledger (`thread_cpu`)."""
    by_iter: dict = {}
    for s in timeline.phase_spans(readers.in_window(run)):
        it = s["attrs"].get("iter")
        if it is not None:
            by_iter.setdefault(it, []).append(s)
    iters = [ss for ss in by_iter.values()
             if any(s["phase"] == "decode_dispatch" for s in ss)]
    if len(iters) < 10 or not all(
            "cpu_ms" in s["attrs"] for ss in iters for s in ss):
        return None
    n = len(iters)
    wall = {p: [] for p in timeline.HOST_PHASES}
    cpu = {p: 0.0 for p in timeline.HOST_PHASES + SYNC_PHASES}
    for ss in iters:
        for s in ss:
            if s["phase"] in cpu:
                cpu[s["phase"]] += s["attrs"]["cpu_ms"]
            if s["phase"] in wall:
                wall[s["phase"]].append((s["t1"] - s["t0"]) * 1e3)
    by_phase = {p: {"wall": sum(wall[p]) / n, "ran": cpu[p] / n,
                    "stood": (sum(wall[p]) - cpu[p]) / n,
                    "wall_p50_p90_max": [stats.median(wall[p]),
                                         stats.percentile(wall[p], 90),
                                         max(wall[p])]}
                for p in timeline.HOST_PHASES if wall[p]}
    _log(step="stood_by_phase", iterations=n,
         mean_ms={k: sum(r[k] for r in by_phase.values())
                  for k in ("wall", "ran", "stood")},
         by_phase_mean_ms=by_phase,
         sync_cpu_mean_ms={p: cpu[p] / n for p in SYNC_PHASES})
    _log_thread_cpu(run)
    return max(0.0, sum(r["stood"] for r in by_phase.values()))


def _ledgers(run: dict) -> tuple | None:
    s0, s1 = run.get("stats") or ({}, {})
    t0, t1 = s0.get("threads"), s1.get("threads")
    if not t0 or not t1 or t1["wall_s"] <= t0["wall_s"]:
        return None
    return t0, t1


def _log_thread_cpu(run: dict) -> None:
    """The line `thread_cpu`: CPU the rows of the replica's thread
    ledger gained between the two readings of `stats`, the five that
    gained most, the engine thread's own with its phases' CPU and wall
    seconds, the process's CPU no live Python thread accounts for (the
    XLA runtime, transfers, threads that ended), and
    `rivals_pct_of_a_core`: every row but the engine's over the wall
    seconds between the readings, an upper bound on who could have held
    the GIL (native code that released it counts).  `holds_profiler`
    says that a device trace was taken and stopped between the two
    readings: the rivals then hold `stop_trace`'s 25-45 s of CPU on a
    `serve-call` thread and say what tracing costs, not what serving
    does, which is why no metric is made of this line."""
    led = _ledgers(run)
    if led is None:
        return
    t0, t1 = led
    wall = t1["wall_s"] - t0["wall_s"]
    delta = {n: c - t0["by_name"].get(n, 0.0)
             for n, c in t1["by_name"].items()}
    rivals = {n: d for n, d in delta.items() if n != ENGINE_THREAD}
    proc = t1["process_cpu_s"] - t0["process_cpu_s"]
    s0, s1 = run["stats"]
    cpu0, cpu1 = (s["loop"].get("phase_cpu_s", {}) for s in (s0, s1))
    wall0, wall1 = (s["loop"].get("phase_s", {}) for s in (s0, s1))
    tw = readers.trace_wall(run)
    _log(step="thread_cpu", wall_s=wall,
         holds_profiler=bool(
             tw and t0["wall_s"] < tw[1] and tw[0] < t1["wall_s"]),
         rivals_pct_of_a_core=100.0 * sum(rivals.values()) / wall,
         top5_cpu_s=sorted(rivals.items(), key=lambda kv: -kv[1])[:5],
         engine_cpu_s=delta.get(ENGINE_THREAD),
         process_cpu_s=proc, unaccounted_cpu_s=proc - sum(delta.values()),
         engine_phase_cpu_s={k: v - cpu0.get(k, 0.0)
                             for k, v in cpu1.items()},
         engine_phase_s={k: v - wall0.get(k, 0.0)
                         for k, v in wall1.items()})


def _loop_delta(run: dict, key: str) -> float | None:
    """As `timeline.loop_delta`, for a key a parent's `loop` lacks."""
    s0, s1 = run.get("stats") or ({}, {})
    if key not in s0.get("loop", {}) or key not in s1.get("loop", {}):
        return None
    return s1["loop"][key] - s0["loop"][key]


def _build_spans(run: dict, t_lo: float, t_hi: float) -> list[dict]:
    return sorted((s for s in run.get("spans") or []
                   if s["name"] == BUILD_SPAN and t_lo <= s["t0"] < t_hi),
                  key=lambda s: s["t0"])


def program_build_ms_in_window(run: dict) -> float | None:
    """Milliseconds the replica spent tracing, lowering and compiling
    (or loading) programs between the two readings of `stats`: 0 in a
    sound run, since the warm-up ran every shape.  An earlier line names
    every `llm.program_build` span of that stretch."""
    d, led = _loop_delta(run, "program_build_s"), _ledgers(run)
    if d is None or led is None:
        return None
    w0, w1 = led[0]["wall_s"], led[1]["wall_s"]
    _log(step="builds_in_window", program_build_s=d,
         program_builds=_loop_delta(run, "program_builds"),
         program_cache_misses=_loop_delta(run, "program_cache_misses"),
         spans=[[s["attrs"].get("fun"), s["attrs"].get("stage"),
                 s["attrs"].get("cache"), (s["t1"] - s["t0"]) * 1e3,
                 s["attrs"].get("thread"), s["attrs"].get("depth")]
                for s in _build_spans(run, w0, w1)])
    return d * 1e3


def setup_program_build_s(run: dict) -> float | None:
    """Seconds the replica spent in the three stages of every program it
    built from its engine's construction to the window's opening (the
    first reading of `stats`): the engine's own programs and the whole
    warm-up.  The harness's jitted `init_params` ran before the engine
    was made and stays `bench_probe`'s `init_params_s`.  An earlier line
    sets it beside the harness's own timings of the same stretch and
    gives the builds by stage, by cache outcome, and the programs that
    took longest."""
    s0 = (run.get("stats") or ({},))[0]
    total = s0.get("loop", {}).get("program_build_s")
    if total is None or "threads" not in s0:
        return None
    spans = _build_spans(run, 0.0, s0["threads"]["wall_s"])
    by_stage = {st: [0, 0.0] for st in BUILD_STAGES}
    cache = {"hit": [0, 0.0], "miss": [0, 0.0], "off": [0, 0.0]}
    by_fun: dict = {}
    for s in spans:
        a, dur = s["attrs"], s["t1"] - s["t0"]
        if a.get("stage") == "compile":
            c = cache.setdefault(a.get("cache", "off"), [0, 0.0])
            c[0], c[1] = c[0] + 1, c[1] + dur
        if a.get("depth", 0):
            continue        # inside another stage: its seconds are there
        st = by_stage.setdefault(a.get("stage"), [0, 0.0])
        st[0], st[1] = st[0] + 1, st[1] + dur
        f = by_fun.setdefault(a.get("fun"), {})
        f[a.get("stage")] = f.get(a.get("stage"), 0.0) + dur
    setup, dev = run.get("setup", {}), run.get("device", {})
    _log(step="setup_builds", program_build_s=total,
         program_builds=s0["loop"].get("program_builds"),
         program_cache_misses=s0["loop"].get("program_cache_misses"),
         serve_run_s=setup.get("serve_run_s"),
         warmup_s=setup.get("warmup_s"),
         init_params_s=dev.get("init_params_s"),
         engine_init_s=dev.get("engine_init_s"),
         spans=len(spans), by_stage_n_s=by_stage, compile_by_cache_n_s=cache,
         longest_s=sorted(
             ([f, sum(st.values()), st] for f, st in by_fun.items()),
             key=lambda r: -r[1])[:12])
    return total
