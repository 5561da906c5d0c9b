"""Arithmetic of the readers that ask whether the engine loop STALLED,
and whether the cyclic garbage collector held the interpreter (PR 50).
`stood.py` says THAT the engine thread stood; the records read here say
in which phase, for how long, and who held the interpreter or the
process meanwhile.

The record keys read here, all the program's own (`serve/llm.py`):

- `run["stats"][i]["loop"]["stalls"]`, `["stall_s"]`: stalls of the
  engine loop with work waiting and their seconds, counted by the
  engine's watcher thread (`llm-stall-watch`): a host phase open over
  0.2 s, the watcher itself woken over 0.4 s late, or a `_sync` phase
  open over 1 s and three times its longest.  A stall in `idle` is a span
  and is not counted.
- spans `llm.stall` on the engine's own trace (`phase`, `iter`,
  `trigger` = host_phase | late_wake | sync, `held` = engine |
  interpreter | process | device, `stood_ms`, `engine_cpu_ms`,
  `nth` (the value `loop.stalls` took when this one was counted; 0 in
  `idle`), `late_wake_ms`, `process_cpu_ms`, `by_thread_cpu_ms` (a JSON
  list of [ledger row, ms], the five largest), `native_cpu_ms`,
  `ledger_ms` (the stretch those rows were read over), `gc_ms`,
  `build_ms`, `engine_frames`, `faults_major`, `faults_minor`,
  `switches_involuntary`, `mem_in_use`, `mem_largest_free`, `pending`,
  `lanes`), on `time.time()` like every `llm.loop.*` span.
- `run["stats"][i]["loop"]["gc_pauses"]`, `["gc_pause_s"]`,
  `["gc_by_generation"]` (`{"0" | "1" | "2": {"pauses", "pause_s"}}`):
  every collection of the replica PROCESS since its first engine was
  made, whichever thread started it.
- spans `llm.gc_pause` (`generation`, `collected`, `uncollectable`,
  `thread` = the ledger row of the thread it ran on): the pauses of 1 ms
  and more.

"The window" of the two counter metrics is the stretch between the two
readings of `stats`, as for `engine.program_build_ms_in_window.*`.  In a
traced run that stretch holds the profiler's start and its `stop_trace`,
which run on a `serve-call` thread of the replica: a stall that lies in
either is flagged (`in_profiler`), says what tracing costs, not what
serving does, and is taken off `engine.stall_ms_in_window.*`.
A program without these records (the parent of PR 50) gives every reader
here nothing to read: None, never an exception.
"""
from __future__ import annotations

import json

from . import readers
from .stood import _ledgers, _log, _loop_delta

STALL_SPAN = "llm.stall"
GC_SPAN = "llm.gc_pause"


def _spans(run: dict, name: str, t_lo: float, t_hi: float) -> list[dict]:
    return sorted((s for s in run.get("spans") or []
                   if s["name"] == name and t_lo <= s["t0"] < t_hi),
                  key=lambda s: s["t0"])


def _covered_s(intervals: list, t0: float, t1: float) -> float:
    return sum(max(0.0, min(t1, b) - max(t0, a)) for a, b in intervals)


def _rows(value) -> list:
    """`by_thread_cpu_ms` as the program wrote it: a JSON list."""
    try:
        return json.loads(value)
    except (TypeError, ValueError):
        return []


def stall_ms_in_window(run: dict) -> float | None:
    """Milliseconds the engine loop stood in the stalls it counted
    between the two readings of `stats` (`loop.stalls`, `loop.stall_s`),
    but for the stalls that had ended before the first reading and were
    counted after it, and the stalls under the profiler's own start or
    stop.  0 in a sound run, at least 200 in a run that lost a tenth.
    Which stalls the stretch counted, the spans say (a span's `nth` is
    the value `loop.stalls` took when it was counted); what the counter
    gained beyond the spans found (a span the ring no longer holds)
    stays in the number.  An earlier line, `stalls_in_window`, gives every `llm.stall`
    span of that stretch with its attrs, whether it lies in the measured
    window and in the profiler's start or stop, and, where it overlaps
    the traced stretch, the seconds of the device's idle gaps it covers
    (the reduced trace's longest gaps, on `start_wall_s`); and what was
    taken off the number (`counted_late`, `in_profiler_ms`)."""
    d, led = _loop_delta(run, "stall_s"), _ledgers(run)
    if d is None or led is None:
        return None
    w0, w1 = led[0]["wall_s"], led[1]["wall_s"]
    n0, n1 = (s["loop"]["stalls"] for s in run["stats"])
    # counted between the readings: the watcher counts a stall at its
    # first wake after the stall ended, which a loading program delays,
    # so the warm-up's last compile may be counted after the first reading
    counted = [s for s in run.get("spans") or [] if s["name"] == STALL_SPAN
               and n0 < s["attrs"].get("nth", 0) <= n1]
    late = [s for s in counted if s["t1"] <= w0]
    red, tw = readers.traced(run), readers.trace_wall(run)
    gaps = ([(red["start_wall_s"] + s, red["start_wall_s"] + e)
             for _dur, s, e in red["devices"][0]["gaps"]] if tw else [])
    # the profiler at work: its start (the two seconds up to the traced
    # stretch) and its stop (from the stretch's end to the return of the
    # call that stopped it)
    prof = ([(tw[0] - 2.0, tw[0]),
             (tw[1], (run.get("trace_wall") or (0.0, tw[1]))[1])]
            if tw else [])
    m0, m1 = run.get("window_wall") or (w0, w1)
    rows = []
    for s in _spans(run, STALL_SPAN, w0, w1):
        row = {k: v for k, v in s["attrs"].items() if k != "by_thread_cpu_ms"}
        row.update(
            at_s=s["t0"] - w0, wall_ms=(s["t1"] - s["t0"]) * 1e3,
            by_thread_cpu_ms=_rows(s["attrs"].get("by_thread_cpu_ms")),
            in_measured_window=m0 <= s["t0"] < m1,
            in_profiler=_covered_s(prof, s["t0"], s["t1"]) > 0.0,
            device_idle_s=(_covered_s(gaps, s["t0"], s["t1"])
                           if tw and s["t0"] < tw[1] and tw[0] < s["t1"]
                           else None))
        rows.append(row)
    by_held: dict = {}
    for r in rows:
        if r.get("phase") != "idle":
            h = by_held.setdefault(r.get("held"), [0, 0.0])
            h[0], h[1] = h[0] + 1, h[1] + r.get("stood_ms", 0.0)
    def ms(spans):
        return sum(s["attrs"].get("stood_ms", 0.0) for s in spans)

    in_prof = [s for s in counted if s["t1"] > w0
               and _covered_s(prof, s["t0"], s["t1"]) > 0.0]
    _log(step="stalls_in_window", stalls=n1 - n0, stall_s=d,
         by_held_n_ms=by_held,
         counted_late=[[s["attrs"].get("phase"), s["attrs"].get("stood_ms"),
                        s["attrs"].get("build_ms"), s["t1"] - w0]
                       for s in late],
         in_profiler_ms=ms(in_prof), spans=rows)
    # the kept spans' own milliseconds, and what the counter gained
    # beyond the spans found (a span the ring no longer holds)
    kept = [s for s in counted if s["t1"] > w0 and s not in in_prof]
    lost = d * 1e3 - ms(counted) if len(counted) < n1 - n0 else 0.0
    return ms(kept) + max(0.0, lost)


def gc_pause_ms_in_window(run: dict) -> float | None:
    """Milliseconds the replica process's cyclic collector held the
    interpreter between the two readings of `stats`, every generation,
    whichever thread started the collection.  An earlier line,
    `gc_in_window`, gives the pauses and seconds by generation (the
    counters) and, from the `llm.gc_pause` spans (pauses of 1 ms and
    more), by the thread row they ran on, and the five longest."""
    d, led = _loop_delta(run, "gc_pause_s"), _ledgers(run)
    if d is None or led is None:
        return None
    g0, g1 = (s["loop"].get("gc_by_generation", {}) for s in run["stats"])
    by_gen = {g: {k: v - g0.get(g, {}).get(k, 0) for k, v in row.items()}
              for g, row in g1.items()}
    spans = _spans(run, GC_SPAN, led[0]["wall_s"], led[1]["wall_s"])
    by_thread: dict = {}
    for s in spans:
        t = by_thread.setdefault(s["attrs"].get("thread"), [0, 0.0])
        t[0], t[1] = t[0] + 1, t[1] + (s["t1"] - s["t0"])
    _log(step="gc_in_window", pauses=_loop_delta(run, "gc_pauses"),
         pause_s=d, by_generation=by_gen, spans=len(spans),
         by_thread_n_s=by_thread,
         longest_ms=[[(s["t1"] - s["t0"]) * 1e3,
                      s["attrs"].get("generation"),
                      s["attrs"].get("thread"),
                      s["attrs"].get("collected"),
                      s["t0"] - led[0]["wall_s"]]
                     for s in sorted(spans,
                                     key=lambda s: s["t0"] - s["t1"])[:5]])
    return d * 1e3
