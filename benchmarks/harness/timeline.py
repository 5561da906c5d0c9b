"""Arithmetic of the readers that read what the engine thread records
about itself (PR 25): the `llm.loop.<phase>` spans of `run["spans"]`,
the `loop` counters of `run["stats"]`, and the kernels' own names in
the reduced trace.

The phases (`serve/llm.py:_LOOP_PHASES`) partition the engine thread's
time: admit, prefill_dispatch, prefill_sync, fund, decode_dispatch,
decode_sync, deliver, idle.  Every span carries `iter`, the loop's
iteration counter, except `idle`, which runs over many iterations.  A
program without them (the parent of PR 25) gives every reader here
nothing to read: None, never an exception.
"""
from __future__ import annotations

import json

from . import flops, readers, stats

PREFIX = "llm.loop."
# phases in which the device can only wait for the host; the two `_sync`
# phases are the host waiting for the device, `idle` is nobody waiting
HOST_PHASES = ("admit", "prefill_dispatch", "fund", "decode_dispatch",
               "deliver")


def _log(**kv) -> None:
    print(json.dumps(kv), flush=True)


def phase_spans(spans: list[dict]) -> list[dict]:
    """The `llm.loop.*` spans, by start, each with its `phase`."""
    out = [dict(s, phase=s["name"][len(PREFIX):]) for s in spans
           if s["name"].startswith(PREFIX)]
    return sorted(out, key=lambda s: s["t0"])


def partition(spans: list[dict]) -> dict | None:
    """How well the phases partition the thread: seconds two of them
    overlap, seconds between the first and the last no phase covers."""
    ph = phase_spans(spans)
    if len(ph) < 2:
        return None
    overlap = uncovered = 0.0
    end = ph[0]["t1"]
    for s in ph[1:]:
        if s["t0"] < end:
            overlap += min(end, s["t1"]) - s["t0"]
        else:
            uncovered += s["t0"] - end
        end = max(end, s["t1"])
    return {"overlap_s": overlap, "uncovered_s": uncovered,
            "stretch_s": end - ph[0]["t0"], "spans": len(ph)}


def host_ms_per_window(run: dict) -> float | None:
    """Host time a decode window costs the device: per loop iteration
    that dispatched a decode window inside the measured window, the
    summed duration of its HOST_PHASES; the median over iterations."""
    by_iter: dict = {}
    for s in phase_spans(readers.in_window(run)):
        it = s["attrs"].get("iter")
        if it is not None:
            by_iter.setdefault(it, []).append(s)
    per = [sum((s["t1"] - s["t0"]) * 1e3 for s in ss
               if s["phase"] in HOST_PHASES)
           for ss in by_iter.values()
           if any(s["phase"] == "decode_dispatch" for s in ss)]
    return stats.median(per) if len(per) >= 10 else None


def loop_delta(run: dict, key: str) -> float | None:
    """A `loop` counter's increase over the measured window."""
    s0, s1 = run.get("stats") or ({}, {})
    if "loop" not in s0 or "loop" not in s1:
        return None
    return s1["loop"][key] - s0["loop"][key]


def ratio(run: dict, num: str, den: str) -> float | None:
    n, d = loop_delta(run, num), loop_delta(run, den)
    return n / d if n is not None and d else None


def prefill_pad_factor(run: dict) -> float | None:
    """Token positions the prefill programs computed per true prompt
    token (width bucket x length bucket over what the prompts held)."""
    return ratio(run, "prefill_padded_tokens", "prefill_true_tokens")


def lanes_live(run: dict) -> float | None:
    """Mean lanes holding a request per decode step."""
    return ratio(run, "lane_steps_live", "decode_steps")


def gaps_by_phase(run: dict) -> list[dict] | None:
    """For each of the reduced trace's longest idle gaps of the chip
    (at most 40), the seconds of it each phase of the engine thread
    covers: [{"gap_s", "by_phase": {phase: s}, "uncovered_s"}], longest
    first.  Gaps lie on the wall clock through `start_wall_s`."""
    red = readers.traced(run)
    ph = phase_spans(run.get("spans") or [])
    if red is None or red.get("start_wall_s") is None or not ph:
        return None
    base = red["start_wall_s"]
    out = []
    for dur, s, e in red["devices"][0]["gaps"]:
        w0, w1 = base + s, base + e
        by: dict = {}
        for p in ph:
            if p["t0"] >= w1:
                break
            cov = min(w1, p["t1"]) - max(w0, p["t0"])
            if cov > 0:
                by[p["phase"]] = by.get(p["phase"], 0.0) + cov
        out.append({"gap_s": dur, "by_phase": by,
                    "uncovered_s": max(0.0, dur - sum(by.values()))})
    return out


def device_idle_with_work_pct(run: dict) -> float | None:
    """Of the traced stretch, the share in which the chip is idle AND
    the engine thread is in a phase other than `idle`: the chip waiting
    for the host.  Earlier lines give the share of all idle time the
    gaps hold, its split by phase, the ten longest gaps, and how well
    the phases partition the thread."""
    gaps = gaps_by_phase(run)
    red = readers.traced(run)
    if not gaps or not red["window_s"]:
        return None
    by: dict = {}
    for g in gaps:
        for k, v in g["by_phase"].items():
            by[k] = by.get(k, 0.0) + v
    in_gaps = sum(g["gap_s"] for g in gaps)
    idle_s = red["window_s"] - red["devices"][0]["busy_s"]
    _log(step="idle_by_phase", gaps=len(gaps), gaps_s=in_gaps,
         idle_s=idle_s, window_s=red["window_s"],
         share_of_idle_in_gaps=in_gaps / idle_s if idle_s > 0 else None,
         by_phase_s=by, uncovered_s=sum(g["uncovered_s"] for g in gaps),
         longest_ms=[[g["gap_s"] * 1e3,
                      {k: v * 1e3 for k, v in g["by_phase"].items()},
                      g["uncovered_s"] * 1e3] for g in gaps[:10]])
    tw = readers.trace_wall(run)
    _log(step="loop_partition",
         traced=partition([s for s in run["spans"]
                           if tw[0] <= s["t0"] and s["t1"] <= tw[1]]),
         window=partition(readers.in_window(run)))
    with_work = sum(v for k, v in by.items() if k != "idle")
    return 100.0 * with_work / red["window_s"]


def flash_bwd_only_roofline(run: dict) -> float | None:
    """Train step: least time for the flash BACKWARD's work alone over
    the self time of the two backward kernels, told by their names."""
    rec = run.get("rec") or {}
    if not rec.get("trace_steps"):
        return None
    tr = run["cell"].config["train"]
    f_b, b_b = flops.flash_bwd_cost(run["model"], tr["batch"], tr["seq"])
    # per chip: batch over fsdp, heads over tensor
    n = rec["trace_steps"] / run["cell"].chips \
        * readers.kernel_layers(run, "flash_bwd_dq")
    return readers.roofline_pct(
        run, readers.TRAIN_PROGRAM, ("flash_bwd_dq", "flash_bwd_dkv"),
        f_b * n, b_b * n, "kernel.flash_bwd_only_roofline")
