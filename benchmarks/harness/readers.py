"""Arithmetic the per-layer readers share.  A reader is `metrics/<name>.py`;
it gets the run's record and returns a number or None (nothing to read).

The record (`run`): `cell`, `model`, `seconds`, `setup` (host-clock
seconds of the set-up stages), `requests` (serve: the window's requests
as timed at the client), `window_wall` (serve: the window on the wall
clock), `spans` (the engine's flight-recorder spans, wall clock), `trace`
(the reduced device trace), `stats` (serve: engine counters before and
after), `rec` (train: the loop's record), `device`, `e2e`.
"""
from __future__ import annotations

import json
import re

from . import flops, peaks, spec, stats, trace_reduce

# the programs, by the names `jax.jit` gives the engine's and the train
# step's functions: a program of any family keeps them
DECODE_PROGRAM = r"decode_k"
PREFILL_PROGRAMS = r"prefill_fwd_only|prefill_suffix|scatter"
FLASH_PREFILL_PROGRAM = r"prefill_fwd_only"
TRAIN_PROGRAM = r"jit_step"


def kernel_op(*names: str) -> str:
    """Pattern of the op events of the Pallas kernels of these names.
    `pl.pallas_call(name=...)` reaches the `XLA Ops` line as `<name>.N
    custom-call`; where the call was differentiated the name is wrapped
    (`jvp_<name>_.N`, `transpose_jvp_<name>__.N`).  A roofline reads the
    kernels it is named after: another kernel in the same program has
    another name and is not read."""
    alt = "|".join(re.escape(n) for n in names)
    return rf"(^|_)({alt})_*\.\d+ custom-call( |$)"


def in_window(run: dict, spans=None) -> list[dict]:
    w0, w1 = run["window_wall"]
    return [s for s in (run["spans"] if spans is None else spans)
            if w0 <= s["t0"] < w1]


def spans_named(run: dict, name: str) -> list[dict]:
    return [s for s in in_window(run) if s["name"] == name]


def decode_windows(run: dict) -> list[tuple[float, float, int]]:
    """(t0, t1, steps) of each decode window, once (the engine records
    one span per co-resident request: deduplicated by start stamp)."""
    seen = {}
    for s in spans_named(run, "llm.decode_window"):
        seen[s["t0"]] = (s["t0"], s["t1"], int(s["attrs"].get("steps", 0)))
    return sorted(seen.values())


def between_windows_ms_p50(run: dict) -> float | None:
    wins = decode_windows(run)
    gaps = [(b[0] - a[1]) * 1e3 for a, b in zip(wins, wins[1:])]
    return stats.median(gaps) if len(gaps) >= 10 else None


def traced(run: dict) -> dict | None:
    red = run.get("trace")
    return red if red and red.get("devices") else None


def trace_wall(run: dict) -> tuple[float, float] | None:
    red = traced(run)
    if red is None or red.get("start_wall_s") is None:
        return None
    return (red["start_wall_s"] + red["t_lo"],
            red["start_wall_s"] + red["t_hi"])


def decode_step_ms(run: dict) -> float | None:
    """Device time of the decode program per step: each program event's
    duration over its K steps, the median over the traced events."""
    red = traced(run)
    if red is None:
        return None
    d = trace_reduce.module_durations(red, DECODE_PROGRAM)
    k = run["engine"]["steps_per_sync"]
    return stats.median(d) * 1e3 / k if d else None


def prefill_spans_in_trace(run: dict) -> tuple[list[dict], float]:
    """The `llm.prefill` spans wholly inside the traced stretch, and the
    factor by which spans the stretch's edges cut outnumber them (device
    time of a cut wave is in the trace, its span is not counted)."""
    tw = trace_wall(run)
    if tw is None:
        return [], 1.0
    spans = [s for s in run["spans"] if s["name"] == "llm.prefill"]
    inside = [s for s in spans if tw[0] <= s["t0"] and s["t1"] <= tw[1]]
    touching = [s for s in spans if s["t0"] < tw[1] and s["t1"] > tw[0]]
    return inside, (len(touching) / len(inside) if inside else 1.0)


def prefill_ms_per_ktok(run: dict) -> float | None:
    red = traced(run)
    sp, cut = prefill_spans_in_trace(run)
    if red is None or not sp:
        return None
    t = sum(trace_reduce.module_durations(red, PREFILL_PROGRAMS))
    toks = sum(int(s["attrs"].get("prompt_tokens", 0))
               - int(s["attrs"].get("prefill_from", 0)) for s in sp)
    if not toks or t <= 0:
        return None
    return t * 1e3 / cut / (toks / 1000.0)


def kernel_layers(run: dict, kernel: str) -> int:
    """How many layers of the run's model call the kernel of that name:
    the family's count, never the depth."""
    return spec.family_of(run["cell"]).kernel_layers(run["model"], kernel)


def roofline_pct(run: dict, program: str, kernels: tuple, need_flops: float,
                 need_bytes: float, log_name: str) -> float | None:
    """Least time by the chip's peaks over the measured self time of the
    events of the kernels named `kernels` inside `program`, in percent."""
    red = traced(run)
    if red is None:
        return None
    n, t = trace_reduce.op_time(red, program, kernel_op(*kernels))
    if not n or t <= 0 or need_flops <= 0:
        return None
    least, bound = peaks.roofline_s(need_flops, need_bytes,
                                    run["device"]["kind"])
    print(json.dumps({"step": "roofline", "metric": log_name, "bound": bound,
                      "kernel_events": n, "kernel_s": t, "least_s": least}),
          flush=True)
    return 100.0 * least / t


def flash_fwd_roofline(run: dict) -> float | None:
    sp, cut = prefill_spans_in_trace(run)
    if not sp:
        return None
    lens = [int(s["attrs"].get("prompt_tokens", 0)) for s in sp]
    fl, by = flops.flash_fwd_cost(run["model"], lens)
    scale = kernel_layers(run, "flash_fwd") * cut
    return roofline_pct(run, FLASH_PREFILL_PROGRAM, ("flash_fwd",),
                        fl * scale, by * scale, "kernel.flash_fwd_roofline")


def paged_attn_roofline(run: dict) -> float | None:
    """Context lengths from the spans: a request's context in its i-th
    decode window is its prompt + 1 + i*K tokens at the window's start
    and grows by one a step."""
    tw = trace_wall(run)
    if tw is None:
        return None
    k = run["engine"]["steps_per_sync"]
    prompt = {s["tid"]: int(s["attrs"].get("prompt_tokens", 0))
              for s in run["spans"] if s["name"] == "llm.prefill"}
    by_req: dict = {}
    for s in run["spans"]:
        if s["name"] == "llm.decode_window":
            by_req.setdefault(s["tid"], []).append(s)
    ctx = []
    for tid, wins in by_req.items():
        if tid not in prompt:
            continue
        for i, s in enumerate(sorted(wins, key=lambda s: s["t0"])):
            if tw[0] <= s["t0"] and s["t1"] <= tw[1]:
                base = prompt[tid] + 1 + i * k
                ctx += [base + j for j in range(k)]
    if not ctx:
        return None
    fl, by = flops.paged_attn_cost(run["model"], ctx)
    layers = kernel_layers(run, "paged_attn")
    return roofline_pct(run, DECODE_PROGRAM, ("paged_attn",), fl * layers,
                        by * layers, "kernel.paged_attn_roofline")


def flash_bwd_roofline(run: dict) -> float | None:
    """Train step: the three kernel calls of an attention layer, the
    forward and the two backward kernels, read together against the work
    of all three (`kernel.flash_bwd_only_roofline` reads the backward
    alone)."""
    rec = run.get("rec") or {}
    if not rec.get("trace_steps"):
        return None
    t = run["cell"].config["train"]
    model = run["model"]
    # per chip: batch over fsdp, heads over tensor
    per_chip = rec["trace_steps"] / run["cell"].chips
    n_f = per_chip * kernel_layers(run, "flash_fwd")
    n_b = per_chip * kernel_layers(run, "flash_bwd_dq")
    f_f, b_f = flops.flash_fwd_cost(model, [t["seq"]] * t["batch"])
    f_b, b_b = flops.flash_bwd_cost(model, t["batch"], t["seq"])
    return roofline_pct(run, TRAIN_PROGRAM,
                        ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
                        f_f * n_f + f_b * n_b, b_f * n_f + b_b * n_b,
                        "kernel.flash_bwd_roofline")


def train_mfu_pct(run: dict) -> float | None:
    rec = run.get("rec")
    if not rec:
        return None
    t = run["cell"].config["train"]
    need = flops.train_flops_per_step(spec.family_of(run["cell"]),
                                      run["model"], t["batch"], t["seq"])
    per_s = need * rec["steps"] / rec["window_s"]
    peak = peaks.peaks_for(run["device"]["kind"])["bf16_flops"]
    return 100.0 * per_s / (run["cell"].chips * peak)


def collective_share_pct(run: dict) -> float | None:
    red = traced(run)
    if red is None or not red["window_s"]:
        return None
    return 100.0 * trace_reduce.collective_exposed_s(red) / red["window_s"]
