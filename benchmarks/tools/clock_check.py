#!/usr/bin/env python3
"""Is the flight recorder's wall clock the profiler's clock?  The
benchmark sets spans (`time.time()`) beside device events through the
xplane's `profile_start_time`; this tool checks that, on the machine with
the chip, in ONE process that holds the chip itself:

    python3 benchmarks/tools/clock_check.py [--workload mistral7b.batch.closed] [--seconds 6]

It makes the cell's weights and engine (no cluster), keeps the engine's
lanes busy with a closed loop of callers at the traffic's median prompt
length, traces `--seconds` of it, and prints one JSON line:

- `offset_ms`: per `llm.loop.decode_dispatch` TraceAnnotation on the
  profiler's host plane, its start on the wall clock (profile_start_time
  + the event's start) minus the start of the flight-recorder span of the
  same `iter`: median, worst (largest magnitude), n;
- `decode_programs`: how many decode programs the device ran in the
  traced stretch, and how many of them start before their iteration's
  `decode_dispatch` phase starts or end after its `decode_sync` ends
  (`outside`; the engine syncs every window, so a program belongs
  between the two);
- `worst_outside_ms`: by how much the worst of those sticks out.

`--rehearse` walks the code on the CPU at a debug size (no device plane:
`decode_programs` reads 0) and is never a measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

DISPATCH = "llm.loop.decode_dispatch"


def host_annotations(xplane: str, name: str) -> list[tuple[int, float, float]]:
    """(iter, start_s, dur_s) of the TraceAnnotation events called `name`
    on the host planes, seconds from the start of the profile."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    it = dict(e.stats).get("iter")
                    if it is not None:
                        out.append((int(it), e.start_ns / 1e9,
                                    e.duration_ns / 1e9))
    return out


def check(xplane: str, spans: list[dict]) -> dict:
    from benchmarks.harness import readers, stats, trace_reduce

    tr = trace_reduce.load(xplane)
    base = tr["start_wall_s"]
    by_iter = {}
    for s in spans:
        if s["name"].startswith("llm.loop.") and "iter" in s["attrs"]:
            by_iter.setdefault(s["attrs"]["iter"], {})[s["name"]] = s
    offs = [(base + start - by_iter[it][DISPATCH]["t0"]) * 1e3
            for it, start, _ in host_annotations(xplane, DISPATCH)
            if DISPATCH in by_iter.get(it, {})]
    # each iteration's window on the wall clock: dispatch start .. sync end
    wins = sorted((ph[DISPATCH]["t0"], ph["llm.loop.decode_sync"]["t1"])
                  for ph in by_iter.values()
                  if DISPATCH in ph and "llm.loop.decode_sync" in ph)
    n = outside = 0
    worst = 0.0
    for dev in tr["devices"].values():
        for name, s, d, _ in dev["modules"]:
            if readers.DECODE_PROGRAM not in name:
                continue
            w0, w1 = base + s, base + s + d
            mid = 0.5 * (w0 + w1)
            win = next((w for w in wins if w[0] <= mid <= w[1]), None)
            if win is None:
                continue        # dispatched before the spans begin
            n += 1
            out = max(win[0] - w0, w1 - win[1], 0.0)
            if out > 0:
                outside += 1
                worst = max(worst, out)
    return {
        "offset_ms": {"n": len(offs),
                      "median": stats.median(offs) if offs else None,
                      "worst": max(offs, key=abs) if offs else None},
        "decode_programs": {"n": n, "outside": outside},
        "worst_outside_ms": worst * 1e3,
        "start_wall_s": base}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="mistral7b.batch.closed")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ["RAY_TPU_TRACE_BUFFER"] = str(1 << 17)

    import jax

    from benchmarks.harness import serve_cell, spec, trace_reduce
    from benchmarks.harness.replica import seed_key
    from ray_tpu import tracing
    from ray_tpu.serve.llm import LLMEngine

    cell = spec.load_cell(args.workload)
    if args.rehearse:
        serve_cell.rehearsal_cell(cell)
    eng_kw = dict(cell.config["engine"], paged=True)
    prompt_len = int(cell.traffic["prompt_len"]["median"])
    new_tokens = 4 * int(cell.traffic["output_len"]["clip"][0])
    fam = cell.family
    model = fam.published(cell.config)
    vocab = fam.vocab_size(model)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"jax came up on {dev.platform!r}, not the chip")
    lcfg = fam.program_config(model, max_seq=eng_kw["max_len"])
    params = jax.jit(lambda k: fam.init_params(k, lcfg))(
        seed_key(args.seed))
    eng = LLMEngine(lcfg, params=params, seed=0, **eng_kw)
    eng.start()
    stop = threading.Event()

    def caller(i: int) -> None:
        n = 0
        while not stop.is_set():
            prompt = [1 + (i * 131 + n * 17 + j) % (vocab - 1)
                      for j in range(prompt_len)]
            eng.generate(prompt, max_new_tokens=new_tokens, _cache_ok=False)
            n += 1

    # the width buckets a closed loop of callers can reach, then decode
    for w in eng._width_buckets:
        eng.stop()
        futs = [eng.submit([1 + (i + j) % 97 for j in range(prompt_len)],
                           max_new_tokens=eng.steps_per_sync + 1,
                           _cache_ok=False) for i in range(w)]
        eng.start()
        for f in futs:
            f.result(timeout=900.0)
    threads = [threading.Thread(target=caller, args=(i,), daemon=True)
               for i in range(eng.max_batch + eng.max_batch // 2)]
    for th in threads:
        th.start()
    time.sleep(3.0)
    trace_dir = os.path.join(ROOT, ".bench_trace", "clock_check")
    tracing.clear()
    trace_reduce.start(trace_dir)
    time.sleep(args.seconds)
    jax.profiler.stop_trace()
    spans = [r for r in tracing.snapshot() if r["name"].startswith("llm.")]
    stop.set()
    for th in threads:
        th.join(timeout=120.0)
    eng.stop()
    out = check(trace_reduce.find_xplane(trace_dir), spans)
    print(json.dumps({"tool": "clock_check", "workload": args.workload,
                      "rehearse": args.rehearse, "platform": dev.platform,
                      "kind": dev.device_kind, "seconds": args.seconds,
                      **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
