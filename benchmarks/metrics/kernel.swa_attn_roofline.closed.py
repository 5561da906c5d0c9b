"""Per-layer metric `kernel.swa_attn_roofline.closed`: window-layer decode-attention kernel `swa_attn` in the decode program: least time by the chip s peaks to read each LIVE row of a lane s ring once and score and weigh it for every head (slots the window has left, and lanes that hold no request, are not counted) over the kernel s measured self time, closed-loop cells of a model with window layers kept as a ring a lane. The rows come from the `swa_rows_attended` attribute of the engine s `llm.loop.decode_dispatch` spans (lengths the host holds)."""
import json

from benchmarks.harness import readers, spec, trace_reduce

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def rows_a_call(run, layers: int) -> float | None:
    """Mean rows one `swa_attn` call (a window layer of a step, all its
    live lanes) attends in the traced stretch."""
    tw = readers.trace_wall(run)
    if tw is None:
        return None
    rows = calls = 0
    for s in run["spans"]:
        a = s["attrs"]
        if (s["name"] == "llm.loop.decode_dispatch"
                and tw[0] <= s["t0"] < tw[1] and "swa_rows_attended" in a):
            rows += int(a["swa_rows_attended"])
            calls += int(a["steps"]) * layers
    return rows / calls if calls else None


def read(run):
    red = readers.traced(run)
    fam = spec.family_of(run["cell"])
    if red is None or not hasattr(fam, "swa_attn_cost"):
        return None
    calls, _ = trace_reduce.op_time(red, readers.DECODE_PROGRAM,
                                    readers.kernel_op("swa_attn"))
    layers = readers.kernel_layers(run, "swa_attn")
    rows = rows_a_call(run, layers) if calls and layers else None
    if not calls or not rows:
        return None
    print(json.dumps({"step": "swa_attn_calls", "traced_calls": calls,
                      "rows_a_call": rows}), flush=True)
    fl, by = fam.swa_attn_cost(run["model"], calls * rows)
    return readers.roofline_pct(run, readers.DECODE_PROGRAM, ("swa_attn",),
                                fl, by, "kernel.swa_attn_roofline")
