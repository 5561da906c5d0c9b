"""Per-layer metric `kernel.dsa_attn_roofline.closed`: sparse latent decode-attention kernel `dsa_attn` in the decode program: least time by the chip s peaks to read each SELECTED latent row once and score and weigh it for every head (rows gathered but masked, and lanes that hold no request, are not counted) over the kernel s measured self time, closed-loop cells of a model with learned sparse attention. The rows come from the `dsa_rows_selected` attribute of the engine s `llm.loop.decode_dispatch` spans (lengths the host holds)."""
import json

from benchmarks.harness import readers, spec, trace_reduce

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def rows_a_call(run, layers: int) -> float | None:
    """Mean rows one `dsa_attn` call (a sparse layer of a step, all its
    live lanes) attends in the traced stretch."""
    tw = readers.trace_wall(run)
    if tw is None:
        return None
    rows = calls = 0
    for s in run["spans"]:
        a = s["attrs"]
        if (s["name"] == "llm.loop.decode_dispatch"
                and tw[0] <= s["t0"] < tw[1] and "dsa_rows_selected" in a):
            rows += int(a["dsa_rows_selected"])
            calls += int(a["steps"]) * layers
    return rows / calls if calls else None


def read(run):
    red = readers.traced(run)
    fam = spec.family_of(run["cell"])
    if red is None or not hasattr(fam, "dsa_attn_cost"):
        return None
    calls, _ = trace_reduce.op_time(red, readers.DECODE_PROGRAM,
                                    readers.kernel_op("dsa_attn"))
    layers = readers.kernel_layers(run, "dsa_attn")
    rows = rows_a_call(run, layers) if calls and layers else None
    if not calls or not rows:
        return None
    print(json.dumps({"step": "dsa_attn_calls", "traced_calls": calls,
                      "rows_a_call": rows}), flush=True)
    fl, by = fam.dsa_attn_cost(run["model"], calls * rows)
    return readers.roofline_pct(run, readers.DECODE_PROGRAM, ("dsa_attn",),
                                fl, by, "kernel.dsa_attn_roofline")
