"""Per-layer metric `kernel.bsa_attn_roofline.closed`: block-sparse decode-attention kernel `bsa_attn` in the decode program: least time by the chip s peaks to read K and V of each row a selection NEEDS once and score and weigh it for every query head (q in, o out a lane-step; rows of pages walked but not chosen, and lanes that hold no request, are not counted) over the kernel s measured self time, closed-loop cells of a model whose attention selects its own key blocks. The rows come from the `bsa_rows_attended` attribute of the engine s `llm.loop.decode_dispatch` spans (lengths the host holds)."""
import json

from benchmarks.harness import readers, spec, trace_reduce

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def work_a_call(run, layers: int):
    """Mean (rows attended, live lanes) of one `bsa_attn` call (a sparse
    layer of a step, all its live lanes) in the traced stretch."""
    tw = readers.trace_wall(run)
    if tw is None:
        return None
    rows = lanes = calls = 0
    for s in run["spans"]:
        a = s["attrs"]
        if (s["name"] == "llm.loop.decode_dispatch"
                and tw[0] <= s["t0"] < tw[1] and "bsa_rows_attended" in a):
            rows += int(a["bsa_rows_attended"])
            lanes += int(a["lanes"]) * int(a["steps"]) * layers
            calls += int(a["steps"]) * layers
    return (rows / calls, lanes / calls) if calls else None


def read(run):
    red = readers.traced(run)
    fam = spec.family_of(run["cell"])
    if red is None or not hasattr(fam, "bsa_attn_cost"):
        return None
    calls, _ = trace_reduce.op_time(red, readers.DECODE_PROGRAM,
                                    readers.kernel_op("bsa_attn"))
    layers = readers.kernel_layers(run, "bsa_attn")
    work = work_a_call(run, layers) if calls and layers else None
    if not calls or not work or not work[0]:
        return None
    rows, lanes = work
    print(json.dumps({"step": "bsa_attn_calls", "traced_calls": calls,
                      "rows_a_call": rows, "lanes_a_call": lanes}),
          flush=True)
    fl, by = fam.bsa_attn_cost(run["model"], calls * rows, calls * lanes)
    return readers.roofline_pct(run, readers.DECODE_PROGRAM, ("bsa_attn",),
                                fl, by, "kernel.bsa_attn_roofline")
