"""Per-layer metric `kernel.kda_update_roofline.closed`: one-step gated delta-rule kernel `kda_update` in the decode program: least time by the chip s peaks for the work its calls NEED (each LIVE lane s state matrices read and written once a layer-step, its a, k, q, v, beta in and o out, the update s operations; a lane that holds no request is not counted) over the kernel s measured self time, closed-loop cells of a KDA model."""
import json

from benchmarks.harness import readers, spec, trace_reduce

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def lanes_a_call(run, layers: int) -> float | None:
    """Mean live lanes of one `kda_update` call (a layer of a step) in
    the traced stretch: `ssm_lane_steps` (the engine's count of the
    one-step state kernel's work, whatever the kernel) over steps x layers
    of the `llm.loop.decode_dispatch` spans that start inside it."""
    tw = readers.trace_wall(run)
    if tw is None:
        return None
    lane_steps = calls = 0
    for s in run["spans"]:
        a = s["attrs"]
        if (s["name"] == "llm.loop.decode_dispatch"
                and tw[0] <= s["t0"] < tw[1] and "ssm_lane_steps" in a):
            lane_steps += int(a["ssm_lane_steps"])
            calls += int(a["steps"]) * layers
    return lane_steps / calls if calls else None


def read(run):
    red = readers.traced(run)
    fam = spec.family_of(run["cell"])
    if red is None or not hasattr(fam, "kda_update_cost"):
        return None
    calls, _ = trace_reduce.op_time(red, readers.DECODE_PROGRAM,
                                    readers.kernel_op("kda_update"))
    layers = readers.kernel_layers(run, "kda_update")
    lanes = lanes_a_call(run, layers) if calls and layers else None
    if not calls or not lanes:
        return None
    print(json.dumps({"step": "kda_update_calls", "traced_calls": calls,
                      "live_lanes_a_call": lanes}), flush=True)
    fl, by = fam.kda_update_cost(run["model"], calls * lanes)
    return readers.roofline_pct(run, readers.DECODE_PROGRAM, ("kda_update",),
                                fl, by, "kernel.kda_update_roofline")
