"""Per-layer metric `kernel.ssm_update_roofline.closed`: one-step state-space kernel `ssm_update` in the decode program: least time by the chip s peaks for the work its calls NEED (each LIVE lane s state matrices read and written once a layer-step, its x, B, C, dt in and y out, the update s operations; a lane that holds no request is not counted) over the kernel s measured self time, closed-loop cells of a state-space model."""
import json

from benchmarks.harness import readers, spec, trace_reduce

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def lanes_a_call(run, layers: int) -> float | None:
    """Mean live lanes of one `ssm_update` call (a layer of a step) in
    the traced stretch: `ssm_lane_steps` over steps x layers of the
    `llm.loop.decode_dispatch` spans that start inside it.  (Not the
    window's counters: they run on through the drain, where the lanes
    empty; the stretch lies in the steady window.)"""
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
    """The kernel's traced calls, each over the mean live lanes of a
    call in the stretch: cut programs at the stretch's edges count by
    the calls of theirs that lie inside."""
    red = readers.traced(run)
    fam = spec.family_of(run["cell"])
    if red is None or not hasattr(fam, "ssm_update_cost"):
        return None
    calls, _ = trace_reduce.op_time(red, readers.DECODE_PROGRAM,
                                    readers.kernel_op("ssm_update"))
    lanes = lanes_a_call(run, readers.kernel_layers(run, "ssm_update"))
    if not calls or not lanes:
        return None
    print(json.dumps({"step": "ssm_update_calls", "traced_calls": calls,
                      "live_lanes_a_call": lanes}), flush=True)
    fl, by = fam.ssm_update_cost(run["model"], calls * lanes)
    return readers.roofline_pct(run, readers.DECODE_PROGRAM, ("ssm_update",),
                                fl, by, "kernel.ssm_update_roofline")
