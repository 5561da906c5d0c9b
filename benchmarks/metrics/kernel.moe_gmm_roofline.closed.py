"""Per-layer metric `kernel.moe_gmm_roofline.closed`: grouped-matmul kernel `moe_gmm` in the decode program: least time by the chip s peaks for the work its calls NEED (every expert that held a row streamed once a layer-step, never the experts nobody hit; each assignment s rows in and out and its three matmuls) over the kernel s measured self time, closed-loop cells of a routed model."""
from benchmarks.harness import readers, spec, trace_reduce

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def read(run):
    """The window's counters give the mean work of one routed layer-step
    (a closed loop is steady); the traced stretch ran `decode_k` events x
    K steps x the family's `kernel_layers` of them."""
    red = readers.traced(run)
    s0, s1 = (s.get("loop", {}) for s in run["stats"])
    if red is None or "moe_layer_steps" not in s1:
        return None
    steps = s1["moe_layer_steps"] - s0["moe_layer_steps"]
    events = len(trace_reduce.module_durations(red, readers.DECODE_PROGRAM))
    if steps <= 0 or not events:
        return None
    traced_steps = (events * run["engine"]["steps_per_sync"]
                    * readers.kernel_layers(run, "moe_gmm"))
    share = traced_steps / steps
    fam = spec.family_of(run["cell"])
    fl, by = fam.moe_gmm_cost(
        run["model"],
        (s1["moe_assignments"] - s0["moe_assignments"]) * share,
        (s1["moe_experts_hit"] - s0["moe_experts_hit"]) * share)
    return readers.roofline_pct(run, readers.DECODE_PROGRAM, ("moe_gmm",),
                                fl, by, "kernel.moe_gmm_roofline")
