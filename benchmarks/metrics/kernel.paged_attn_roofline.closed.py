"""Per-layer metric `kernel.paged_attn_roofline.closed`: paged decode-attention kernel `paged_attn` in the decode program at keys wider than values: least time by the chip s peaks to read each live context row s K and V once a kv head a layer-step and score and weigh it for every query head, over the kernel s measured self time, closed-loop cells of a family that gives `paged_attn_cost` (the work at its own two widths). The rows come from the engine s `attn_ctx_rows` counter (lengths the host holds)."""
from benchmarks.harness import readers, spec, trace_reduce

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def read(run):
    """The window's counters give the rows a decode step attends in the
    mean (a closed loop is steady); the traced stretch ran `decode_k`
    events x K steps, each a call in every layer that holds the kernel."""
    red = readers.traced(run)
    s0, s1 = (s.get("loop", {}) for s in run["stats"])
    fam = spec.family_of(run["cell"])
    if (red is None or "attn_ctx_rows" not in s1
            or not hasattr(fam, "paged_attn_cost")):
        return None
    steps = s1["decode_steps"] - s0["decode_steps"]
    events = len(trace_reduce.module_durations(red, readers.DECODE_PROGRAM))
    if steps <= 0 or not events:
        return None
    share = events * run["engine"]["steps_per_sync"] / steps
    fl, by = fam.paged_attn_cost(
        run["model"], (s1["attn_ctx_rows"] - s0["attn_ctx_rows"]) * share)
    layers = readers.kernel_layers(run, "paged_attn")
    return readers.roofline_pct(run, readers.DECODE_PROGRAM, ("paged_attn",),
                                fl * layers, by * layers,
                                "kernel.paged_attn_roofline.closed")
