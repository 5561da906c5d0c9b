"""Per-layer metric `engine.moe_experts_hit_pct.closed`: experts that held at least one row, of the experts there are, per routed layer and decode step over the window (the engine s loop counters): how much of the routed layers the batch keeps busy, closed-loop cells of a routed model. Better HIGHER in a closed loop: the experts hit rise with the lanes that are live (37 of 64 lanes hit 83 %, 57 hit 90 %: PR 28), and each expert hit is streamed once for all its rows, so tokens/s rise with it although each step costs more."""
LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def read(run):
    s0, s1 = (s.get("loop", {}) for s in run["stats"])
    if "moe_layer_steps" not in s1:
        return None          # a program without the counters
    steps = s1["moe_layer_steps"] - s0["moe_layer_steps"]
    hit = s1["moe_experts_hit"] - s0["moe_experts_hit"]
    if steps <= 0:
        return None
    return 100.0 * hit / (run["model"]["num_experts"] * steps)
