"""Per-layer metric `engine.moe_rows_per_expert_hit.closed`: token-expert assignments computed over experts that held at least one row, summed over the window s decode programs (the engine s loop counters): the rows each STREAMED expert multiplies in a routed layer of a decode step. An expert hit is read whole whatever its rows, so tokens/s rise with the rows it serves; top-22 of 512 at a quarter held reads ~2.9 where the deployment s four chips  lanes would read 11. Closed-loop cells of a routed model whose program has the counters."""
LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"
UNIT = "rows"
BETTER = "higher"


def read(run):
    s0, s1 = (s.get("loop", {}) for s in run["stats"])
    if "moe_experts_hit" not in s1 or "moe_assignments" not in s1:
        return None          # a program without the counters
    hit = s1["moe_experts_hit"] - s0.get("moe_experts_hit", 0)
    if hit <= 0:
        return None
    return (s1["moe_assignments"] - s0.get("moe_assignments", 0)) / hit
