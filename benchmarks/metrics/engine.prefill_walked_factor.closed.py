"""Per-layer metric `engine.prefill_walked_factor.closed`: token positions the position-wise halves of the prefill programs computed per true prompt token over the window (the loop counters of the engine, `prefill_walked_tokens` / `prefill_true_tokens`, host arithmetic on the lengths it holds): under `engine.prefill_pad_factor.closed` is the proof that the dense products stop at the true lengths of the rows; equal to it, every bucket was one chunk."""
from benchmarks.harness import timeline

LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"
UNIT = "x"
BETTER = "lower"


def read(run):
    if "prefill_walked_tokens" not in run["stats"][1].get("loop", {}):
        return None          # a program without the counter
    return timeline.ratio(run, "prefill_walked_tokens", "prefill_true_tokens")
