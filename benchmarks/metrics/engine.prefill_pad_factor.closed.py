"""Per-layer metric `engine.prefill_pad_factor.closed`: token positions the prefill programs computed per true prompt token over the window, from the engine s loop counters, closed-loop cells."""
from benchmarks.harness import timeline

LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"
UNIT = "x"
BETTER = "lower"


def read(run):
    return timeline.prefill_pad_factor(run)
