"""Per-layer metric `engine.prefill_pad_factor.open`: token positions the prefill programs computed per true prompt token over the window, from the engine s loop counters, open-loop cells."""
from benchmarks.harness import timeline

LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"
UNIT = "x"
BETTER = "lower"


def read(run):
    return timeline.prefill_pad_factor(run)
