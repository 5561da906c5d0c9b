"""Per-layer metric `engine.lanes_live.closed`: mean lanes holding a request per decode step over the window, from the engine s loop counters, closed-loop cells."""
from benchmarks.harness import timeline

LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"
UNIT = "lanes"
BETTER = "higher"


def read(run):
    return timeline.lanes_live(run)
