"""Per-layer metric `engine.program_build_ms_in_window.closed`: ms the replica spent tracing, lowering and compiling or loading programs between the two readings of stats (loop.program_build_s): 0 in a sound run, closed-loop cells."""
from benchmarks.harness import stood

LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return stood.program_build_ms_in_window(run)
