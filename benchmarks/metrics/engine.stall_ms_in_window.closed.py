"""Per-layer metric `engine.stall_ms_in_window.closed`: ms the engine loop stood in stalls with work waiting between the two readings of stats (loop.stall_s: a host phase open over 0.2 s, the watcher woken over 0.2 s late, or a _sync phase open over 1 s and three times its longest): 0 in a sound run, at least 200 in one that lost a tenth, closed-loop cells."""
from benchmarks.harness import stall

LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return stall.stall_ms_in_window(run)
