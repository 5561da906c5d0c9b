"""Per-layer metric `engine.gc_pause_ms_in_window.closed`: ms the replica process's cyclic garbage collector held the interpreter between the two readings of stats (loop.gc_pause_s: every collection, whichever thread started it), closed-loop cells."""
from benchmarks.harness import stall

LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return stall.gc_pause_ms_in_window(run)
