"""Per-layer metric `engine.gc_pause_ms_in_window.open`: ms the replica process's cyclic garbage collector held the interpreter between the two readings of stats (loop.gc_pause_s: every collection, whichever thread started it), open-loop cells."""
from benchmarks.harness import stall

LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return stall.gc_pause_ms_in_window(run)
