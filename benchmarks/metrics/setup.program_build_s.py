"""Per-layer metric `setup.program_build_s`: seconds the replica spent in trace + lower + compile-or-load of every program from its engine's construction to the window's opening (loop.program_build_s at the first reading of stats): the engine's own programs and the whole warm-up."""
from benchmarks.harness import stood

LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "setup_s"
UNIT = "s"
BETTER = "lower"


def read(run):
    return stood.setup_program_build_s(run)
