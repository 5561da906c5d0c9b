"""Per-layer metric `engine.stood_ms_per_window.closed`: of the host time of the engine thread per decode window (engine.host_ms_per_window's iterations and phases), the ms the thread did not run: the host phases' summed span wall less their summed cpu_ms over those iterations, a window (a mean: the chip machine's thread clock ticks in 10 ms), closed-loop cells."""
from benchmarks.harness import stood

LAYER = "engine loop"
SOURCE = "program_span"
MOVES = "serve_tok_s"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return stood.stood_ms_per_window(run)
