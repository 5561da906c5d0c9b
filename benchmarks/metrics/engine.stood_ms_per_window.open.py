"""Per-layer metric `engine.stood_ms_per_window.open`: of the host time of the engine thread per decode window (engine.host_ms_per_window's iterations and phases), the ms the thread did not run: the host phases' summed span wall less their summed cpu_ms over those iterations, a window (a mean: the chip machine's thread clock ticks in 10 ms), open-loop cells."""
from benchmarks.harness import stood

LAYER = "engine loop"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return stood.stood_ms_per_window(run)
