"""Per-layer metric `engine.host_ms_per_window.open`: host time of the engine thread per decode window (admit + prefill_dispatch + fund + decode_dispatch + deliver of one loop iteration), median, open-loop cells."""
from benchmarks.harness import timeline

LAYER = "engine loop"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return timeline.host_ms_per_window(run)
