"""Per-layer metric `engine.host_ms_per_window.closed`: host time of the engine thread per decode window (admit + prefill_dispatch + fund + decode_dispatch + deliver of one loop iteration), median, closed-loop cells."""
from benchmarks.harness import timeline

LAYER = "engine loop"
SOURCE = "program_span"
MOVES = "serve_tok_s"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return timeline.host_ms_per_window(run)
