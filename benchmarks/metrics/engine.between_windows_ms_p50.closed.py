"""Per-layer metric `engine.between_windows_ms_p50.closed`: host time from the end of one llm.decode_window to the start of the next, median, closed-loop cells."""
from benchmarks.harness import readers

LAYER = "engine loop"
SOURCE = "program_span"
MOVES = "serve_tok_s"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return readers.between_windows_ms_p50(run)
