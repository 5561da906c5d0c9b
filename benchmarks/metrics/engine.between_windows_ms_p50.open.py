"""Per-layer metric `engine.between_windows_ms_p50.open`: host time from the end of one llm.decode_window to the start of the next (admit, prefill dispatch, bookkeeping), median, open-loop cells."""
from benchmarks.harness import readers

LAYER = "engine loop"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return readers.between_windows_ms_p50(run)
