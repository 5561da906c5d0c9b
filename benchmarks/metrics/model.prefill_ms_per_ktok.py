"""Per-layer metric `model.prefill_ms_per_ktok`: device time of the prefill programs (forward and page scatter) per 1,000 prompt tokens prefilled in the traced window."""
from benchmarks.harness import readers

LAYER = "model step"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return readers.prefill_ms_per_ktok(run)
