"""Per-layer metric `model.decode_step_ms.open`: device time of the decode program per step (program duration / K), median, open-loop cells."""
from benchmarks.harness import readers

LAYER = "model step"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return readers.decode_step_ms(run)
