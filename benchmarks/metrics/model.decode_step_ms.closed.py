"""Per-layer metric `model.decode_step_ms.closed`: device time of the decode program per step (program duration / K), median, closed-loop cells."""
from benchmarks.harness import readers

LAYER = "model step"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "ms"
BETTER = "lower"


def read(run):
    return readers.decode_step_ms(run)
