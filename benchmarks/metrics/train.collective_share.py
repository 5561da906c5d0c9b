"""Per-layer metric `train.collective_share`: device time in collectives with no compute running, over the traced steps."""
from benchmarks.harness import readers

LAYER = "train step"
SOURCE = "device_trace"
MOVES = "train_tok_s_chip"
UNIT = "%"
BETTER = "lower"


def read(run):
    return readers.collective_share_pct(run)
