"""Per-layer metric `train.mfu`: required FLOPs a token (6 per matmul parameter + causal attention, recompute not counted) x tokens/s over chips x peak."""
from benchmarks.harness import readers

LAYER = "train step"
SOURCE = "host_clock"
MOVES = "train_tok_s_chip"
UNIT = "%"
BETTER = "higher"


def read(run):
    return readers.train_mfu_pct(run)
