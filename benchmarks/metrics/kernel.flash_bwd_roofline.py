"""Per-layer metric `kernel.flash_bwd_roofline`: flash kernels of the train step (forward and the two backward kernels together): least time by the chip s peaks over their measured time."""
from benchmarks.harness import readers

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "train_tok_s_chip"
UNIT = "%"
BETTER = "higher"


def read(run):
    return readers.flash_bwd_roofline(run)
