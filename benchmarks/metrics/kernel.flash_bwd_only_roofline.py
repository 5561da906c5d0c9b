"""Per-layer metric `kernel.flash_bwd_only_roofline`: the two flash backward kernels of the train step, told by name: least time for the backward s work alone over their measured time."""
from benchmarks.harness import timeline

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "train_tok_s_chip"
UNIT = "%"
BETTER = "higher"


def read(run):
    return timeline.flash_bwd_only_roofline(run)
