"""Per-layer metric `kernel.flash_fwd_roofline`: flash forward kernel in the prefill programs: least time by the chip s peaks for the causal work of the TRUE prompt lengths over the kernel s measured time."""
from benchmarks.harness import readers

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"
UNIT = "%"
BETTER = "higher"


def read(run):
    return readers.flash_fwd_roofline(run)
