"""Per-layer metric `kernel.paged_attn_roofline.open`: paged decode attention kernel: least time to read each live lane s context once over the kernel s measured time, open-loop cells."""
from benchmarks.harness import readers

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"
UNIT = "%"
BETTER = "higher"


def read(run):
    return readers.paged_attn_roofline(run)
