"""Per-layer metric `model.kda_scan_share_of_prefill_pct.closed`: self time of the chunked gated delta-rule kernel `kda_scan` inside the prefill programs over those programs  device time in the traced stretch: how much of a long prompt s pass is the scan and not the products over the weights, closed-loop cells of a KDA model."""
from benchmarks.harness import readers, trace_reduce

LAYER = "model step"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "lower"


def read(run):
    red = readers.traced(run)
    if red is None:
        return None
    n, t = trace_reduce.op_time(red, readers.FLASH_PREFILL_PROGRAM,
                                readers.kernel_op("kda_scan"))
    total = sum(trace_reduce.module_durations(red, readers.PREFILL_PROGRAMS))
    if not n or total <= 0:
        return None          # a program without the kernel
    return 100.0 * t / total
