"""Per-layer metric `model.kda_update_share_of_decode_pct.closed`: self time of the one-step gated delta-rule kernel `kda_update` inside the decode program over that program s device time in the traced stretch: how much of a decode step the lanes  KDA state matrices are, closed-loop cells of a KDA model."""
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
    n, t = trace_reduce.op_time(red, readers.DECODE_PROGRAM,
                                readers.kernel_op("kda_update"))
    total = sum(trace_reduce.module_durations(red, readers.DECODE_PROGRAM))
    if not n or total <= 0:
        return None          # a program without the kernel
    return 100.0 * t / total
