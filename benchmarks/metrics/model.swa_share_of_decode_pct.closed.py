"""Per-layer metric `model.swa_share_of_decode_pct.closed`: self time of the window-layer decode-attention kernel `swa_attn` inside the decode program over that program s device time in the traced stretch, closed-loop cells of a model with window layers kept as a ring a lane. The ATTEND part only: the ring s row write and its bias are XLA ops."""
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
                                readers.kernel_op("swa_attn"))
    total = sum(trace_reduce.module_durations(red, readers.DECODE_PROGRAM))
    if not n or total <= 0:
        return None          # a program without the kernel
    return 100.0 * t / total
