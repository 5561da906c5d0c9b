"""Per-layer metric `model.paged_attn_share_of_decode_pct.closed`: self time of the paged decode-attention kernel `paged_attn` inside the decode program over that program s device time in the traced stretch: how much of a decode step reading the global layers K and V pages is, closed-loop cells of a model whose global grouped-query layers keep pages beside window layers kept as rings."""
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
                                readers.kernel_op("paged_attn"))
    total = sum(trace_reduce.module_durations(red, readers.DECODE_PROGRAM))
    if not n or total <= 0:
        return None          # a program without the kernel
    return 100.0 * t / total
