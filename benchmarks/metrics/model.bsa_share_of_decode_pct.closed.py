"""Per-layer metric `model.bsa_share_of_decode_pct.closed`: self time of the block-sparse kernels inside the decode program, `bsa_index` (a step s scores over a lane s kernel keys and its selection) and `bsa_attn` (the attention under the selection), over that program s device time in the traced stretch, closed-loop cells of a model whose attention selects its own key blocks. The gather of a lane s stride rows through its table and the selection s bias a row are XLA ops, which the reduced trace keeps by instruction name and not by scope (PERF.md section 5 has their time from a dumped trace)."""
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
                                readers.kernel_op("bsa_index", "bsa_attn"))
    total = sum(trace_reduce.module_durations(red, readers.DECODE_PROGRAM))
    if not n or total <= 0:
        return None          # a program without the kernel
    return 100.0 * t / total
