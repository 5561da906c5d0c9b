"""Per-layer metric `model.bsa_share_of_prefill_pct.closed`: self time of the block-sparse kernels inside the prefill programs, `bsa_index` (the scores over the kernel keys and the selection of a query s blocks) and `bsa_prefill` (the attention under the selection), over those programs  device time in the traced stretch: how much of a long prompt s pass the mechanism is beside the products over the weights and the scan, closed-loop cells of a model whose attention selects its own key blocks. A program whose scores are XLA ops has no `bsa_index` event and reads the attention alone."""
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
                                readers.kernel_op("bsa_index", "bsa_prefill"))
    total = sum(trace_reduce.module_durations(red, readers.PREFILL_PROGRAMS))
    if not n or total <= 0:
        return None          # a program without the kernel
    return 100.0 * t / total
