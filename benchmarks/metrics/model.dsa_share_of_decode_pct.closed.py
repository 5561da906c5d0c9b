"""Per-layer metric `model.dsa_share_of_decode_pct.closed`: self time of the sparse latent decode-attention kernel `dsa_attn` inside the decode program over that program s device time in the traced stretch, closed-loop cells of a model with learned sparse attention. The ATTEND part only: the indexer s scores, the top-k and the gather are XLA ops, which the reduced trace keeps by instruction name and not by scope (PERF.md section 7 says which file would have to keep the scope)."""
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
                                readers.kernel_op("dsa_attn"))
    total = sum(trace_reduce.module_durations(red, readers.DECODE_PROGRAM))
    if not n or total <= 0:
        return None          # a program without the kernel
    return 100.0 * t / total
