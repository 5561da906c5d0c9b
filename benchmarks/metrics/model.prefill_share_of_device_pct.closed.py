"""Per-layer metric `model.prefill_share_of_device_pct.closed`: device time of the prefill programs (forward and page scatter) over the device time of every program in the traced stretch: which of the two paths, prefill or decode, carries a closed-loop cell of long prompts."""
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
    total = sum(trace_reduce.module_durations(red, ""))
    prefill = sum(trace_reduce.module_durations(red,
                                                readers.PREFILL_PROGRAMS))
    if total <= 0 or not prefill:
        return None
    return 100.0 * prefill / total
