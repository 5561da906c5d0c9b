"""Per-layer metric `model.attn_share_of_prefill_pct.closed`: self time of the prefill attention kernels `flash_fwd` (global layers) and `swa_band` (window layers under their band) inside the prefill programs over those programs  device time in the traced stretch: how much of a long prompt s pass is attention and not the products over the weights, closed-loop cells of a model whose window layers prefill as `swa_band`."""
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
    band, t_band = trace_reduce.op_time(
        red, readers.FLASH_PREFILL_PROGRAM, readers.kernel_op("swa_band"))
    _, t_flash = trace_reduce.op_time(
        red, readers.FLASH_PREFILL_PROGRAM, readers.kernel_op("flash_fwd"))
    total = sum(trace_reduce.module_durations(red, readers.PREFILL_PROGRAMS))
    if not band or total <= 0:
        return None          # a program without a banded kernel of its own
    return 100.0 * (t_band + t_flash) / total
