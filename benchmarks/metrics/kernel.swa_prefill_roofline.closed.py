"""Per-layer metric `kernel.swa_prefill_roofline.closed`: window-layer prefill attention kernel (`flash_fwd` under a band of 513 positions, q/k width 256, v width 128, 64 heads) in the prefill programs: least time by the chip s peaks for the band s work of the TRUE prompt lengths at the TRUE widths over the kernel s measured self time, closed-loop cells of a model with window layers."""
from benchmarks.harness import readers, spec

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def read(run):
    fam = spec.family_of(run["cell"])
    sp, cut = readers.prefill_spans_in_trace(run)
    if not sp or not hasattr(fam, "swa_prefill_cost"):
        return None
    lens = [int(s["attrs"].get("prompt_tokens", 0)) for s in sp]
    fl, by = fam.swa_prefill_cost(run["model"], lens)
    scale = readers.kernel_layers(run, "flash_fwd") * cut
    return readers.roofline_pct(run, readers.FLASH_PREFILL_PROGRAM,
                                ("flash_fwd",), fl * scale, by * scale,
                                "kernel.swa_prefill_roofline")
