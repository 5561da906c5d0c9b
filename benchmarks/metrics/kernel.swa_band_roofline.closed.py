"""Per-layer metric `kernel.swa_band_roofline.closed`: window-layer prefill attention kernel `swa_band` (the flash forward under a band of 128 positions with a learned sink, q/k width 192, v width 128, 64 query heads over 8 kv heads) in the prefill programs: least time by the chip s peaks for the band s work of the TRUE prompt lengths at the TRUE widths over the kernel s measured self time, closed-loop cells of a model whose window layers prefill under that name."""
from benchmarks.harness import readers, spec

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def read(run):
    fam = spec.family_of(run["cell"])
    sp, cut = readers.prefill_spans_in_trace(run)
    if not sp or not hasattr(fam, "swa_band_cost"):
        return None
    lens = [int(s["attrs"].get("prompt_tokens", 0)) for s in sp]
    fl, by = fam.swa_band_cost(run["model"], lens)
    scale = readers.kernel_layers(run, "swa_band") * cut
    return readers.roofline_pct(run, readers.FLASH_PREFILL_PROGRAM,
                                ("swa_band",), fl * scale, by * scale,
                                "kernel.swa_band_roofline.closed")
