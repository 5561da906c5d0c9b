"""Per-layer metric `kernel.flash_fwd_roofline.closed`: global-layer prefill attention kernel `flash_fwd` in the prefill programs: least time by the chip s peaks for the causal work of the TRUE prompt lengths at the family s own heads and widths (its `flash_fwd_cost(model, lens)`: 128 query heads over 8 kv heads of 128 + 128 for `cohere2_moe`) over the kernel s measured self time, closed-loop cells of a model whose global grouped-query layers prefill through `flash_fwd` beside window layers under another name."""
from benchmarks.harness import readers, spec

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def read(run):
    fam = spec.family_of(run["cell"])
    sp, cut = readers.prefill_spans_in_trace(run)
    if not sp or not hasattr(fam, "flash_fwd_cost"):
        return None
    lens = [int(s["attrs"].get("prompt_tokens", 0)) for s in sp]
    fl, by = fam.flash_fwd_cost(run["model"], lens)
    scale = readers.kernel_layers(run, "flash_fwd") * cut
    return readers.roofline_pct(run, readers.FLASH_PREFILL_PROGRAM,
                                ("flash_fwd",), fl * scale, by * scale,
                                "kernel.flash_fwd_roofline.closed")
