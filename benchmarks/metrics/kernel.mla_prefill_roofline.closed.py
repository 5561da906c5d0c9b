"""Per-layer metric `kernel.mla_prefill_roofline.closed`: prefill attention kernel (`flash_fwd` at q/k width 192, v width 128) in the prefill programs of a latent-attention model: least time by the chip s peaks for the causal work of the TRUE prompt lengths at the TRUE widths over the kernel s measured self time, closed-loop cells."""
from benchmarks.harness import readers, spec

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def read(run):
    fam = spec.family_of(run["cell"])
    sp, cut = readers.prefill_spans_in_trace(run)
    if not sp or not hasattr(fam, "mla_prefill_cost"):
        return None
    lens = [int(s["attrs"].get("prompt_tokens", 0)) for s in sp]
    fl, by = fam.mla_prefill_cost(run["model"], lens)
    scale = readers.kernel_layers(run, "flash_fwd") * cut
    return readers.roofline_pct(run, readers.FLASH_PREFILL_PROGRAM,
                                ("flash_fwd",), fl * scale, by * scale,
                                "kernel.mla_prefill_roofline")
