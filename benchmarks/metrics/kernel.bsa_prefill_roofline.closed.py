"""Per-layer metric `kernel.bsa_prefill_roofline.closed`: block-sparse prefill-attention kernel `bsa_prefill` in the prefill programs: least time by the chip s peaks for the rows the queries  selections NAME at the TRUE prompt lengths (a query below dense_len every row at or below it; past it the first block, its window and its top blocks), whatever implements it, over the kernel s measured self time, closed-loop cells of a model whose attention selects its own key blocks."""
from benchmarks.harness import readers, spec

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def read(run):
    fam = spec.family_of(run["cell"])
    sp, cut = readers.prefill_spans_in_trace(run)
    if not sp or not hasattr(fam, "bsa_prefill_cost"):
        return None
    lens = [int(s["attrs"].get("prompt_tokens", 0)) for s in sp]
    fl, by = fam.bsa_prefill_cost(run["model"], lens)
    scale = readers.kernel_layers(run, "bsa_prefill") * cut
    return readers.roofline_pct(run, readers.FLASH_PREFILL_PROGRAM,
                                ("bsa_prefill",), fl * scale, by * scale,
                                "kernel.bsa_prefill_roofline")
