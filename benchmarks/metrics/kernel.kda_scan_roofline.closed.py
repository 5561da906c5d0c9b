"""Per-layer metric `kernel.kda_scan_roofline.closed`: chunked gated delta-rule kernel `kda_scan` in the prefill programs: least time by the chip s peaks for the work its traced calls NEED (q, k, g, v in and o out once in float32 for the TRUE prompt positions, the state written a prompt, and a (head, chunk) s products as the kernel forms them: under a bounded gate the pairs once, under an unbounded one once a level of the halved anchors) over the kernel s measured self time, closed-loop cells of a KDA model."""
from benchmarks.harness import kda_cost, readers, spec

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "higher"


def bounded_cost(m, positions: float, rows: float) -> tuple[float, float]:
    """(flops, bytes) ONE layer s calls need under a gate BOUNDED below
    (`linear_attn_config.gate_lower_bound`: one product for A and B), for
    a family file that brings no `kda_scan_cost` of its own."""
    la = m["linear_attn_config"]
    return kda_cost.scan_cost(la["num_heads"], la["head_dim"], positions,
                              rows, halved=False)


def read(run):
    fam = spec.family_of(run["cell"])
    sp, cut = readers.prefill_spans_in_trace(run)
    if not sp or "linear_attn_config" not in run["model"]:
        return None
    lens = [int(s["attrs"].get("prompt_tokens", 0)) for s in sp]
    cost = getattr(fam, "kda_scan_cost", None) or bounded_cost
    fl, by = cost(run["model"], float(sum(lens)), float(len(lens)))
    # (a family file older than this metric counts the layers under the
    # decode kernel s name: the same layers call both)
    layers = (readers.kernel_layers(run, "kda_scan")
              or readers.kernel_layers(run, "kda_update"))
    scale = layers * cut
    return readers.roofline_pct(run, readers.FLASH_PREFILL_PROGRAM,
                                ("kda_scan",), fl * scale, by * scale,
                                "kernel.kda_scan_roofline.closed")
