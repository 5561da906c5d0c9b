"""Per-layer metric `engine.dsa_selected_pct.closed`: rows the learned selection attended over rows in context, summed over the window s live lane-steps of the sparse layers (the engine s loop counters `dsa_rows_selected` / `dsa_rows_context`, host arithmetic on the lengths it holds): under 100 is the proof that the sparse path ran; 100 means every context was under the selection s size and the layer was dense latent attention."""
LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "lower"


def read(run):
    s0, s1 = (s.get("loop", {}) for s in run["stats"])
    if "dsa_rows_context" not in s1:
        return None          # a program without the counters
    ctx = s1["dsa_rows_context"] - s0.get("dsa_rows_context", 0)
    sel = s1["dsa_rows_selected"] - s0.get("dsa_rows_selected", 0)
    if ctx <= 0:
        return None
    return 100.0 * sel / ctx
