"""Per-layer metric `engine.bsa_selected_pct.closed`: rows the decode steps attended over rows in context, summed over the window s live lane-steps of the block-sparse layers (the engine s loop counters `bsa_rows_attended` / `bsa_rows_context`, host arithmetic on the lengths it holds): under 100 is the proof that the selection ran; 100 means every context was under dense_len and the layer was dense attention."""
LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "lower"


def read(run):
    s0, s1 = (s.get("loop", {}) for s in run["stats"])
    if "bsa_rows_context" not in s1:
        return None          # a program without the counters
    ctx = s1["bsa_rows_context"] - s0.get("bsa_rows_context", 0)
    att = s1["bsa_rows_attended"] - s0.get("bsa_rows_attended", 0)
    if ctx <= 0:
        return None
    return 100.0 * att / ctx
