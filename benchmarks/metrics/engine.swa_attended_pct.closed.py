"""Per-layer metric `engine.swa_attended_pct.closed`: rows a window layer s decode step attended over rows in its lane s context, summed over the window s live lane-steps of the window layers (the engine s loop counters `swa_rows_attended` / `swa_rows_context`, host arithmetic on the lengths it holds): under 100 is the proof that the window bounds the work; 100 means every context was under the window."""
LAYER = "engine loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "lower"


def read(run):
    s0, s1 = (s.get("loop", {}) for s in run["stats"])
    if "swa_rows_context" not in s1:
        return None          # a program without the counters
    ctx = s1["swa_rows_context"] - s0.get("swa_rows_context", 0)
    got = s1["swa_rows_attended"] - s0.get("swa_rows_attended", 0)
    if ctx <= 0:
        return None
    return 100.0 * got / ctx
