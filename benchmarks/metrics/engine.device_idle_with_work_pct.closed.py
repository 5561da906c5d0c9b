"""Per-layer metric `engine.device_idle_with_work_pct.closed`: share of the traced stretch in which the chip is idle while the engine thread is in a phase other than llm.loop.idle, closed-loop cells."""
from benchmarks.harness import timeline

LAYER = "engine loop"
SOURCE = "device_trace"
MOVES = "serve_tok_s"
UNIT = "%"
BETTER = "lower"


def read(run):
    return timeline.device_idle_with_work_pct(run)
