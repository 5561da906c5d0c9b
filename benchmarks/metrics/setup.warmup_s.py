"""Per-layer metric `setup.warmup_s`: seconds the warm-up of this cell's programs took (compiles or cache reads, and one execution each)."""
LAYER = "driver api and node agent"
SOURCE = "host_clock"
MOVES = "setup_s"
UNIT = "s"
BETTER = "lower"


def read(run):
    return run["setup"].get("warmup_s")
