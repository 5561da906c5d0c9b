"""Per-layer metric `setup.train_init_s`: seconds sharded_init and the warm steps (the step's compile or cache read) took in the train worker."""
LAYER = "driver api and node agent"
SOURCE = "host_clock"
MOVES = "setup_s"
UNIT = "s"
BETTER = "lower"


def read(run):
    return run["setup"].get("train_init_s")
