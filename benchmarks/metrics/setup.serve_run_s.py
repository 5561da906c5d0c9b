"""Per-layer metric `setup.serve_run_s`: seconds serve.run took: replica start in the device worker, the jitted weight init and the engine's page pool."""
LAYER = "driver api and node agent"
SOURCE = "host_clock"
MOVES = "setup_s"
UNIT = "s"
BETTER = "lower"


def read(run):
    return run["setup"].get("serve_run_s")
