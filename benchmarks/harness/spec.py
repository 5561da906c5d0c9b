"""Finds everything by name: a cell names a configuration and a traffic
mix, each a data file; a configuration names its model family, one file
under `harness/families/`; a per-layer metric is one reader file under
`metrics/`.  Nothing here knows a cell or an architecture, so a later PR
adds files and `BENCHMARK.json` entries and edits none."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
DEFAULT_FAMILY = "llama"        # a configuration file without `family`
# what a family file gives (README, "A model family"); the tolerances by
# the kind of cell that holds the program to them
FAMILY_ATTRS = ("published", "vocab_size", "program_config", "init_params",
                "reference", "rehearsal", "param_count", "matmul_params",
                "decode_step_bytes", "kernel_layers")
FAMILY_TOLERANCES = {
    "serve": ("REFERENCE_GAP_TOL",),
    "train": ("LOGPROB_RMS_TOL", "GRAD_NORM_RTOL", "LOSS_RTOL")}


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def benchmark_json(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict            # configs/<name>.json as it is run
    family: object          # harness/families/<config["family"]>.py
    traffic: dict           # traffic/<name>.json
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]

    @property
    def kind(self) -> str:
        return self.config["kind"]          # "serve" | "train"

    @property
    def family_name(self) -> str:
        return family_name(self.config)

    @property
    def loop(self) -> str:
        return self.traffic["loop"]         # "open" | "closed" | "steps"


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = benchmark_json(root)
    try:
        (w,) = [w for w in bench["workloads"] if w["name"] == workload]
    except ValueError:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    (c,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    config = load_json(os.path.join(root, c["file"]))
    traffic = load_json(os.path.join(
        root, bench["paths"][0], "traffic", w["traffic"] + ".json"))
    family = config_family(config)
    return Cell(
        name=w["name"], chips=int(w["chips"]), config_name=c["name"],
        traffic_name=w["traffic"], config=config, family=family,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"]
                    if _applies(m, w["name"])],
        per_layer=[m for m in bench["per_layer"]
                   if _applies(m, w["name"])])


def family_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "harness", "families", name + ".py")


def _load_by_path(prefix: str, name: str, path: str):
    """A module by its file: the names hold dots, and a later PR's file
    need not be importable as a package member."""
    spec = importlib.util.spec_from_file_location(
        prefix + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(name: str, kind: str | None = None):
    """The module of model family `name`: `harness/families/<name>.py`.
    A name with no file, or a file that lacks an attribute (of
    FAMILY_ATTRS, and of the tolerances a cell of `kind` needs), stops
    the run here, before anything is started."""
    path = family_path(name)
    if not NAME_RE.match(name) or not os.path.isfile(path):
        raise SystemExit(f"no model family {name!r}: no file {path}")
    mod = _load_by_path("bench_family_", name, path)
    for attr in FAMILY_ATTRS + FAMILY_TOLERANCES.get(kind, ()):
        if not hasattr(mod, attr):
            raise SystemExit(f"{path} declares no {attr}")
    return mod


def family_name(config: dict) -> str:
    return config.get("family", DEFAULT_FAMILY)


def config_family(config: dict):
    """The family a configuration file names (none: the default), held
    to what a cell of the file's `kind` needs."""
    return load_family(family_name(config), config.get("kind"))


def family_of(cell):
    """A cell's family module; a cell built by hand (a test's stand-in)
    has only its config to name one."""
    fam = getattr(cell, "family", None)
    return fam if fam is not None else config_family(cell.config)


def metric_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", name + ".py")


def load_reader(name: str):
    """The module of per-layer metric `name`: `metrics/<name>.py`, which
    declares LAYER, SOURCE, MOVES, UNIT, BETTER and `read(run) -> float |
    None`.  Loaded by path: the names hold dots."""
    path = metric_path(name)
    mod = _load_by_path("bench_metric_", name, path)
    for attr in ("LAYER", "SOURCE", "MOVES", "UNIT", "BETTER", "read"):
        if not hasattr(mod, attr):
            raise AttributeError(f"{path} declares no {attr}")
    if mod.SOURCE not in SOURCES:
        raise ValueError(f"{path}: SOURCE {mod.SOURCE!r}")
    return mod


def list_readers() -> list[str]:
    d = os.path.join(BENCH_DIR, "metrics")
    return sorted(f[:-3] for f in os.listdir(d)
                  if f.endswith(".py") and not f.startswith("_"))


def read_per_layer(cell: Cell, run: dict, log=None) -> dict:
    """{name: {"value", "unit"}} of the cell's per-layer metrics.  A
    reader that finds nothing to read returns None and its metric is
    left out of the line."""
    out = {}
    for m in cell.per_layer:
        reader = load_reader(m["name"])
        try:
            value = reader.read(run)
        except Exception as e:  # noqa: BLE001 - one reader must not sink the line
            if log:
                log(reader_failed=m["name"], error=f"{type(e).__name__}: {e}")
            value = None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
