"""Finds everything by name: a cell names a configuration and a traffic
mix, each a data file; a per-layer metric is one reader file under
`metrics/`.  Nothing here knows a cell, so a later PR adds files and
`BENCHMARK.json` entries and edits none."""
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
    traffic: dict           # traffic/<name>.json
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]

    @property
    def kind(self) -> str:
        return self.config["kind"]          # "serve" | "train"

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
    return Cell(
        name=w["name"], chips=int(w["chips"]), config_name=c["name"],
        traffic_name=w["traffic"], config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"]
                    if _applies(m, w["name"])],
        per_layer=[m for m in bench["per_layer"]
                   if _applies(m, w["name"])])


def metric_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", name + ".py")


def load_reader(name: str):
    """The module of per-layer metric `name`: `metrics/<name>.py`, which
    declares LAYER, SOURCE, MOVES, UNIT, BETTER and `read(run) -> float |
    None`.  Loaded by path: the names hold dots."""
    path = metric_path(name)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
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
