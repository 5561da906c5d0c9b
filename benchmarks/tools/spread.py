#!/usr/bin/env python3
"""Spread of a cell's runs, as the contract measures it: for each metric
the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median, per set of
runs and the wider of the sets.

    python3 benchmarks/tools/spread.py chiprun_out/sets_<cell>_A*.log -- chiprun_out/sets_<cell>_B*.log

Each log is the standard output of one `run.py` run (last line = result).
"""
from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import stats  # noqa: E402


def last_line(path: str) -> dict | None:
    with open(path, encoding="utf-8") as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
    try:
        d = json.loads(lines[-1])
    except (IndexError, ValueError):
        return None
    return d if "metrics" in d else None


def main() -> None:
    args, sets, cur = sys.argv[1:], [], []
    for a in args:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    table: dict = {}
    for i, files in enumerate(sets):
        for f in files:
            d = last_line(f)
            if d is None or not d["correct"]:
                print(f"set {i}: {f}: no correct result", file=sys.stderr)
                continue
            for name, m in d["metrics"].items():
                table.setdefault(name, {}).setdefault(i, []).append(m["value"])
    for name, by_set in sorted(table.items()):
        row = {"metric": name}
        spreads = []
        for i, vals in sorted(by_set.items()):
            row[f"set{i}"] = vals
            row[f"median{i}"] = statistics.median(vals)
            if len(vals) >= 2:
                spreads.append(stats.iqr_share(vals))
                row[f"iqr_share{i}"] = spreads[-1]
        if spreads:
            row["widest"] = max(spreads)
            row["bound_by_rule"] = max(0.01, 5 * max(spreads))
        print(json.dumps(row))


if __name__ == "__main__":
    main()
