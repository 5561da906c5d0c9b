"""The last line of a run: the contract's one JSON object."""
from __future__ import annotations

from . import spec, trace_reduce


def build(cell: spec.Cell, run_rec: dict, e2e: dict, args, log
          ) -> tuple[dict, int]:
    dev = run_rec["device"]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": dev["memory_peak_bytes"]}
    problems = list(run_rec["problems"])
    if device["platform"] != "tpu":
        problems.append(f"platform {device['platform']!r} is not the chip")
    if device["count"] != cell.chips:
        problems.append(f"{device['count']} devices, the cell asks for "
                        f"{cell.chips}")
    if args.rehearse:
        problems.append("a rehearsal is never correct")
    line: dict = {}
    if cell.kind == "serve":
        reqs = run_rec["requests"]
        attempted, failed = len(reqs), sum(not r.ok for r in reqs)
    else:
        attempted, failed = run_rec["rec"]["steps"], 0
    if args.trace:
        run_rec["e2e"] = e2e
        red = run_rec.get("trace")
        metrics = spec.read_per_layer(cell, run_rec, log)
        if red and red.get("busy_s"):
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            log(step="idle", idle_share=1 - red["busy_s"] / red["window_s"])
            line["breakdown"] = {
                "device_ops": trace_reduce.top_ops(red, 10),
                "idle_gaps": trace_reduce.attribute_gaps(
                    red, run_rec.get("spans") or [], 10)}
        else:
            problems.append("the traced run read no device operation")
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                problems.append(f"{m['name']} could not be measured")
                continue
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    if problems:
        log(step="problems", problems=problems)
    correct = not problems
    if args.rehearse:
        # a CPU number never stands under a device metric's name
        metrics = {"rehearsal." + k: v for k, v in metrics.items()}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, **line}
    return line, 0 if correct else 1
