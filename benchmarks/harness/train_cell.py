"""One run of a train cell, driver side: the loop runs in ONE child
process that holds the host's chips.  This process stays off JAX."""
from __future__ import annotations

import time

from . import spec


def rehearsal_cell(cell: spec.Cell) -> None:
    cell.family.rehearsal(cell.config)
    cell.config["train"].update(seq=128)


def run(cell: spec.Cell, args, log, t_process_wall: float) -> dict:
    cfg, t = cell.config, cell.traffic
    run_rec: dict = {"cell": cell, "model": cell.family.published(cfg),
                     "seconds": float(args.seconds), "setup": {},
                     "problems": [], "trace": None, "spans": []}
    loop_config = {
        "model": run_rec["model"], "family": cell.family_name,
        "train": cfg["train"], "seed": args.seed,
        "seconds": float(args.seconds), "chips": cell.chips,
        "trace": bool(args.trace), "dump_trace": bool(args.dump_trace),
        "rehearse": bool(args.rehearse), "cell": cell.name,
        "root": spec.ROOT, "distinct_batches": t["distinct_batches"],
        "warm_steps": t["warm_steps"], "check": t["check"]}
    t0 = time.perf_counter()
    rec = _child(loop_config, log)
    run_rec["drive_s"] = time.perf_counter() - t0
    run_rec.update(rec=rec, device=rec["device"], trace=rec.get("trace"))
    run_rec["problems"] += rec["problems"]
    run_rec["setup_s"] = rec["window_wall0"] - t_process_wall
    run_rec["setup"]["train_init_s"] = (
        rec["times"]["sharded_init_s"] + rec["times"]["warm_steps_s"])
    log(step="train", **{k: v for k, v in rec.items() if k != "trace"})
    return run_rec


def _child(loop_config: dict, log) -> dict:
    """Drive the loop from a child process, as `chip_smoke.py --chips 4`
    does: JaxTrainer's worker is a plain worker, which the node agent
    pins to the CPU (PERF.md, open questions)."""
    import json
    import subprocess
    import sys

    proc = subprocess.Popen(
        [sys.executable, "-c",
         "from benchmarks.harness import train_loop; train_loop.child_main()"],
        cwd=spec.ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        text=True)
    rec = None
    try:
        proc.stdin.write(json.dumps(loop_config))
        proc.stdin.close()
        for line in proc.stdout:
            if line.startswith("BENCH_CHILD_RECORD "):
                rec = json.loads(line[len("BENCH_CHILD_RECORD "):])
            elif line.strip():
                print(line.rstrip(), flush=True)
        rc = proc.wait(timeout=60.0)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rec is None:
        raise RuntimeError(f"the train child ended (rc={rc}) with no record")
    return rec


def end_to_end(run_rec: dict, log) -> dict:
    rec, t = run_rec["rec"], run_rec["cell"].config["train"]
    tokens = rec["steps"] * t["batch"] * t["seq"]
    out = {"setup_s": run_rec["setup_s"],
           "train_tok_s_chip": tokens / rec["window_s"]
           / run_rec["cell"].chips}
    log(step="train_rate", steps=rec["steps"], window_s=rec["window_s"],
        step_s=rec["window_s"] / rec["steps"], **out)
    return out
