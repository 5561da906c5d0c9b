#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in a fresh process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object per line; the LAST line is the result the
contract names (`correct`, `attempted`, `failed`, `metrics`, `device`
and, traced, `breakdown`).  With `--trace 0` the metrics are the cell's
end-to-end metrics, with `--trace 1` its per-layer metrics.  Exit code 0
only for a correct run on the chips the cell asks for.

`--rehearse` walks the same code on the CPU at debug-sized shapes and can
never report `correct: true`.  `--rate` overrides an open cell's arrival
rate (the knee sweep); `--dump-trace` writes a traced run's structure to
chiprun_out/ for reading by hand.
"""
from __future__ import annotations

import time

T_IMPORT_WALL = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DEADLINE_S = 345          # the contract: exit within 360 s


def log(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


def _process_start_wall() -> float:
    try:
        import psutil

        return min(T_IMPORT_WALL, psutil.Process().create_time())
    except Exception:  # noqa: BLE001 - the import stamp is close enough
        return T_IMPORT_WALL


def _environment(args, cell) -> None:
    # the cluster's processes are `python -m ray_tpu...` children: they
    # find ray_tpu and benchmarks through the environment
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
    # one fixed compile cache inside the checkout unless one is given
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    # cache the small programs too: every run is a new process
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.trace:
        # a traced run reads every engine span of the window: the
        # default ring of 4096 would wrap
        os.environ["RAY_TPU_TRACE_BUFFER"] = str(1 << 19)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["RAY_TPU_CHIPS"] = str(cell.chips)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--dump-trace", action="store_true")
    args = ap.parse_args()
    t_start = _process_start_wall()

    from benchmarks.harness import spec

    cell = spec.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(spec.benchmark_json()["run_seconds"])
    if cell.kind == "serve":
        from benchmarks.harness import serve_cell as runner
    elif cell.kind == "train":
        from benchmarks.harness import train_cell as runner
    else:
        raise SystemExit(f"unknown kind {cell.kind!r} in {cell.config_name}")
    if args.rehearse:
        runner.rehearsal_cell(cell)
    _environment(args, cell)

    def _deadline(signum, frame):
        raise TimeoutError(f"the run exceeded {DEADLINE_S} s")

    signal.signal(signal.SIGALRM, _deadline)
    signal.alarm(DEADLINE_S if not args.rehearse else 900)
    log(step="start", workload=cell.name, config=cell.config_name,
        traffic=cell.traffic_name, chips=cell.chips, seed=args.seed,
        seconds=args.seconds, trace=args.trace, rehearse=args.rehearse)
    try:
        run_rec = runner.run(cell, args, log, t_start)
        e2e = runner.end_to_end(run_rec, log)
    except BaseException as e:  # noqa: BLE001 - no result without a run
        signal.alarm(0)
        traceback.print_exc()
        # no `metrics`: this is not a result line
        log(correct=False, error=f"{type(e).__name__}: {e}"[:500])
        return 2
    signal.alarm(0)

    from benchmarks.harness import result

    line, code = result.build(cell, run_rec, e2e, args, log)
    print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
