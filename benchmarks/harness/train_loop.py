"""The train cell's loop: runs in the ONE process that holds the host's
chips, drives the program's own `train/step.py` helpers, and returns one
record at its end."""
from __future__ import annotations

import math
import os
import time

# `correct` for a train cell: like with like.  Before the window ONE
# small batch (the first `sequences` sequences of the first batch, cut to
# `positions` positions; the traffic file says how many) is scored under
# the mesh by the sharded step itself (its first call: the loss and the
# gradient norm it reports come from the forward, backward, collectives
# and kernels the window runs; the schedule's first learning rate is 0,
# so this step moves no weight) and, per position, by the program's
# forward (`token_logprobs`).  The family's float32 reference scores the
# same batch on the same parameters, its backward pass written out.  The
# three tolerances, and the readings each was set from, are the family's
# (`families/<name>.py`: LOGPROB_RMS_TOL, GRAD_NORM_RTOL, LOSS_RTOL).


def judge(program: dict, reference: dict, family) -> list[str]:
    """Problems of the program's reading of the check batch against the
    reference's, by the family's tolerances.  Both: {"loss", "grad_norm",
    "logprobs" [b, s]}."""
    import numpy as np

    problems = []
    d = (np.asarray(program["logprobs"], np.float64)
         - np.asarray(reference["logprobs"], np.float64))
    rms = float(np.sqrt(np.mean(d * d)))
    if not rms <= family.LOGPROB_RMS_TOL:
        problems.append(
            f"per-position log-probabilities differ from the reference's "
            f"by {rms:.4f} rms, tolerance {family.LOGPROB_RMS_TOL}")
    for key, tol in (("loss", family.LOSS_RTOL),
                     ("grad_norm", family.GRAD_NORM_RTOL)):
        rel = abs(program[key] - reference[key]) / abs(reference[key])
        if not rel <= tol:
            problems.append(
                f"the step's {key} {program[key]} against the reference's "
                f"{reference[key]}: {rel:.2e} relative, tolerance {tol}")
    return problems


def loop(config: dict) -> dict:
    import jax
    import numpy as np

    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.train import step as train_step

    from . import spec, trace_reduce
    from .replica import _device_info, seed_key

    rec: dict = {"problems": [], "times": {}}
    t = config["train"]
    fam = spec.load_family(config["family"], "train")
    model, seed = config["model"], int(config["seed"])
    seconds, every = float(config["seconds"]), int(t["loss_every"])
    devs = jax.devices()
    rec["device"] = _device_info()
    if rec["device"]["platform"] != "tpu" and not config["rehearse"]:
        raise RuntimeError(f"jax came up on {rec['device']['platform']!r}, "
                           "not 'tpu': no measurement without the chip")
    if len(devs) != int(config["chips"]):
        raise RuntimeError(f"{len(devs)} devices, the cell asks for "
                           f"{config['chips']}")
    cfg = fam.program_config(model, max_seq=t["seq"],
                             remat_mode=t["remat_mode"])
    mesh = create_mesh(MeshConfig(**t["mesh"]), devices=devs)
    optimizer = getattr(train_step, t["optimizer"])(
        total_steps=t["total_steps"])

    t0 = time.perf_counter()
    state = train_step.sharded_init(seed_key(seed), cfg, optimizer, mesh)
    jax.block_until_ready(state)
    rec["times"]["sharded_init_s"] = time.perf_counter() - t0

    rng = np.random.default_rng([seed, 5])
    toks = rng.integers(0, fam.vocab_size(model),
                        (config["distinct_batches"], t["batch"],
                         t["seq"] + 1), dtype=np.int32)
    b_sh = train_step.batch_shardings(mesh)
    batches = [{"inputs": jax.device_put(x[:, :-1], b_sh),
                "targets": jax.device_put(x[:, 1:], b_sh)} for x in toks]

    # the check batch, scored by the reference before the step donates
    # the state and by the program's forward
    chk = config["check"]
    n_seq, n_pos = int(chk["sequences"]), min(int(chk["positions"]), t["seq"])
    check_toks = toks[0, :n_seq, :n_pos + 1]
    t0 = time.perf_counter()
    ref = fam.reference().loss_and_gradient(
        state.params, check_toks[:, :-1], check_toks[:, 1:], model)
    ref["logprobs"] = np.asarray(ref["logprobs"])
    rec["times"]["reference_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        logp = jax.jit(lambda p, x: train_step.model_module(cfg)
                       .token_logprobs(p, x, cfg))(
            state.params, jax.device_put(check_toks, b_sh))
    prog = {"logprobs": np.asarray(logp, np.float32)}

    step_fn = train_step.sharded_train_step(cfg, optimizer, mesh)
    losses = []
    with jax.set_mesh(mesh):
        state, m = step_fn(state, {
            "inputs": jax.device_put(check_toks[:, :-1], b_sh),
            "targets": jax.device_put(check_toks[:, 1:], b_sh)})
        prog.update(loss=float(m["loss"]), grad_norm=float(m["grad_norm"]))
        rec["times"]["program_check_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        for i in range(int(config["warm_steps"])):     # compile + settle
            state, m = step_fn(state, batches[i % len(batches)])
            losses.append(float(m["loss"]))
        rec["times"]["warm_steps_s"] = time.perf_counter() - t0
        n_done = int(config["warm_steps"])

        def run_steps(n):
            nonlocal state, n_done
            m = None
            for _ in range(n):
                state, m = step_fn(state, batches[n_done % len(batches)])
                n_done += 1
            return m

        # ---------------------------------------------------- window
        rec["window_wall0"] = time.time()
        w0 = time.perf_counter()
        losses.append(float(run_steps(every)["loss"]))  # a trainer that logs
        pace = (time.perf_counter() - w0) / every
        total = max(every, int(seconds / pace))
        steps = every
        if config["trace"]:
            trace_dir = os.path.join(config["root"], ".bench_trace",
                                     config["cell"])
            trace_reduce.start(trace_dir)
            tw0 = time.perf_counter()
            jax.block_until_ready(run_steps(3)["loss"])
            rec["trace_steps"] = 3
            rec["trace_steps_s"] = time.perf_counter() - tw0
            jax.profiler.stop_trace()
            steps += 3
        while steps < total:
            n = min(every, total - steps)
            m = run_steps(n)
            steps += n
            if n == every:
                losses.append(float(m["loss"]))
        jax.block_until_ready(state)
        rec["window_s"] = time.perf_counter() - w0
        rec["steps"] = steps
        losses.append(float(m["loss"]))

    rec["losses"] = losses
    if not all(math.isfinite(x) for x in losses):
        rec["problems"].append(f"a loss is not finite: {losses}")
    elif not losses[-1] < losses[0]:
        rec["problems"].append(
            f"the loss did not fall over the run: {losses}")
    rec["problems"] += judge(prog, ref, fam)
    d = prog["logprobs"] - ref["logprobs"]
    rec["check"] = {
        "sequences": n_seq, "positions": n_pos,
        "program": {k: prog[k] for k in ("loss", "grad_norm")},
        "reference": {k: ref[k] for k in ("loss", "grad_norm")},
        "logprob_rms_diff": float(np.sqrt(np.mean(d * d))),
        "logprob_max_diff": float(np.abs(d).max())}
    rec["device"] = _device_info()
    if config["trace"]:
        dump = (os.path.join(config["root"], "chiprun_out",
                             f"trace_dump.{config['cell']}.txt")
                if config["dump_trace"] else None)
        rec["trace"] = trace_reduce.reduce_dir(trace_dir, dump)
    return rec


def child_main() -> None:
    """The child process of the train cell: the ONE process that touches
    the chips.  Reads the loop's config from stdin, prints the
    record as its last line."""
    import json
    import sys

    rec = loop(json.load(sys.stdin))
    print("BENCH_CHILD_RECORD " + json.dumps(rec), flush=True)
