"""One run of a serve cell, driver side.  This process never initializes
a JAX backend: the chip belongs to the node's device worker, where the
replica lives."""
from __future__ import annotations

import os
import time

from . import cluster, loadgen, spec, stats

# the served tokens the reference scores; the tolerance on their
# teacher-forced logit gap is the family's (`REFERENCE_GAP_TOL`)
SAMPLE_REQUESTS = 4
SAMPLE_NEW_TOKENS = 24


def rehearsal_cell(cell: spec.Cell) -> None:
    """Shrink a cell IN MEMORY to debug-sized shapes for the CPU
    rehearsal: the family's tiny widths, every length a sixteenth.  A
    rehearsal walks the code and can never report `correct: true`."""
    cell.family.rehearsal(cell.config)
    eng = cell.config["engine"]
    eng.update(max_len=eng["max_len"] // 16, page_size=eng["page_size"] // 16)
    t = cell.traffic
    for key in ("prompt_len", "output_len"):
        d = t[key]
        for f in ("median", "low", "high", "value"):
            if f in d:
                d[f] = max(2, d[f] // 16)
        d["clip"] = [max(2, d["clip"][0] // 16), max(3, d["clip"][1] // 16)]
    t["ramp_s"] = min(t.get("ramp_s", 0.0), 2.0)
    t["drain_s"] = 30.0


def _sender(handle, transport: str, traced: bool):
    """send(req, clock): one request through the serve handle, timed at
    the client."""
    from ray_tpu import tracing

    if transport == "stream":
        handle = handle.options(method_name="stream", stream=True)

    def send(r: loadgen.Request, clock: loadgen.Clock) -> None:
        body = {"prompt": r.prompt, "max_new_tokens": r.out_len}
        if traced:
            # the engine's spans of this request carry this trace id
            with tracing.span("bench.request"):
                r.trace_id = tracing.current()[0]
                resp = handle.remote(body)
        else:
            resp = handle.remote(body)
        if transport == "stream":
            toks = []
            for tok in resp:
                now = clock.now()
                if not toks:
                    r.first_s = now
                r.last_s = now
                toks.append(int(tok))
            r.tokens, r.n_tokens = toks, len(toks)
        else:
            out = resp.result(timeout_s=600.0)
            r.last_s = clock.now()
            r.tokens = [int(t) for t in out["tokens"]]
            r.n_tokens = len(r.tokens)

    return send


def run(cell: spec.Cell, args, log, t_process_wall: float) -> dict:
    """Returns the run's record: requests, window, spans, trace, stats."""
    import ray_tpu
    from ray_tpu import serve

    cfg, t = cell.config, cell.traffic
    model = cell.family.published(cfg)
    vocab = cell.family.vocab_size(model)
    eng_kw = dict(cfg["engine"], paged=True)
    transport = t.get("transport", "stream")
    seconds = float(args.seconds)
    ramp = float(t.get("ramp_s", 0.0))
    traced = bool(args.trace)

    # the schedule is made before anything is started: a pure function
    # of (traffic file, seed, seconds)
    if cell.loop == "open":
        reqs = loadgen.open_schedule(t, vocab, args.seed, seconds,
                                     rate_rps=args.rate)
    else:
        reqs = loadgen.closed_pool(t, vocab, args.seed)
    lo, hi = t["prompt_len"]["clip"]
    sample = _sample_requests(vocab, args.seed, lo, hi, eng_kw["max_len"])
    log(step="schedule", loop=cell.loop, n=len(reqs), transport=transport,
        rate_rps=args.rate or (t.get("arrivals") or {}).get("rate_rps"),
        clients=t.get("clients"),
        prompt_tokens=sum(len(r.prompt) for r in reqs),
        output_tokens=sum(r.out_len for r in reqs))

    run_rec: dict = {"cell": cell, "model": model, "engine": eng_kw,
                     "seconds": seconds, "setup": {}, "trace": None,
                     "spans": [], "problems": []}
    node_ids: list[str] = []
    t0 = time.perf_counter()
    ray_tpu.init()
    try:
        node_ids = [n["node_id"] for n in ray_tpu.nodes()]
        total = ray_tpu.cluster_resources()
        run_rec["setup"]["init_s"] = time.perf_counter() - t0
        if not total.get("TPU"):
            raise RuntimeError("the node advertises no TPU resource")
        t0 = time.perf_counter()
        app = serve.deployment(BenchServer()).options(
            name="llm", ray_actor_options={"num_tpus": cell.chips},
            **cfg["deployment"]).bind(model, family=cell.family_name,
                                      seed=args.seed, **eng_kw)
        handle = serve.run(app, name="bench", timeout_s=900.0)
        run_rec["setup"]["serve_run_s"] = time.perf_counter() - t0

        def call(method, *a, timeout_s=900.0):
            return handle.options(method_name=method).remote(*a).result(
                timeout_s=timeout_s)

        dev = call("bench_probe")
        run_rec["device"] = dev
        log(step="serve_run", wall_s=run_rec["setup"]["serve_run_s"], **dev)
        if dev["platform"] != "tpu" and not args.rehearse:
            raise RuntimeError(
                f"the replica's jax came up on {dev['platform']!r}, not "
                "'tpu': no measurement without the chip")
        t0 = time.perf_counter()
        warm = call("bench_warmup", lo, hi)
        run_rec["setup"]["warmup_s"] = time.perf_counter() - t0
        log(step="warmup", wall_s=run_rec["setup"]["warmup_s"], **warm)
        stats0 = call("stats")

        # ----------------------------------------------- ramp + window
        clock = loadgen.Clock()
        send = _sender(handle, transport, traced)
        t_win0, t_win1 = ramp, ramp + seconds
        run_rec["window_wall"] = (clock.wall(t_win0), clock.wall(t_win1))
        run_rec["setup_s"] = clock.wall(t_win0) - t_process_wall
        tracer = None
        if traced:
            import threading

            tracer = threading.Thread(
                target=_trace_window, name="tracer", daemon=True,
                args=(call, clock, t_win0 + 0.4 * seconds,
                      min(6.0, 0.25 * seconds), args, run_rec))
            tracer.start()
        drain = float(t.get("drain_s", 60.0))
        if cell.loop == "open":
            loadgen.run_open(reqs, send, clock, t_win1, drain)
            sent = [r for r in reqs if r.sent_s is not None]
        else:
            sent = loadgen.run_closed(reqs, send, clock, int(t["clients"]),
                                      t_win1, drain)
        if tracer is not None:
            tracer.join(timeout=120.0)
        run_rec["all_requests"] = sent
        run_rec["window"] = (t_win0, t_win1)
        stats1 = call("stats")
        run_rec["stats"] = (stats0, stats1)

        # --------------------------- outside the window: correct, spans
        if traced:
            run_rec["spans"] = call("bench_spans")
            wins: dict = {}
            for sp in run_rec["spans"]:
                if sp["name"] == "llm.decode_window":
                    wins[sp["t0"]] = wins.get(sp["t0"], 0) + 1
            if wins:
                log(step="occupancy", decode_windows=len(wins),
                    lanes_active_mean=sum(wins.values()) / len(wins),
                    lanes=eng_kw["max_batch"])
            dump = (os.path.join(spec.ROOT, "chiprun_out",
                                 f"trace_dump.{cell.name}.txt")
                    if args.dump_trace else None)
            try:
                run_rec["trace"] = call("bench_trace_reduce", dump)
            except Exception as e:  # noqa: BLE001 - no trace, no trace metrics
                run_rec["problems"].append(f"trace reduction: {e}"[:300])
        _check_outputs(run_rec, sent, vocab, sample, send, clock, call, log)
        run_rec["device"] = {**dev, **call("bench_device_stats")}
        serve.delete("bench")
        serve.shutdown()
    finally:
        run_rec["killed_at_shutdown"] = cluster.shutdown(node_ids)
    return run_rec


def BenchServer():
    from .replica import BenchLLMServer

    return BenchLLMServer


def _trace_window(call, clock, start_s, length_s, args, run_rec) -> None:
    """Trace `length_s` of the steady window from the process that holds
    the chip."""
    try:
        wait = start_s - clock.now()
        if wait > 0:
            time.sleep(wait)
        trace_dir = os.path.join(spec.ROOT, ".bench_trace",
                                 run_rec["cell"].name)
        w0 = call("bench_trace_start", trace_dir)
        time.sleep(length_s)
        w1 = call("bench_trace_stop")
        run_rec["trace_wall"] = (w0, w1)
    except Exception as e:  # noqa: BLE001 - reported with the run
        run_rec["problems"].append(f"tracing: {type(e).__name__}: {e}"[:300])


def _sample_requests(vocab, seed, lo, hi, max_len) -> list[loadgen.Request]:
    """SAMPLE_REQUESTS requests of one length inside the traffic's own
    range (so their programs are warm), the first sent twice."""
    import numpy as np

    rng = np.random.default_rng([seed, 4])
    n = min(hi, max(lo, 96), max_len - SAMPLE_NEW_TOKENS - 1)
    out = [loadgen.Request(-1 - i, 0.0, rng.integers(0, vocab, n).tolist(),
                           SAMPLE_NEW_TOKENS)
           for i in range(SAMPLE_REQUESTS)]
    out.append(loadgen.Request(-1 - SAMPLE_REQUESTS, 0.0,
                               list(out[0].prompt), SAMPLE_NEW_TOKENS))
    return out


def _check_outputs(run_rec, sent, vocab, sample, send, clock, call,
                   log) -> None:
    """`correct`, decided outside the timed window."""
    import threading

    problems = run_rec["problems"]
    t0, t1 = run_rec["window"]
    cell = run_rec["cell"]
    tol = cell.family.REFERENCE_GAP_TOL
    if cell.loop == "open":
        measured = [r for r in sent if t0 <= r.due_s < t1]
    else:
        measured = [r for r in sent
                    if r.error is not None
                    or (r.last_s is not None and t0 <= r.last_s < t1)]
    run_rec["requests"] = measured
    bad = [r for r in measured if not r.ok]
    if bad:
        problems.append(f"{len(bad)} of {len(measured)} requests failed; "
                        f"first: {bad[0].error or 'wrong token count'}")
    if any(not all(0 <= tok < vocab for tok in (r.tokens or []))
           for r in measured):
        problems.append("a token outside the vocabulary")
    if len({tuple(r.tokens) for r in measured if r.ok}) <= 1 < len(measured):
        problems.append("every request returned the same tokens")
    s0, s1 = run_rec["stats"]
    if s1["overload"]["level"] != 0 or s1["overload"]["sheds"] \
            != s0["overload"]["sheds"]:
        problems.append(f"the overload ladder moved: {s1['overload']}")
    pre = s1["preemptions"] - s0["preemptions"]
    if pre != cell.traffic.get("expect_preemptions", 0):
        problems.append(f"{pre} preemptions in the window, expected "
                        f"{cell.traffic.get('expect_preemptions', 0)}")
    # a compile inside the window shows as a TTFT on the compile scale
    slow = [r for r in measured if r.first_s is not None
            and r.first_s - r.due_s > cell.traffic.get("cold_ttft_s", 3.0)]
    if slow:
        log(step="suspect_compile", n=len(slow),
            worst_ttft_s=max(r.first_s - r.due_s for r in slow))

    threads = [threading.Thread(target=loadgen.guard, args=(send, r, clock),
                                daemon=True) for r in sample]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300.0)
    if not all(r.ok for r in sample):
        problems.append("a sample request failed: "
                        f"{[r.error for r in sample if not r.ok][:1]}")
        return
    # Two identical prompts: identical tokens, or tokens that part at a
    # near-tie of the reference (the two may ride different waves, whose
    # programs round differently; random weights make near-ties).  Both
    # are scored, so a parting that is no near-tie fails below.
    same = sample[0].tokens == sample[-1].tokens
    part = next((i for i, (a, b) in enumerate(
        zip(sample[0].tokens, sample[-1].tokens)) if a != b), None)
    ref = call("bench_reference", [(r.prompt, r.tokens) for r in sample])
    worst = max(max(g) for g in ref["gaps"])
    exact = sum(g == 0.0 for gs in ref["gaps"] for g in gs)
    log(step="reference", worst_logit_gap=worst, tolerance=tol,
        identical_prompts_identical_tokens=same, parted_at=part,
        tokens_scored=sum(len(g) for g in ref["gaps"]),
        tokens_the_reference_also_chose=exact, wall_s=ref["wall_s"])
    if not worst <= tol:
        problems.append(f"a served token's reference logit is {worst:.4f} "
                        f"below the reference maximum (tolerance {tol})")


# --------------------------------------------------- end-to-end metrics
def end_to_end(run_rec: dict, log) -> dict:
    """The serve cells' end-to-end metrics, all from the client's clock,
    over ALL requests of the window."""
    reqs = [r for r in run_rec["requests"] if r.ok]
    t0, t1 = run_rec["window"]
    out = {"setup_s": run_rec["setup_s"]}
    ttft = [(r.first_s - r.due_s) * 1e3 for r in reqs
            if r.first_s is not None]
    tpot = [(r.last_s - r.first_s) * 1e3 / (r.n_tokens - 1) for r in reqs
            if r.first_s is not None and r.n_tokens > 1]
    if ttft:
        out["ttft_p50_ms"] = stats.median(ttft)
        out["ttft_p90_ms"] = stats.percentile(ttft, 90)
        # the mean and the time per 1,000 prompt tokens are printed to
        # be judged as steadier statistics of TTFT (PERF.md); no metric
        ktok = sum(len(r.prompt) for r in reqs if r.first_s is not None) / 1e3
        log(step="ttft", n=len(ttft), p50_ms=out["ttft_p50_ms"],
            p90_ms=out["ttft_p90_ms"], max_ms=max(ttft),
            mean_ms=sum(ttft) / len(ttft), ms_per_ktok=sum(ttft) / ktok)
    if run_rec["cell"].loop == "open":
        # a starved generator must not read as a fast server
        late = stats.lateness_ms([r.due_s for r in reqs],
                                 [r.sent_s for r in reqs])
        log(step="lateness", p99_ms=stats.percentile(late, 99),
            max_ms=max(late))
    if ttft and run_rec["cell"].loop == "open":
        # the knee criterion's inputs: a backlog that grows shows as a
        # TTFT that grows through the window
        third = (t1 - t0) / 3.0
        by_third = [[(r.first_s - r.due_s) * 1e3 for r in reqs
                     if r.first_s is not None
                     and t0 + i * third <= r.due_s < t0 + (i + 1) * third]
                    for i in range(3)]
        log(step="thirds", ttft_p50_ms_by_third=[
            stats.median(v) if v else None for v in by_third])
    if tpot:
        out["tpot_p50_ms"] = stats.median(tpot)
        out["tpot_p90_ms"] = stats.percentile(tpot, 90)
        log(step="tpot", n=len(tpot), p50_ms=out["tpot_p50_ms"],
            p90_ms=out["tpot_p90_ms"], max_ms=max(tpot))
    done = [r for r in reqs if t0 <= r.last_s < t1]
    out["serve_tok_s"] = sum(r.n_tokens for r in done) / (t1 - t0)
    # tokens completed in each tenth of the window: a stall shows as an
    # empty tenth, a slow regime as ten low ones
    tenth = (t1 - t0) / 10.0
    by_tenth = [0] * 10
    for r in done:
        by_tenth[min(9, int((r.last_s - t0) / tenth))] += r.n_tokens
    log(step="throughput", completed_in_window=len(done),
        serve_tok_s=out["serve_tok_s"], tokens_by_tenth=by_tenth,
        in_flight_at_window_end=sum(
            1 for r in run_rec["all_requests"]
            if r.sent_s < t1 and (r.last_s is None or r.last_s >= t1)))
    return out
