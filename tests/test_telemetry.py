"""Telemetry timeline + trace critical-path analytics (ISSUE 15).

Covers the tentpole's acceptance shape:
  - timeline ring mechanics: wrap, tag-aware series keys, `since`
    windowing, kill switch, msgpack-clean verb replies;
  - head-side merge: cluster harvest reaches worker processes, the
    merged series keep per-process identity, and an armed
    telemetry.harvest failpoint degrades the merge to
    partial-with-diagnostic, never a hang;
  - critical-path analytics: blocking-chain attribution on synthetic
    trees (sum-invariant, last-finisher-wins), aggregate p50/p99
    decomposition, slowest-N;
  - the e2e acceptance: a PD-disagg serve request's critical path is
    connected across all three processes and its segment sum matches
    the observed wall within tolerance;
  - satellites: harvest dropped-span diagnostics, summarize_tasks
    duration percentiles, dashboard /api/v0/timeseries and
    /api/v0/traces?analyze=1.

Engine tests run debug-scale fp32 on the CPU mesh (the
test_flight_recorder.py discipline).
"""
import json
import time
import urllib.request

import pytest


# ------------------------------------------------------- ring mechanics
def _snaps(value: float, tags: dict | None = None) -> list[dict]:
    """A minimal registry-snapshot list (utils.metrics shape)."""
    return [{"name": "tt_metric", "type": "gauge",
             "tag_keys": list(tags or {}),
             "values": [{"tags": dict(tags or {}), "value": value}]}]


@pytest.fixture
def tel():
    from ray_tpu._private import telemetry as impl

    prev = impl.ENABLED
    impl.set_enabled(True)
    impl.clear()
    yield impl
    impl.set_enabled(prev)
    impl.clear()


def test_ring_wraps_oldest_first(tel):
    cap = tel._CAPACITY
    for i in range(cap + 25):
        tel.record_from_snapshots(_snaps(float(i)))
    st = tel.stats()
    assert st["buffered"] == cap
    assert st["sampled"] == cap + 25
    assert st["dropped"] == 25
    samples = tel.snapshot()
    assert len(samples) == cap
    vals = [s["series"]["tt_metric"] for s in samples]
    # Oldest 25 overwritten; survivors in time order.
    assert vals[0] == 25.0 and vals[-1] == float(cap + 24)
    assert vals == sorted(vals)


def test_tag_aware_series_keys_and_merge(tel):
    tel.record_from_snapshots([
        {"name": "q_depth", "type": "gauge", "tag_keys": ["engine"],
         "values": [{"tags": {"engine": "a"}, "value": 1.0},
                    {"tags": {"engine": "b"}, "value": 2.0}]},
        {"name": "lat_ms", "type": "histogram", "tag_keys": ["engine"],
         "values": [{"tags": {"engine": "a"}, "value": 30.0}],
         "counts": [{"tags": {"engine": "a"}, "counts": [2, 1]}]},
    ])
    series = tel.snapshot()[-1]["series"]
    # Two engines' same-named gauge stay distinct series; histograms
    # contribute _sum and _count totals.
    assert series["q_depth{engine=a}"] == 1.0
    assert series["q_depth{engine=b}"] == 2.0
    assert series["lat_ms_sum{engine=a}"] == 30.0
    assert series["lat_ms_count{engine=a}"] == 3.0

    # Head-side merge keeps per-process identity and time order.
    from ray_tpu import telemetry

    replies = [
        {"proc": "w1", "enabled": True, "samples": [
            {"t": 10.0, "series": {"q_depth{engine=a}": 1.0}},
            {"t": 12.0, "series": {"q_depth{engine=a}": 3.0}}]},
        {"proc": "w2", "enabled": True, "samples": [
            {"t": 11.0, "series": {"q_depth{engine=a}": 7.0}}]},
    ]
    doc = telemetry.merged(replies)
    pts = doc["series"]["q_depth{engine=a}"]
    assert [(p["t"], p["proc"]) for p in pts] == \
        [(10.0, "w1"), (11.0, "w2"), (12.0, "w1")]
    assert telemetry.latest(doc, "q_depth{engine=a}") == 3.0


def test_since_windowing_and_series_filter(tel):
    # Count only OUR snapshots: under full-suite load the process-wide
    # metrics flush loop can sample the (shared) registry mid-test and
    # interleave an unrelated snapshot into the window.
    def mine(**kw):
        return [s for s in tel.snapshot(**kw) if "tt_metric" in s["series"]]

    t0 = time.time()
    tel.record_from_snapshots(_snaps(1.0))
    time.sleep(0.05)
    cut = time.time()
    tel.record_from_snapshots(_snaps(2.0))
    assert len(mine(since=cut)) == 1
    assert len(mine(since=t0)) == 2
    assert tel.snapshot(series=["tt_"])[-1]["series"]
    assert tel.snapshot(series=["zzz_"]) == []
    rep = tel.control({"op": "collect", "since": cut})
    samples = [s for s in rep["samples"] if "tt_metric" in s["series"]]
    assert len(samples) == 1
    assert samples[0]["series"]["tt_metric"] == 2.0


def test_kill_switch_and_live_flip(tel):
    import os

    tel.set_enabled(False)
    assert os.environ["RAY_TPU_TELEMETRY"] == "0"
    n0 = tel.stats()["sampled"]
    tel.record_from_snapshots(_snaps(1.0))
    assert tel.sample_now() is False
    assert tel.stats()["sampled"] == n0
    # Live flip via the verb (same-run A/B).
    tel.control({"op": "enable", "on": True})
    tel.record_from_snapshots(_snaps(2.0))
    assert tel.stats()["sampled"] == n0 + 1


def test_control_verb_roundtrips_msgpack(tel):
    import msgpack

    tel.record_from_snapshots(_snaps(1.5, {"k": "v"}))
    reply = tel.control({"op": "collect"})
    back = msgpack.unpackb(msgpack.packb(reply, use_bin_type=True),
                           raw=False)
    assert back["samples"][-1]["series"]["tt_metric{k=v}"] == 1.5
    assert "boot" in back and back["enabled"] is True
    with pytest.raises(ValueError):
        tel.control({"op": "nonsense"})


def test_facade_reads_live_flag(tel):
    from ray_tpu import telemetry

    tel.set_enabled(False)
    assert telemetry.ENABLED is False
    tel.set_enabled(True)
    assert telemetry.ENABLED is True


def test_rate_sums_across_procs_never_mixes_bases():
    from ray_tpu import telemetry

    doc = {"series": {"c": [
        {"t": 0.0, "v": 0.0, "proc": "w1"},
        {"t": 0.0, "v": 100.0, "proc": "w2"},
        {"t": 10.0, "v": 50.0, "proc": "w1"},
        {"t": 10.0, "v": 200.0, "proc": "w2"},
    ]}}
    # Per-proc deltas: (50-0)/10 + (200-100)/10 — never w1 vs w2.
    assert telemetry.rate(doc, "c", window_s=60.0) == pytest.approx(15.0)


# ------------------------------------------------ critical-path (unit)
def _rec(name, t0, t1, sid, par="", proc="p"):
    return {"name": name, "proc": proc, "sid": sid, "par": par,
            "tid": "T", "t0": t0, "t1": t1, "attrs": {}}


def test_critical_path_last_finisher_wins_and_sums_exactly():
    from ray_tpu import tracing

    t = 1000.0
    spans = [
        _rec("root", t, t + 10, "r"),
        _rec("a", t + 1, t + 4, "a", "r"),          # overlapped by b
        _rec("b", t + 3, t + 9, "b", "r"),          # finishes later
        _rec("b1", t + 3.5, t + 8, "b1", "b"),      # deepest blocker
        _rec("zero", t + 5, t + 5, "z", "b"),       # zero-len child
    ]
    tree = tracing.trace_trees(spans)["T"][0]
    path = tracing.critical_path(tree)
    names = [(s["name"], round(s["t0"] - t, 2), round(s["t1"] - t, 2))
             for s in path]
    assert names == [("root", 0, 1.0), ("a", 1.0, 3.0),
                     ("b", 3.0, 3.5), ("b1", 3.5, 8.0),
                     ("b", 8.0, 9.0), ("root", 9.0, 10.0)], names
    assert sum(s["ms"] for s in path) == pytest.approx(10_000.0)
    # `until` clamps the window (the TTFT-decomposition shape).
    clipped = tracing.critical_path(tree, until=t + 4)
    assert sum(s["ms"] for s in clipped) == pytest.approx(4_000.0)
    assert clipped[-1]["t1"] == t + 4


def test_attribution_skips_disconnected_and_shares_sum():
    from ray_tpu import tracing

    spans = [
        _rec("req", 0.0, 1.0, "r1"),
        _rec("work", 0.2, 0.9, "w1", "r1"),
    ]
    # A second trace with a missing parent → two roots → skipped.
    broken = [dict(s, tid="B", sid=s["sid"] + "b") for s in spans]
    broken[1]["par"] = "missing"
    trees = tracing.trace_trees(spans + broken)
    attr = tracing.attribution(trees)
    assert attr["requests"] == 1
    assert attr["skipped_disconnected"] == 1
    shares = [s["share_pct"] for s in attr["stages"].values()]
    assert sum(shares) == pytest.approx(100.0, abs=0.5)
    assert attr["stages"]["work"]["share_pct"] == pytest.approx(70.0,
                                                                abs=1)
    rows = tracing.slowest(trees, n=5)
    assert len(rows) == 1 and rows[0]["name"] == "req"
    assert rows[0]["path"]


def test_harvest_reports_dropped_spans_as_truncation():
    """Satellite: a wrapped 4096-slot ring reads as TRUNCATED in the
    harvest diagnostics, never as a silently partial tree."""
    from ray_tpu import tracing
    from ray_tpu._private import spans as impl

    impl.clear()
    for _ in range(impl._CAPACITY + 10):
        impl.emit("tt.flood", time.time())
    spans_list, diags = tracing.harvest(with_diagnostics=True)
    assert spans_list
    me = [p for p in diags["procs"] if p["dropped"] > 0]
    assert me, diags["procs"]
    assert diags["dropped_total"] >= 10
    assert diags["truncated"] is True
    impl.clear()
    # Default shape unchanged for existing callers.
    assert isinstance(tracing.harvest(), list)


# ------------------------------------------------- cluster harvest
def test_cluster_timeseries_reaches_workers(ray_shared):
    import ray_tpu
    from ray_tpu import telemetry

    @ray_tpu.remote
    class Meter:
        def bump(self):
            from ray_tpu.utils import metrics as um

            c = um.get_or_create(um.Counter, "tt_worker_bumps",
                                 "test counter", ("who",))
            c.inc(1, {"who": "m"})
            return True

    m = Meter.remote()
    assert ray_tpu.get(m.bump.remote(), timeout=120)
    # fresh=True forces every process to sample before replying, so
    # the 2s cadence never makes this flaky.
    doc = telemetry.timeseries(series=["tt_worker_"], fresh=True)
    pts = doc["series"].get("tt_worker_bumps{who=m}")
    assert pts, doc["series"].keys()
    assert any(p["proc"].startswith("worker:") for p in pts)
    assert doc["diagnostics"] == []
    ray_tpu.kill(m)


def test_harvest_failpoint_degrades_to_partial(ray_shared):
    """telemetry.harvest armed on the agent: the cluster harvest
    completes in bounded time with a per-node diagnostic — partial,
    never a hang."""
    import ray_tpu
    from ray_tpu import telemetry
    from ray_tpu._private.worker import global_worker

    w = global_worker()
    addrs = {n["node_id"]: n["agent_addr"] for n in ray_tpu.nodes()
             if n["state"] == "ALIVE"}
    victim = sorted(addrs)[0]
    w.call(addrs[victim], "failpoints",
           {"op": "set", "spec": "telemetry.harvest=error:RuntimeError"},
           timeout=30.0)
    try:
        t0 = time.time()
        doc = telemetry.timeseries(fresh=True)
        assert time.time() - t0 < 60
        assert doc["diagnostics"], doc
    finally:
        w.call(addrs[victim], "failpoints",
               {"op": "set", "spec": "telemetry.harvest=off"},
               timeout=30.0)
    doc = telemetry.timeseries()
    assert doc["diagnostics"] == []


def test_summarize_tasks_durations(ray_shared):
    import ray_tpu
    from ray_tpu.utils import state

    @ray_tpu.remote
    def tt_sleeper():
        time.sleep(0.05)
        return 1

    assert ray_tpu.get([tt_sleeper.remote() for _ in range(3)],
                       timeout=120) == [1, 1, 1]
    deadline = time.time() + 20
    row = None
    while time.time() < deadline:
        summary = state.summarize_tasks()["cluster"]["summary"]
        row = next((v for k, v in summary.items()
                    if "tt_sleeper" in k), None)
        if row and row.get("duration_ms") \
                and row["states"].get("FINISHED", 0) >= 3:
            break
        time.sleep(0.3)     # events flush on a period
    assert row, summary
    assert row["states"]["FINISHED"] >= 3
    d = row["duration_ms"]
    assert d["count"] >= 3
    assert d["p95"] >= d["p50"] >= 50.0 * 0.5   # slept 50ms per task


# -------------------------------------------------- dashboard surfaces
@pytest.fixture
def dash(ray_shared):
    from ray_tpu.dashboard import start_dashboard

    head = start_dashboard(port=0)
    yield head
    head.stop()


def _get(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=60) as resp:
        return json.loads(resp.read().decode())


def test_dashboard_timeseries_endpoint(dash, tel):
    from ray_tpu.utils import metrics as um

    g = um.get_or_create(um.Gauge, "tt_dash_gauge", "g", ("k",))
    g.set(42.0, {"k": "x"})
    doc = _get(dash.url + "/api/v0/timeseries?series=tt_dash_"
               "&fresh=1")["result"]
    pts = doc["series"].get("tt_dash_gauge{k=x}")
    assert pts and pts[-1]["v"] == 42.0
    # ?since= relative form: everything is within the last hour...
    doc = _get(dash.url + "/api/v0/timeseries?series=tt_dash_"
               "&since=3600")["result"]
    assert doc["series"]
    # ...and nothing is newer than "0 seconds ago".
    doc = _get(dash.url + "/api/v0/timeseries?series=tt_dash_"
               "&since=0")["result"]
    assert not doc["series"]


def test_dashboard_traces_analyze(dash):
    from ray_tpu import tracing

    with tracing.span("tt.dash_req"):
        with tracing.span("tt.dash_stage"):
            time.sleep(0.02)
    # High limit: the shared ring holds every prior test's traces and
    # slowest-N is global — the fresh trace must not fall off the list.
    doc = _get(dash.url + "/api/v0/traces?analyze=1&limit=500")
    assert "diagnostics" in doc
    assert "dropped_total" in doc["diagnostics"]
    ana = doc["analysis"]
    assert ana["attribution"]["requests"] >= 1
    assert any(r["name"] == "tt.dash_req" for r in ana["slowest"])
    row = next(r for r in ana["slowest"] if r["name"] == "tt.dash_req")
    assert sum(s["ms"] for s in row["path"]) == pytest.approx(
        row["ms"], rel=0.01)
    # ?match= scopes the analysis to one root-name family: the
    # attribution no longer mixes in control-plane/task traces.
    doc = _get(dash.url + "/api/v0/traces?analyze=1&limit=5"
               "&match=tt.dash_req")
    ana = doc["analysis"]
    assert ana["attribution"]["requests"] == 1
    assert set(ana["attribution"]["stages"]) <= {"tt.dash_req",
                                                 "tt.dash_stage"}
    assert [r["name"] for r in ana["slowest"]] == ["tt.dash_req"]


# --------------------------------------- PD-disagg e2e (acceptance)
@pytest.fixture(scope="module")
def small():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=128, max_seq=128, remat=False, dtype=jnp.float32)
    return cfg


PROMPT = [(i * 11 + 5) % 127 + 1 for i in range(21)]


@pytest.fixture
def serve_ray(small):
    import ray_tpu
    from ray_tpu import serve

    if not ray_tpu.is_initialized():
        ray_tpu.init(resources={"CPU": 4})
    serve.start()
    yield serve
    serve.shutdown()


def test_pd_disagg_critical_path_across_three_processes(serve_ray,
                                                        small):
    """The acceptance criterion: a disaggregated request's critical
    path is connected across the router, prefill and decode processes,
    and its segment sum matches the observed wall within tolerance
    (the chain partitions the root interval exactly; the root tracks
    the driver-observed wall)."""
    from ray_tpu import tracing
    from ray_tpu.serve.llm import LLMServer

    cfg = small
    ekw = dict(max_batch=2, max_len=64, page_size=8, steps_per_sync=4,
               seed=11)
    Decode = serve_ray.deployment(LLMServer).options(
        name="decode", num_replicas=1, max_ongoing_requests=4)
    decode_app = Decode.bind(cfg, role="decode", **ekw)
    Prefill = serve_ray.deployment(LLMServer).options(
        name="prefill", num_replicas=1, max_ongoing_requests=4)
    app = Prefill.bind(cfg, role="prefill",
                       decode_deployment=decode_app, **ekw)
    h = serve_ray.run(app, name="tt_pd", route_prefix="/ttpd")
    try:
        t_wall0 = time.time()
        with tracing.span("tt.cp_request") as _:
            ctx = tracing.current()
            out = h.remote({"prompt": PROMPT[:13],
                            "max_new_tokens": 6}).result(timeout_s=300)
        wall_ms = (time.time() - t_wall0) * 1000.0
        assert out.get("disagg") is True
        # Spans from the replicas' export threads land async.
        deadline = time.time() + 60
        while True:
            spans = tracing.harvest(trace_id=ctx[0])
            if tracing.connected(spans, ctx[0]) and \
                    {"llm.prefill", "llm.kv_import"} <= \
                    {s["name"] for s in spans} or \
                    time.time() > deadline:
                break
            time.sleep(0.5)
        assert tracing.connected(spans, ctx[0]), [
            (s["name"], s["proc"], s["sid"], s["par"]) for s in spans]
        tree = tracing.trace_trees(spans)[ctx[0]][0]
        path = tracing.critical_path(tree)
        # The chain itself crosses all three processes.
        assert len({seg["proc"] for seg in path}) >= 3, [
            (seg["name"], seg["proc"]) for seg in path]
        # Exact partition of the root interval...
        root = tree["span"]
        root_ms = (root["t1"] - root["t0"]) * 1000.0
        assert sum(seg["ms"] for seg in path) == pytest.approx(
            root_ms, rel=0.01)
        # ...which tracks the driver-observed wall (the span closes
        # inside the timed window; generous bound for this noisy box).
        assert root_ms <= wall_ms + 50.0
        assert root_ms >= 0.25 * wall_ms, (root_ms, wall_ms)
        # The engine stages the ISSUE names show up on the chain.
        chain_names = {seg["name"] for seg in path}
        assert "llm.prefill" in chain_names or \
            "llm.decode_window" in chain_names, chain_names
        attr = tracing.attribution({ctx[0]: [tree]})
        assert attr["requests"] == 1
        assert sum(s["share_pct"] for s in
                   attr["stages"].values()) == pytest.approx(100.0,
                                                             abs=1.0)
    finally:
        serve_ray.delete("tt_pd")
