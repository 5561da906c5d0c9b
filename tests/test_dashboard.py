"""Dashboard HTTP API tests.

Mirrors ray: python/ray/dashboard/modules/*/tests (REST endpoints against
a live cluster) — here against the shared single-node runtime with the
dashboard on an ephemeral port.
"""
import json
import urllib.request

import pytest

import ray_tpu


@pytest.fixture
def dash(ray_shared):
    from ray_tpu.dashboard import start_dashboard

    head = start_dashboard(port=0)
    yield head
    head.stop()


def _get(url: str):
    with urllib.request.urlopen(url, timeout=30) as resp:
        body = resp.read().decode()
        ctype = resp.headers.get("Content-Type", "")
    return body, ctype


def test_healthz_and_version(dash):
    body, _ = _get(dash.url + "/api/healthz")
    assert body == "success"
    body, _ = _get(dash.url + "/api/version")
    assert "version" in json.loads(body)


def test_nodes_and_actors_endpoints(dash):
    @ray_tpu.remote
    class Pinger:
        def ping(self):
            return "pong"

    p = Pinger.remote()
    assert ray_tpu.get(p.ping.remote()) == "pong"

    body, _ = _get(dash.url + "/api/v0/nodes")
    nodes = json.loads(body)["data"]["nodes"]
    assert any(n["state"] == "ALIVE" for n in nodes)

    body, _ = _get(dash.url + "/api/v0/actors")
    actors = json.loads(body)["result"]
    assert any(a["state"] == "ALIVE" for a in actors)
    ray_tpu.kill(p)


def test_tasks_and_summary(dash):
    @ray_tpu.remote
    def tracked():
        return 1

    ray_tpu.get([tracked.remote() for _ in range(3)])
    # Task events flush to the controller periodically — poll.
    import time

    events = []
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        body, _ = _get(dash.url + "/api/v0/tasks")
        events = json.loads(body)["result"]
        if len(events) >= 3:
            break
        time.sleep(0.3)
    assert len(events) >= 3
    body, _ = _get(dash.url + "/api/v0/tasks/summarize")
    assert "cluster" in json.loads(body)["result"]


def test_index_metrics_timeline(dash):
    body, ctype = _get(dash.url + "/")
    assert "ray-tpu" in body and "text/html" in ctype
    # The SPA frontend (ray: dashboard/client) + its script load.
    assert 'src="app.js"' in body
    js, jstype = _get(dash.url + "/app.js")
    assert "javascript" in jstype and "/api/v0/nodes" in js
    legacy, _ = _get(dash.url + "/legacy")
    assert "ray-tpu" in legacy
    body, ctype = _get(dash.url + "/metrics")
    assert "ray_tpu_cluster_alive_nodes" in body
    body, _ = _get(dash.url + "/api/v0/timeline")
    trace = json.loads(body)
    assert isinstance(trace, list)


def test_jobs_rest_roundtrip(dash):
    from ray_tpu.job_submission import JobSubmissionClient

    # HTTP transport — exactly how the reference's SDK talks to it.
    cli = JobSubmissionClient(dash.url)
    jid = cli.submit_job(entrypoint="python -c \"print('rest-ok')\"")
    status = cli.wait_until_finished(jid, timeout_s=120)
    assert status == "SUCCEEDED"
    assert "rest-ok" in cli.get_job_logs(jid)
    jobs = cli.list_jobs()
    assert any(j["job_id"] == jid for j in jobs)


def test_traces_endpoint_formats(dash):
    """Flight-recorder harvest route (ISSUE 10): /api/v0/traces merges
    every process's span ring, filters by ?trace_id=, and exports the
    Chrome-trace / OTLP document shapes."""
    from ray_tpu import tracing

    @ray_tpu.remote
    def traced_fn():
        return 41

    with tracing.span("dash.req") as _:
        ctx = tracing.current()
        assert ray_tpu.get(traced_fn.remote()) == 41
    body, ctype = _get(dash.url + f"/api/v0/traces?trace_id={ctx[0]}")
    assert "application/json" in ctype
    doc = json.loads(body)
    names = {s["name"] for s in doc["spans"]}
    assert "dash.req" in names
    assert doc["traces"][ctx[0]]["connected"] is True
    body, _ = _get(dash.url
                   + f"/api/v0/traces?trace_id={ctx[0]}&format=chrome")
    chrome = json.loads(body)
    assert all(e["ph"] == "X" for e in chrome["traceEvents"])
    assert any(e["name"] == "dash.req" for e in chrome["traceEvents"])
    body, _ = _get(dash.url
                   + f"/api/v0/traces?trace_id={ctx[0]}&format=otlp")
    otlp = json.loads(body)
    spans = otlp["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert spans and all(len(s["traceId"]) == 32 for s in spans)


def test_metrics_histogram_family_exposition(dash):
    """A Histogram metric is exposed as a REAL Prometheus histogram
    family — cumulative _bucket series ending at le="+Inf", plus _sum
    and _count — not a collapsed scalar (the ISSUE 10 small fix; the
    TTFT/TPOT histograms are scrape-broken otherwise)."""
    import time as _time

    from ray_tpu.utils import metrics as um

    h = um.get_or_create(um.Histogram, "dash_test_latency_ms",
                         "exposition test", tag_keys=("leg",),
                         boundaries=[1.0, 10.0, 100.0])
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v, {"leg": "a"})
    deadline = _time.time() + 30
    body = ""
    while _time.time() < deadline:
        body, _ = _get(dash.url + "/metrics")
        if "ray_tpu_dash_test_latency_ms_bucket" in body:
            break
        _time.sleep(1.0)   # metrics flush to the controller KV at ~2s
    name = "ray_tpu_dash_test_latency_ms"
    assert f"# TYPE {name} histogram" in body
    lines = [ln for ln in body.splitlines() if ln.startswith(name)]
    buckets = [ln for ln in lines if "_bucket" in ln
               and 'leg="a"' in ln]
    assert any('le="+Inf"' in ln for ln in buckets), lines
    # Cumulative and complete: +Inf bucket == _count == 4 observations.
    inf = next(ln for ln in buckets if 'le="+Inf"' in ln)
    assert inf.rsplit(" ", 1)[1] == "4"
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts)
    assert any("_sum{" in ln for ln in lines)
    cnt = next(ln for ln in lines if "_count{" in ln
               and 'leg="a"' in ln)
    assert cnt.rsplit(" ", 1)[1] == "4"
    # The serve TTFT family rides the same path once engines flush.
