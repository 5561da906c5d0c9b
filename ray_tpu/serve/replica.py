"""Replica actor: hosts one copy of a deployment's user callable.

Analog of ray: python/ray/serve/_private/replica.py (ReplicaActor).  Async
actor: requests overlap up to max_ongoing_requests; sync user code runs on a
thread pool so the event loop keeps serving queue-length probes (the same
reason the reference's replica is an asyncio actor).
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import dataclasses
import inspect
import os
import time
from typing import Any


@dataclasses.dataclass
class ReplicaContext:
    """Identity of the replica the calling code runs inside (ray:
    serve.get_replica_context / ReplicaContext)."""
    app_name: str
    deployment: str
    replica_tag: str
    servable_object: Any


# Per-call context (ContextVar: carries into the task handling one
# request and, via copy_context().run, into pool threads) with a
# process-global fallback for __init__-time calls.  The fallback alone
# is wrong when one process hosts several replicas — e.g. every TPU
# deployment's replicas share the host's single device worker — because
# the last-constructed replica would clobber the rest.
import contextvars

_ctx_var: contextvars.ContextVar = contextvars.ContextVar(
    "raytpu_serve_replica_ctx", default=None)
_current_context: ReplicaContext | None = None

_METRICS = None


def _replica_metrics():
    """Per-deployment request series (utils.metrics registry → the
    telemetry timeline + dashboard /metrics): req/s and live queue
    depth for deployments that run no LLM engine (the engine exports
    its own richer serve_llm_* series)."""
    global _METRICS
    if _METRICS is None:
        from ray_tpu.utils import metrics as um

        # (app, deployment, replica): deployment names default to the
        # class name, so two apps' same-named deployments would
        # otherwise merge into one series (the serve_prefix_tier2_bytes
        # precedent) — and the replica tag keeps N replicas' gauges
        # distinct even when they share one process (TPU deployments
        # co-host every replica on the device worker); the reader sums
        # per-replica latest values, never trusts one series key to
        # mean "the deployment".
        tk = ("app", "deployment", "replica")
        _METRICS = {
            "processed": um.get_or_create(
                um.Counter, "serve_replica_processed",
                "Requests completed by this replica", tk),
            "ongoing": um.get_or_create(
                um.Gauge, "serve_replica_ongoing",
                "Requests queued + executing on this replica", tk),
            "rejected": um.get_or_create(
                um.Counter, "serve_replica_rejected",
                "Requests rejected by bounded-queue admission", tk),
        }
    return _METRICS


def get_current_context() -> ReplicaContext | None:
    return _ctx_var.get() or _current_context


class Replica:
    """Created via ActorClass(Replica).options(max_concurrency=...)."""

    def __init__(self, cls, init_args: tuple, init_kwargs: dict,
                 max_ongoing_requests: int, user_config: Any = None,
                 app_name: str = "default", deployment: str = "",
                 max_queued_requests: int = -1):
        self._cls = cls
        self._max_ongoing = max_ongoing_requests
        self._num_ongoing = 0
        self._num_processed = 0
        # Bounded admission queue (overload control): requests waiting
        # past max_ongoing_requests count against this budget; beyond
        # it (per priority tier) the request rejects EARLY with
        # ServeOverloadedError instead of queueing unboundedly.
        # -1 = default bound of 2 x max_ongoing; kill switch
        # RAY_TPU_SERVE_ADMISSION=0 restores unbounded queues.
        self._max_queued = (2 * max_ongoing_requests
                            if max_queued_requests < 0
                            else max_queued_requests)
        self._num_rejected = 0
        # Recent queue-wait samples (ms, slot-acquisition wait) — the
        # non-LLM deployment's SLO signal for the controller's scaling
        # loop (LLM engines report their own richer window via stats).
        # Age-bounded: a spike's tail must not report its p99 forever.
        from ray_tpu.serve import slo

        self._queue_waits = slo.LatencyWindow(maxlen=256)
        # EWMA service seconds — sizes ServeOverloadedError.retry_after_s.
        self._svc_ewma_s = 0.0
        # Replica-side concurrency bound: routers cap dispatch too, but
        # multiple handles can race past their local counts (ray: replica
        # enforces max_ongoing_requests itself).  Bounds async handlers as
        # well — the thread pool only bounds sync ones.
        self._slots = asyncio.Semaphore(max_ongoing_requests)
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(2, max_ongoing_requests),
            thread_name_prefix="serve-call")
        import ray_tpu

        global _current_context
        ctx = ray_tpu.get_runtime_context()
        self._context = ReplicaContext(
            app_name=app_name, deployment=deployment,
            replica_tag=ctx.get_actor_id() or "", servable_object=None)
        _current_context = self._context
        token = _ctx_var.set(self._context)
        try:
            self._instance = cls(*init_args, **init_kwargs)
        finally:
            _ctx_var.reset(token)
        self._context.servable_object = self._instance
        if user_config is not None:
            self._reconfigure_sync(user_config)

    def _reconfigure_sync(self, user_config: Any) -> None:
        fn = getattr(self._instance, "reconfigure", None)
        if fn is not None:
            fn(user_config)

    async def reconfigure(self, user_config: Any) -> None:
        """Apply a new user_config without restarting (ray: replica.py
        reconfigure path driven by DeploymentState on config-only changes)."""
        fn = getattr(self._instance, "reconfigure", None)
        if fn is None:
            return
        if inspect.iscoroutinefunction(fn):
            await fn(user_config)
        else:
            await asyncio.get_running_loop().run_in_executor(
                self._pool, fn, user_config)

    def _admit_or_reject(self, priority, args: tuple,
                         kwargs: dict) -> None:
        """Bounded-queue admission decision (overload control): a
        request arriving while `max_queued_requests` others already
        wait for a slot rejects EARLY with a typed, retriable
        ServeOverloadedError — bounded queue wait instead of a timeout
        storm.  Priority tiers: HIGH may use 2x the budget, LOW half
        (serve/slo.py queue_budget).  Runs BEFORE _num_ongoing is
        incremented, so a rejected request never pollutes the router /
        autoscaler load signal."""
        from ray_tpu.serve import slo

        if not slo.admission_on():
            return
        budget = slo.queue_budget(
            slo.request_priority(priority, args, kwargs),
            self._max_queued)
        # Reject iff the tier's queue budget is consumed: compare the
        # FULL ongoing count so budget 0 ('no queue') still admits to
        # free execution slots (queued alone can't tell empty from
        # exactly-full).
        if self._num_ongoing < self._max_ongoing + budget:
            return
        queued = max(0, self._num_ongoing - self._max_ongoing)
        self._num_rejected += 1
        try:
            _replica_metrics()["rejected"].inc(1, self._metric_tags())
        except Exception:  # noqa: BLE001 - metrics never block serving
            pass
        from ray_tpu.exceptions import ServeOverloadedError

        # How long until a queue slot plausibly frees: the wave ahead
        # of this request, served max_ongoing-wide at the EWMA service
        # time.
        retry = (queued + 1) * max(self._svc_ewma_s, 0.01) \
            / max(1, self._max_ongoing)
        raise ServeOverloadedError(
            "replica admission queue full",
            deployment=self._context.deployment,
            queue_depth=queued,
            retry_after_s=round(min(30.0, max(0.05, retry)), 3))

    async def handle_request(self, method: str, args: tuple,
                             kwargs: dict,
                             priority: int | None = None) -> Any:
        """Execute one request (ray: replica.py handle_request).
        `_num_ongoing` counts queued + executing — the queue-length signal
        the router and autoscaler consume."""
        from ray_tpu import failpoints

        if failpoints.ACTIVE:
            # Admission-window failpoint: latency/queue-full injection
            # BEFORE the bounded-queue decision and the ongoing count
            # (serve.admit=delay:... backs up the queue; =error:
            # ServeOverloadedError forges a rejection).
            await failpoints.fire_async("serve.admit")
        self._admit_or_reject(priority, args, kwargs)
        self._num_ongoing += 1
        self._observe_load()
        from ray_tpu import tracing

        t_adm = time.time() if tracing.ENABLED else 0.0
        t_q0 = time.perf_counter()
        try:
            async with self._slots:
                self._queue_waits.observe(
                    "queue", (time.perf_counter() - t_q0) * 1000.0)
                # Flight recorder: how long this request waited for a
                # replica slot (max_ongoing_requests backpressure) —
                # the replica-side "admit" stage of the serve timeline.
                # Context: the handler task's adopted trace (async
                # actor), so it lands in the request's own trace.
                # The t_adm guard skips requests that entered before a
                # LIVE recorder flip (t_adm == 0.0 would record an
                # epoch-0 span).
                if tracing.ENABLED and t_adm:
                    tracing.emit("serve.admit", t_adm,
                                 attrs={"deployment":
                                        self._context.deployment})
                # Failpoint window: the request is admitted but the user
                # callable has not run (crash = replica dies mid-request;
                # the handle must requeue to another replica).
                if failpoints.ACTIVE:
                    await failpoints.fire_async("serve.replica_call")
                target = getattr(self._instance, method)
                token = _ctx_var.set(self._context)
                t_svc0 = time.perf_counter()
                try:
                    if inspect.iscoroutinefunction(target):
                        return await target(*args, **kwargs)
                    # copy_context carries the replica identity into the
                    # pool thread (run_in_executor alone does not).
                    call_ctx = contextvars.copy_context()
                    return await asyncio.get_running_loop().run_in_executor(
                        self._pool,
                        lambda: call_ctx.run(target, *args, **kwargs))
                finally:
                    _ctx_var.reset(token)
                    dur = time.perf_counter() - t_svc0
                    self._svc_ewma_s = dur if not self._svc_ewma_s \
                        else 0.8 * self._svc_ewma_s + 0.2 * dur
        finally:
            self._num_ongoing -= 1
            self._num_processed += 1
            self._observe_load(done=True)

    def _metric_tags(self) -> dict:
        return {"app": self._context.app_name,
                "deployment": self._context.deployment,
                "replica": (self._context.replica_tag or "")[:12]}

    def _observe_load(self, done: bool = False) -> None:
        """Mirror the live queue depth (and completions) into the
        per-replica metric series the telemetry timeline samples."""
        try:
            m = _replica_metrics()
            tags = self._metric_tags()
            m["ongoing"].set(float(self._num_ongoing), tags)
            if done:
                m["processed"].inc(1, tags)
        except Exception:  # noqa: BLE001 - metrics never block serving
            pass

    def handle_request_streaming(self, method: str, args: tuple,
                                 kwargs: dict,
                                 priority: int | None = None):
        """Streaming request: a sync generator the caller invokes with
        num_returns="streaming" — items ship to the consumer as the user
        generator produces them (ray: replica ASGI streaming path).  A
        non-generator result streams as a single item."""
        from ray_tpu import failpoints

        if failpoints.ACTIVE:
            failpoints.fire("serve.admit")
        self._admit_or_reject(priority, args, kwargs)
        self._num_ongoing += 1
        self._observe_load()
        token = _ctx_var.set(self._context)
        try:
            target = getattr(self._instance, method)
            result = target(*args, **kwargs)
            if inspect.isgenerator(result):
                yield from result
            else:
                yield result
        finally:
            _ctx_var.reset(token)
            self._num_ongoing -= 1
            self._num_processed += 1
            self._observe_load(done=True)

    async def get_queue_len(self) -> int:
        """Probe for the power-of-two-choices router (ray:
        replica_scheduler/pow_2_scheduler.py queue-length RPC)."""
        return self._num_ongoing

    async def get_metrics(self) -> dict:
        out = {"num_ongoing": self._num_ongoing,
               "num_processed": self._num_processed,
               "max_ongoing": self._max_ongoing,
               "max_queued": self._max_queued,
               "num_rejected": self._num_rejected,
               # Which process hosts this replica (TPU replicas share
               # the node's device worker).
               "pid": os.getpid(),
               # Recent slot-wait percentiles (ms) — the queue-wait SLO
               # signal the controller's scaling loop consumes for
               # deployments that report no engine stats.
               "queue_wait_ms": self._queue_waits.snapshot().get(
                   "queue"),
               "ts": time.time()}
        # Surface the user callable's own stats() (e.g. the LLM engine's
        # cache hit/preempt counters) through the serve state API, not
        # only via direct handle calls.
        fn = getattr(self._instance, "stats", None)
        if fn is not None:
            try:
                r = fn()
                if inspect.isawaitable(r):
                    r = await r
                out["user_stats"] = r
            except Exception:  # noqa: BLE001 - stats must not fail probes
                pass
        # Resident @serve.multiplexed models, for the handle's
        # residency routing (serve/multiplex.py; LLM engines report
        # theirs under user_stats["lora"]["resident"] instead).
        try:
            from ray_tpu.serve import multiplex

            mux = multiplex.resident_models(self._instance)
            if mux:
                out["multiplexed"] = mux
        except Exception:  # noqa: BLE001 - metrics must not fail probes
            pass
        return out

    async def check_health(self) -> bool:
        """User class may define check_health; raising marks unhealthy
        (ray: deployment_state.py health-check polling)."""
        fn = getattr(self._instance, "check_health", None)
        if fn is not None:
            r = fn()
            if inspect.isawaitable(r):
                await r
        return True

    async def prepare_for_shutdown(self) -> None:
        """Drain: wait for ongoing requests, then call user __del__-style
        hook (ray: replica graceful shutdown)."""
        while self._num_ongoing > 0:
            await asyncio.sleep(0.02)
        # Drop this replica's tagged series: the hosting process (the
        # co-hosted device worker) outlives replicas, and an autoscaler
        # cycling replicas all day would otherwise grow the registry —
        # and leave a stale nonzero `ongoing` gauge `ray-tpu top` sums
        # as phantom load — without bound.
        try:
            tags = self._metric_tags()
            for m in _replica_metrics().values():
                m.remove(tags)
        except Exception:  # noqa: BLE001 - metrics never block shutdown
            pass
        fn = getattr(self._instance, "shutdown", None)
        if fn is not None:
            r = fn()
            if inspect.isawaitable(r):
                await r
