"""DeploymentHandle: the client-side router to a deployment's replicas.

Analog of ray: python/ray/serve/handle.py (DeploymentHandle.remote:714,786)
with the power-of-two-choices replica scheduler (ray:
_private/replica_scheduler/pow_2_scheduler.py:51) folded in.  Replica
membership comes from the controller and is cached with a TTL; the
scheduler picks 2 random replicas and routes to the one with the lower
locally-tracked in-flight count (the reference probes queue lengths over
RPC; local counts are the zero-RPC equivalent since every request through
this handle is visible to it).

Threading: `remote()` must never block — handles are used from the driver
(plain threads) AND from inside async replica/proxy actors, where blocking
would deadlock the worker IO loop (membership RPC replies arrive on that
same loop).  Membership refresh therefore runs on a per-handle daemon
router thread; when no membership is cached yet, the request is queued to
that thread and the DeploymentResponse is backed by a Future[ObjectRef].
"""
from __future__ import annotations

import concurrent.futures
import logging
import queue as queue_mod
import random
import threading
import time
from typing import Any

from ray_tpu import tracing
from ray_tpu.actor import ActorHandle
from ray_tpu.object_ref import ObjectRef
from ray_tpu.serve import kv_router

logger = logging.getLogger(__name__)

_MEMBERSHIP_TTL_S = 0.5
# Prefix-summary refresh cadence: the router thread re-pulls every
# replica's cached-prefix digest (serve/kv_router.py) through the
# controller at this TTL while requests are flowing.  Staler than
# membership on purpose — a summary is advisory (a miss only costs a
# recomputed prefix), membership is correctness.
_SUMMARY_TTL_S = 1.0
# Dead-replica requeue budget per request: a submit that lands on a
# replica which dies before producing any response is re-routed to
# another running replica at most this many times (ray: serve retries
# ActorDiedError/ActorUnavailableError requests that never started).
_REQUEUE_BUDGET = 3


def _is_replica_death(e: BaseException) -> bool:
    """True for errors that mean the REPLICA PROCESS failed before (or
    while) handling the request — never for user-code exceptions, which
    arrive as TaskError and must surface to the caller, and never for
    ObjectLostError: a lost RESULT object means the request already
    executed to completion (the side effects are applied) and only the
    stored reply was lost with its node — requeueing would re-execute."""
    from ray_tpu.exceptions import (ActorError, ConnectionLost,
                                    WorkerCrashedError)

    return isinstance(e, (ActorError, WorkerCrashedError, ConnectionLost))


def _as_overload(e: BaseException):
    """The typed early-rejection behind a response failure, or None:
    ServeOverloadedError (admission overflow) or AdapterLoadError (a
    multi-LoRA request whose adapter could not be paged in).  Either
    crosses the process boundary wrapped in TaskError like any user
    exception — unwrap it so callers get the TYPED error (fields:
    queue_depth / retry_after_s, model_id / reason) without fishing
    through .cause.  Both mean the request NEVER RAN — never a replica
    death, so they spend no dead-replica requeue budget."""
    from ray_tpu.exceptions import (AdapterLoadError,
                                    ServeOverloadedError, TaskError)

    typed = (ServeOverloadedError, AdapterLoadError)
    if isinstance(e, typed):
        return e
    if isinstance(e, TaskError) and isinstance(
            getattr(e, "cause", None), typed):
        return e.cause
    return None


class _NoCapacity(RuntimeError):
    """No replica can accept the request right now — retried by the router
    thread until the 30s assignment deadline."""


class DeploymentResponse:
    """Future for one request (ray: serve/handle.py DeploymentResponse).

    Awaitable; `.result()` blocks (only call it off the worker IO loop);
    passing it to another handle call chains on the underlying ObjectRef.
    """

    def __init__(self, ref: ObjectRef | None,
                 ref_future: "concurrent.futures.Future | None" = None,
                 requeue=None):
        self._ref = ref
        self._ref_future = ref_future
        # Callable(exc) -> ObjectRef | None: re-route this request to
        # another running replica after the assigned one died before
        # producing a response (None = budget exhausted / no replica).
        self._requeue = requeue

    def _to_object_ref(self, timeout_s: float | None = 30.0) -> ObjectRef:
        if self._ref is None:
            self._ref = self._ref_future.result(timeout=timeout_s)
        return self._ref

    def result(self, timeout_s: float | None = None) -> Any:
        import ray_tpu
        from ray_tpu.exceptions import GetTimeoutError

        # One deadline for the WHOLE call, spanning requeue retries —
        # each retry gets the remaining budget, not a fresh timeout_s.
        deadline = None if timeout_s is None \
            else time.monotonic() + timeout_s
        while True:
            remaining = None if deadline is None \
                else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                raise GetTimeoutError(
                    f"deployment response not ready within {timeout_s}s")
            try:
                ref = self._to_object_ref(
                    remaining if remaining is not None else 30.0)
                # Ref resolution may have blocked (router-queued
                # submit): re-derive the budget or the get below would
                # run on the stale pre-wait value, overshooting the
                # caller's deadline by the whole resolution wait.
                if deadline is not None:
                    remaining = max(0.0, deadline - time.monotonic())
                value = ray_tpu.get(ref, timeout=remaining)
                # The requeue closure pins the request's args/kwargs;
                # once a response has been produced it can never be used
                # again — release the payload with the closure.
                self._requeue = None
                return value
            except concurrent.futures.TimeoutError:
                bound = timeout_s if timeout_s is not None else 30.0
                raise GetTimeoutError(
                    "deployment response not ready: replica submit did "
                    f"not resolve within {bound}s") from None
            except Exception as e:  # noqa: BLE001 - filtered below
                ov = _as_overload(e)
                if ov is not None:
                    self._requeue = None   # rejected = never ran; typed
                    raise ov from None
                if self._requeue is None or not _is_replica_death(e):
                    raise
                if deadline is None:
                    ref = self._requeue(e)
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise
                    # Cap the re-route wait too: it blocks on membership
                    # refresh + the router thread.
                    ref = self._requeue(e, wait_s=remaining)
                if ref is None:
                    self._requeue = None   # budget exhausted — for good
                    raise
                self._ref = ref

    def __await__(self):
        import asyncio

        async def _resolve():
            while True:
                try:
                    # Ref resolution INSIDE the try: a router-submitted
                    # request whose replica died at submit time fails
                    # the ref_future itself, and must requeue exactly
                    # like a post-submit death (the sync result() path
                    # already does — the two must not diverge).
                    ref = self._ref
                    if ref is None:
                        ref = await asyncio.wrap_future(self._ref_future)
                        self._ref = ref
                    value = await ref
                    self._requeue = None   # see result(): drop the payload
                    return value
                except Exception as e:  # noqa: BLE001 - filtered below
                    ov = _as_overload(e)
                    if ov is not None:
                        self._requeue = None
                        raise ov from None
                    if self._requeue is None or not _is_replica_death(e):
                        raise
                    # The requeue refreshes membership over blocking RPC
                    # — never on this (possibly worker-IO) loop.
                    loop = asyncio.get_running_loop()
                    new_ref = await loop.run_in_executor(
                        None, self._requeue, e)
                    if new_ref is None:
                        self._requeue = None
                        raise
                    self._ref = new_ref

        return _resolve().__await__()

    def __reduce__(self):
        return (DeploymentResponse, (self._to_object_ref(),))


class DeploymentResponseGenerator:
    """Streaming response: iterating yields each item the replica's user
    generator produces, as it is produced (ray: serve/handle.py
    DeploymentResponseGenerator via handle.options(stream=True))."""

    def __init__(self, gen_future: "concurrent.futures.Future",
                 requeue=None):
        self._gen_future = gen_future
        self._gen = None
        self._yielded = 0
        # Callable(exc) -> stream generator | None; only consulted while
        # ZERO items have been produced — a partially-consumed stream
        # must fail (replaying it would duplicate delivered items).
        self._requeue = requeue

    def _resolve(self):
        if self._gen is None:
            self._gen = self._gen_future.result(timeout=30.0)
        return self._gen

    def _try_requeue(self, e: BaseException) -> bool:
        if (self._yielded or self._requeue is None
                or not _is_replica_death(e)):
            return False
        gen = self._requeue(e)
        if gen is None:
            self._requeue = None   # budget exhausted — drop the payload
            return False
        self._gen = gen
        return True

    def __iter__(self):
        return self

    def __next__(self) -> Any:
        import ray_tpu

        while True:
            try:
                item = ray_tpu.get(next(self._resolve()))
            except StopIteration:
                raise
            except Exception as e:  # noqa: BLE001 - filtered in helper
                ov = _as_overload(e)
                if ov is not None:
                    self._requeue = None
                    raise ov from None
                if not self._try_requeue(e):
                    raise
                continue
            self._yielded += 1
            # A partially-consumed stream never requeues; the closure
            # pins the request payload — release both together.
            self._requeue = None
            return item

    def __aiter__(self):
        return self

    async def __anext__(self) -> Any:
        import asyncio

        import ray_tpu

        loop = asyncio.get_running_loop()
        while True:
            try:
                gen = await loop.run_in_executor(None, self._resolve)
                ref = await gen.__anext__()
                item = await loop.run_in_executor(None, ray_tpu.get, ref)
            except StopAsyncIteration:
                raise
            except Exception as e:  # noqa: BLE001 - filtered in helper
                ov = _as_overload(e)
                if ov is not None:
                    self._requeue = None
                    raise ov from None
                # Requeue refreshes membership over blocking RPC: keep
                # it off this (possibly worker-IO) loop.
                if not await loop.run_in_executor(
                        None, self._try_requeue, e):
                    raise
                continue
            self._yielded += 1
            self._requeue = None   # see __next__
            return item


class DeploymentHandle:
    def __init__(self, deployment: str, app: str, controller_id: str,
                 method_name: str = "__call__", stream: bool = False,
                 priority: int | None = None):
        self.deployment_name = deployment
        self.app_name = app
        self._controller_id = controller_id
        self._method = method_name
        self._stream = stream
        # Admission-priority tier for requests through this handle
        # (serve/slo.py: 0=high, 1=normal, 2=low); None = let the
        # replica resolve it from the request payload.
        self._priority = priority
        self._lock = threading.Lock()
        self._replicas: list[str] = []      # replica actor ids
        self._handles: dict[str, ActorHandle] = {}
        self._inflight: dict[str, int] = {}
        self._max_ongoing = 0               # 0 = no cap known yet
        self._fetched_at = 0.0
        self._router_q: queue_mod.Queue | None = None
        self._router_thread: threading.Thread | None = None
        # Cache-aware routing state (serve/kv_router.py): per-replica
        # prefix summaries refreshed by the router thread on their own
        # TTL.  Empty until a replica reports one (non-LLM deployments
        # never do — scoring is skipped and this stays pure pow-2;
        # their poll interval backs off 10x, and polling stops
        # entirely once the handle has been idle for a while).
        self._summaries: dict[str, dict] = {}
        self._summaries_at = 0.0
        self._summary_interval = _SUMMARY_TTL_S
        self._last_request_t = 0.0
        # Tier-2 store view ({page: frozenset(hashes)} — the
        # controller's prefix_store_summary), refreshed with the
        # replica summaries: cluster-RESIDENT prefixes score even when
        # no live radix tree holds them.
        self._store_sets: dict[int, frozenset] = {}
        # Multi-LoRA residency view ({rid: {model_id: entry}}), same
        # poll: LLM engines export resident adapters (+ KV salt / LRU
        # age) under stats()["lora"]["resident"], plain
        # @serve.multiplexed replicas export bare model-id lists.
        # kv_router.choose scores residency so a cold adapter loads on
        # ONE least-loaded replica instead of thrashing the pool.
        self._residency: dict[str, dict] = {}
        # Malformed-summary accounting: a replica whose metrics dict is
        # broken must not silently degrade routing to power-of-two —
        # count every drop and warn ONCE per handle (a gossip
        # regression is a bug to surface, not noise to repeat).
        self._summary_drops = 0
        self._summary_warned = False

    # -- membership ---------------------------------------------------------
    def _refresh_blocking(self) -> None:
        """Fetch membership from the controller.  Blocks — router thread /
        driver thread only."""
        import ray_tpu

        info = ray_tpu.get(
            ActorHandle(self._controller_id).get_deployment_info.remote(
                self.app_name, self.deployment_name))
        with self._lock:
            self._fetched_at = time.monotonic()
            self._replicas = list(info["replicas"])
            self._max_ongoing = info.get("max_ongoing", 0)
            for rid in self._replicas:
                self._handles.setdefault(rid, ActorHandle(rid))
                self._inflight.setdefault(rid, 0)
            for rid in list(self._handles):
                if rid not in self._replicas:
                    self._handles.pop(rid)
                    self._inflight.pop(rid, None)
                    self._summaries.pop(rid, None)

    def _refresh_summaries(self) -> None:
        """Pull every replica's prefix-cache summary through the
        controller's replica_metrics verb (the serve state API detail
        path — the summary rides each replica's user_stats).  Blocks —
        router thread only.  Deployments whose replicas report no
        summary (anything that isn't an LLM engine) just leave the dict
        empty and cost one controller RT per TTL while traffic flows."""
        import ray_tpu

        rm = ray_tpu.get(
            ActorHandle(self._controller_id).replica_metrics.remote(
                self.app_name, deployment=self.deployment_name,
                full_ids=True),
            timeout=10.0)
        reps = rm.get(self.app_name, {}).get(self.deployment_name, {})
        summaries = self._compile_replica_summaries(reps)
        residency = self._compile_residency(reps)
        store_sets: dict[int, frozenset] = {}
        if kv_router.prefix_store_on():
            # Tier-2 directory view, same poll (advisory like the
            # replica summaries; an old controller without the verb
            # just leaves it empty).
            try:
                ss = ray_tpu.get(
                    ActorHandle(self._controller_id)
                    .prefix_store_summary.remote(self.app_name),
                    timeout=10.0)
                for page, hs in ((ss or {}).get("pages") or {}).items():
                    store_sets[int(page)] = frozenset(
                        int(h) for h in hs)
            except Exception:  # noqa: BLE001 - controller restarting
                pass
        with self._lock:
            self._summaries = summaries
            self._store_sets = store_sets
            self._residency = residency
            self._summaries_at = time.monotonic()
            self._summary_interval = _SUMMARY_TTL_S \
                if summaries or store_sets or residency \
                else 10 * _SUMMARY_TTL_S

    def _compile_replica_summaries(self, reps: dict) -> dict:
        """Normalize per-replica prefix summaries for scoring.  A
        replica that reports NO summary (any non-LLM deployment) is
        silently skipped — that's the designed shape.  A summary that
        is PRESENT but unusable (malformed metrics dict, wrong types)
        means the gossip path regressed: count it and warn once,
        instead of silently scoring the replica as no-match forever."""
        summaries = {}
        for rid, m in reps.items():
            if not isinstance(m, dict):
                self._note_malformed_summary(rid, m)
                continue
            raw = ((m.get("user_stats") or {}).get("kv") or {}) \
                .get("prefix_summary")
            if raw is None:
                continue       # not an LLM replica — nothing to score
            s = kv_router.compile_summary(raw)
            if s is None:
                self._note_malformed_summary(rid, raw)
                continue
            summaries[rid] = s
        return summaries

    def _compile_residency(self, reps: dict) -> dict:
        """Per-replica resident-adapter view out of the same metrics
        poll: {rid: {model_id: entry}}.  LLM engines report
        stats()["lora"]["resident"] = {mid: {"salt", "version",
        "age"}}; plain @serve.multiplexed replicas report a bare
        model-id list/dict under "multiplexed" (no KV salt — routing
        still scores residency, just without salted prefix depth).
        Replicas reporting neither are simply absent."""
        residency: dict[str, dict] = {}
        for rid, m in reps.items():
            if not isinstance(m, dict):
                continue       # counted by the summary compile already
            ents: dict = {}
            lora = (m.get("user_stats") or {}).get("lora")
            if isinstance(lora, dict) \
                    and isinstance(lora.get("resident"), dict):
                ents.update(lora["resident"])
            mux = m.get("multiplexed")
            if mux is None:
                mux = (m.get("user_stats") or {}).get("multiplexed")
            if isinstance(mux, dict):
                for mid in mux:
                    ents.setdefault(mid, True)
            elif isinstance(mux, (list, tuple, set)):
                for mid in mux:
                    ents.setdefault(mid, True)
            if ents:
                residency[rid] = ents
        return residency

    def _note_malformed_summary(self, rid, raw) -> None:
        self._summary_drops += 1
        if not self._summary_warned:
            self._summary_warned = True
            logger.warning(
                "deployment %r replica %s reported a malformed prefix "
                "summary (%s); scoring it as no-match — cache-aware "
                "routing is silently degrading to power-of-two "
                "(prefix-summary gossip regression?)",
                self.deployment_name, str(rid)[:12],
                type(raw).__name__)

    def _ensure_router(self) -> queue_mod.Queue:
        with self._lock:
            if self._router_q is None:
                self._router_q = queue_mod.Queue()
                self._router_thread = threading.Thread(
                    target=self._router_main, daemon=True,
                    name=f"serve-router-{self.deployment_name}")
                self._router_thread.start()
            return self._router_q

    def _router_main(self) -> None:
        """Completes queued submits and keeps membership fresh while
        requests are flowing (ray: Router long-poll updates,
        _private/router.py:320)."""
        while True:
            try:
                item = self._router_q.get(timeout=_MEMBERSHIP_TTL_S)
            except queue_mod.Empty:
                item = None
            now = time.monotonic()
            with self._lock:
                stale = now - self._fetched_at > _MEMBERSHIP_TTL_S
                # Summary refresh is ADVISORY and must not delay queued
                # submits/requeues (the controller fan-out can block
                # seconds on a dying replica): poll on idle ticks, only
                # while requests have flowed recently, at an interval
                # that backs off 10x for deployments that report no
                # summaries (non-LLM: polling them forever would cost a
                # controller RT per TTL for nothing).  A queue that
                # never drains must not STARVE the poll either — past
                # 5x the interval, refresh anyway (bounded: at most one
                # blocking refresh per 5 TTLs ahead of a queued item).
                age = now - self._summaries_at
                refresh_summaries = (
                    age > self._summary_interval
                    and now - self._last_request_t < 30.0
                    and (item is None
                         or age > 5 * self._summary_interval))
            if stale:
                try:
                    self._refresh_blocking()
                except Exception:  # noqa: BLE001 - controller restarting
                    pass
            if refresh_summaries and kv_router.cache_router_on():
                try:
                    self._refresh_summaries()
                except Exception:  # noqa: BLE001 - controller restarting
                    # Back off on failure too: without advancing the
                    # stamp, a wedged controller would re-block every
                    # idle tick for up to the RPC timeout — exactly the
                    # queued-submit delay this gating exists to avoid.
                    with self._lock:
                        self._summaries_at = time.monotonic()
                        self._summary_interval = 10 * _SUMMARY_TTL_S
            if item is None:
                continue
            fut, submit_fn, args, kwargs, deadline = item
            # PENDING→RUNNING is atomic with a consumer's cancel(): an
            # abandoned submit (requeue caller timed out) is skipped
            # instead of executed-with-no-consumer.  A _NoCapacity
            # retry re-enters here already RUNNING — don't re-claim.
            if not fut.running() and not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(submit_fn(args, kwargs))
            except _NoCapacity as e:
                if time.monotonic() > deadline:
                    # Router-side overload surface: every replica stayed
                    # at its cap (or membership stayed empty) for the
                    # whole assignment window — reject with the typed,
                    # retriable error instead of a bare RuntimeError
                    # (which it still subclasses, for legacy handlers).
                    from ray_tpu.exceptions import ServeOverloadedError

                    with self._lock:
                        depth = sum(self._inflight.values())
                    fut.set_exception(ServeOverloadedError(
                        str(e), deployment=self.deployment_name,
                        queue_depth=depth, retry_after_s=1.0))
                else:
                    time.sleep(0.05)
                    self._router_q.put(item)
            except Exception as e:  # noqa: BLE001
                fut.set_exception(e)

    # -- routing ------------------------------------------------------------
    def _pick(self, exclude=(), prompt=None, model_id=None,
              explain: dict | None = None) -> tuple[str, ActorHandle]:
        """Power-of-two choices over in-flight counts, skipping replicas at
        their max_ongoing_requests cap — the routing-side backpressure of
        ray: pow_2_scheduler.py:51 (replicas over capacity are not sent
        more work; the request queues in the router instead).  `exclude`
        holds replica ids that already FAILED this request (dead-replica
        requeue must land somewhere else).

        With `prompt` (a token-id list) and cached prefix summaries,
        the replica whose radix cache holds the deepest prefix of the
        prompt wins, discounted by its queue length (kv_router.choose —
        the SGLang cache-aware routing shape).  Capacity still rules:
        a replica at its cap is not a candidate no matter how deep its
        match.  No match anywhere (or RAY_TPU_CACHE_ROUTER=0) → pure
        power-of-two, exactly as before."""
        with self._lock:
            self._last_request_t = time.monotonic()
            reps = [r for r in self._replicas if r not in exclude] \
                if exclude else self._replicas
            if not reps:
                raise _NoCapacity(
                    f"deployment {self.deployment_name!r} has no running "
                    f"replicas"
                    + (f" ({len(exclude)} excluded after failure)"
                       if exclude else ""))
            cap = self._max_ongoing
            if cap > 0:
                eligible = [r for r in reps
                            if self._inflight.get(r, 0) < cap]
                if not eligible:
                    raise _NoCapacity(
                        f"all replicas of {self.deployment_name!r} are at "
                        f"max_ongoing_requests={cap}")
            else:
                eligible = reps
            choice = None
            # Residency routing for multiplexed requests: gated by its
            # own switches (RAY_TPU_LORA + RAY_TPU_LORA_ROUTER — the
            # bench's blind arm turns only the latter off), independent
            # of the base-model cache router.
            lora_pick = (model_id is not None and self._residency
                         and kv_router.lora_on()
                         and kv_router.lora_router_on())
            if lora_pick or (prompt is not None
                             and (self._summaries or self._store_sets)
                             and kv_router.cache_router_on()):
                store = self._store_sets \
                    if self._store_sets and kv_router.prefix_store_on() \
                    else None
                choice = kv_router.choose(
                    prompt, eligible, self._inflight, self._summaries,
                    explain=explain, store=store,
                    model_id=model_id if lora_pick else None,
                    residency=self._residency if lora_pick else None)
            if choice is None:
                if len(eligible) == 1:
                    choice = eligible[0]
                else:
                    a, b = random.sample(eligible, 2)
                    choice = a if self._inflight.get(a, 0) <= \
                        self._inflight.get(b, 0) else b
            self._inflight[choice] = self._inflight.get(choice, 0) + 1
            handle = self._handles[choice]
        return choice, handle

    def _submit(self, args: tuple, kwargs: dict,
                state: dict | None = None) -> ObjectRef:
        # Routing happens OUTSIDE the flight-recorder span: a
        # _NoCapacity attempt (the router thread retries every 50ms for
        # up to 30s) must not burn ring slots on phantom error spans,
        # nor consume the queued_at stamp the eventually-successful
        # attempt needs for its serve.queue span.
        explain: dict = {}
        rid, handle = self._pick(
            state["failed"] if state is not None else (),
            prompt=kv_router.extract_prompt(args, kwargs)
            if (self._summaries or self._store_sets) else None,
            model_id=kv_router.extract_model_id(args, kwargs),
            explain=explain)
        if state is not None:
            state["rid"] = rid
        # Flight-recorder route span: roots the request's trace at the
        # handle edge (or joins the caller's — a replica calling its
        # decode pool continues ONE request trace across processes);
        # the actor_call submitted inside the span parents to it.
        with tracing.span(
                "serve.route",
                ctx=state.get("trace") if state is not None else None,
                attrs={"deployment": self.deployment_name,
                       "replica": rid, **explain}):
            t_q = state.pop("queued_at", None) if state is not None \
                else None
            if t_q is not None:
                # Time the request waited in the router-thread queue
                # (no membership / no capacity) before routing.
                tracing.emit("serve.queue", t_q)
            try:
                args = tuple(a._to_object_ref()
                             if isinstance(a, DeploymentResponse)
                             else a for a in args)
                kwargs = {k: (v._to_object_ref()
                              if isinstance(v, DeploymentResponse) else v)
                          for k, v in kwargs.items()}
            except BaseException:
                self._done(rid)
                raise
            pr = {} if self._priority is None \
                else {"priority": self._priority}
            # unbatched: a request's reply must not wait for another
            # request's that happened to share its RPC (worker.py
            # `_drain_actor_outbox`)
            ref = handle.handle_request.options(unbatched=True).remote(
                self._method, args, kwargs, **pr)
            ref.future().add_done_callback(lambda _f: self._done(rid))
            return ref

    def _done(self, rid: str) -> None:
        with self._lock:
            if self._inflight.get(rid, 0) > 0:
                self._inflight[rid] -= 1

    def _submit_streaming(self, args: tuple, kwargs: dict,
                          state: dict | None = None):
        """Route one streaming request: returns a
        StreamingObjectRefGenerator over the replica generator's items."""
        # See _submit: routing stays OUTSIDE the span so _NoCapacity
        # retries neither emit phantom spans nor eat the queue stamp.
        explain: dict = {}
        rid, handle = self._pick(
            state["failed"] if state is not None else (),
            prompt=kv_router.extract_prompt(args, kwargs)
            if (self._summaries or self._store_sets) else None,
            model_id=kv_router.extract_model_id(args, kwargs),
            explain=explain)
        if state is not None:
            state["rid"] = rid
        with tracing.span(
                "serve.route",
                ctx=state.get("trace") if state is not None else None,
                attrs={"deployment": self.deployment_name,
                       "stream": True, "replica": rid, **explain}):
            t_q = state.pop("queued_at", None) if state is not None \
                else None
            if t_q is not None:
                tracing.emit("serve.queue", t_q)
            try:
                args = tuple(a._to_object_ref()
                             if isinstance(a, DeploymentResponse) else a
                             for a in args)
                kwargs = {k: (v._to_object_ref()
                              if isinstance(v, DeploymentResponse) else v)
                          for k, v in kwargs.items()}
                pr = {} if self._priority is None \
                    else {"priority": self._priority}
                gen = handle.handle_request_streaming.options(
                    num_returns="streaming").remote(self._method, args,
                                                    kwargs, **pr)
            except BaseException:
                self._done(rid)
                raise
            gen.task_done_ref().future().add_done_callback(
                lambda _f: self._done(rid))
            return gen

    def _make_requeue(self, submit_fn, args: tuple, kwargs: dict,
                      state: dict):
        """Bounded dead-replica requeue for one request: refresh
        membership (dropping the dead replica), then re-route through
        the router thread — which keeps retrying while the controller
        starts a replacement — to a replica that has not already failed
        this request.  Returns the new ref/generator or None (budget
        spent / nothing to route to: the original error surfaces)."""
        def _requeue(exc: BaseException, wait_s: float = 35.0):
            if state["budget"] <= 0:
                return None
            state["budget"] -= 1
            if state.get("rid"):
                state["failed"].add(state["rid"])
            try:
                self._refresh_blocking()
            except Exception:  # noqa: BLE001 - controller restarting
                pass
            fut: concurrent.futures.Future = concurrent.futures.Future()
            state["queued_at"] = time.time()
            self._ensure_router().put(
                (fut, submit_fn, args, kwargs,
                 time.monotonic() + min(30.0, wait_s)))
            try:
                return fut.result(timeout=wait_s)
            except Exception:  # noqa: BLE001 - surface the ORIGINAL error
                # Still queued (router wedged in a refresh): cancel so
                # the router skips it — executing an abandoned submit
                # would dispatch a request nobody consumes.
                fut.cancel()
                return None
        return _requeue

    def remote(self, *args, **kwargs) -> DeploymentResponse:
        chained_pending = any(
            isinstance(a, DeploymentResponse) and a._ref is None
            for a in list(args) + list(kwargs.values()))
        # Per-request routing state: requeue budget + replicas that
        # already failed it (see _make_requeue) + the caller's trace
        # context, captured HERE (API edge, caller thread) because the
        # submit may execute later on the router thread, which has no
        # ambient context of its own.
        state = {"budget": _REQUEUE_BUDGET, "failed": set(), "rid": None,
                 "trace": tracing.capture() if tracing.ENABLED else None}
        if self._stream:
            def submit_stream(a, k):
                return self._submit_streaming(a, k, state=state)

            requeue = self._make_requeue(submit_stream, args, kwargs,
                                         state)
            fut: concurrent.futures.Future = concurrent.futures.Future()
            with self._lock:
                have = bool(self._replicas)
            if have and not chained_pending:
                try:
                    fut.set_result(submit_stream(args, kwargs))
                    return DeploymentResponseGenerator(fut,
                                                       requeue=requeue)
                except _NoCapacity:
                    fut = concurrent.futures.Future()
            # No membership / unresolved chained response / no capacity:
            # the router thread resolves the generator off the caller's
            # thread (which may be a worker IO loop — never block it).
            state["queued_at"] = time.time()
            self._ensure_router().put(
                (fut, submit_stream, args, kwargs,
                 time.monotonic() + 30.0))
            return DeploymentResponseGenerator(fut, requeue=requeue)

        def submit(a, k):
            return self._submit(a, k, state=state)

        requeue = self._make_requeue(submit, args, kwargs, state)
        # An unresolved chained response would require a blocking wait to
        # convert to an ObjectRef — never do that on the caller's thread
        # (it may be a worker IO loop); hand it to the router thread.
        with self._lock:
            have = bool(self._replicas)
            fresh = (time.monotonic() - self._fetched_at) < _MEMBERSHIP_TTL_S
        if have and not chained_pending:
            if not fresh:    # serve stale, refresh in background
                self._ensure_router()
            try:
                return DeploymentResponse(submit(args, kwargs),
                                          requeue=requeue)
            except _NoCapacity:
                pass         # queue to the router thread below
        fut: concurrent.futures.Future = concurrent.futures.Future()
        state["queued_at"] = time.time()
        self._ensure_router().put(
            (fut, submit, args, kwargs, time.monotonic() + 30.0))
        return DeploymentResponse(None, ref_future=fut, requeue=requeue)

    def options(self, method_name: str | None = None,
                stream: bool | None = None,
                priority: int | None = None) -> "DeploymentHandle":
        return DeploymentHandle(
            self.deployment_name, self.app_name, self._controller_id,
            method_name or self._method,
            self._stream if stream is None else stream,
            self._priority if priority is None else priority)

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        return self.options(method_name=name)

    def __repr__(self):
        return (f"DeploymentHandle({self.app_name}/{self.deployment_name}"
                f".{self._method})")

    def __reduce__(self):
        return (DeploymentHandle, (self.deployment_name, self.app_name,
                                   self._controller_id, self._method,
                                   self._stream, self._priority))
