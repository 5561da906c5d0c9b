"""ServeController: the reconciling control plane for Serve.

Analog of ray: python/ray/serve/_private/controller.py (ServeController,
run_control_loop:372) + deployment_state.py (DeploymentState reconciler) +
autoscaling_state.py (autoscaling policy) + deployment_scheduler.py.

A *threaded* actor (not asyncio): the control loop and RPC methods run on
the actor's thread pool so they may freely make blocking framework calls
(create actor / get / kill) — the same reason the reference runs its
reconciler off the replica event loops.  Replica membership is versioned;
handles poll `get_deployment_info` (the long-poll analog of ray:
_private/long_poll.py LongPollHost).

Concurrency discipline: the controller lock only guards in-memory state —
no RPC is ever made while holding it.  Replica starts/health checks are
asynchronous (pending ObjectRefs polled each reconcile tick), so one slow
replica init never stalls reconciliation of other deployments (ray:
deployment_state starts replicas async and polls readiness).
"""
from __future__ import annotations

import logging
import threading
import time
import traceback
import uuid
from typing import Any

from ray_tpu import tracing
from ray_tpu.serve import slo

logger = logging.getLogger(__name__)

CONTROLLER_NAME = "SERVE_CONTROLLER"
RECONCILE_PERIOD_S = 0.2
REPLICA_INIT_TIMEOUT_S = 120.0


class _DeploymentState:
    """Target spec + live replicas for one deployment (ray:
    deployment_state.py DeploymentState)."""

    def __init__(self, app: str, name: str, cls, init_args, init_kwargs,
                 config, version: str):
        self.app = app
        self.name = name
        self.cls = cls
        self.init_args = init_args
        self.init_kwargs = init_kwargs
        self.config = config
        self.version = version
        self.target_replicas = config.num_replicas
        # replica actor_id -> {"handle", "state", "init_ref", "init_deadline",
        #                      "health_ref", "health_deadline", "last_health"}
        self.replicas: dict[str, dict] = {}
        # Old-version replicas still serving during a rolling code update;
        # advertised only until the new version is up (ray: gradual rollout).
        self.draining: dict[str, dict] = {}
        self.membership_version = 0
        self.last_scale_up = 0.0
        self.last_scale_down = 0.0
        self.deleting = False
        self.superseded = False   # replaced by a newer _DeploymentState
        # autoscale probe in flight: list of (rec, ref) + deadline
        self.probe: tuple[list, float] | None = None
        # Last completed metrics probe, merged: {total_ongoing,
        # p99_ttft_ms, p99_queue_ms, n, t} — the SLO loop's decision
        # input and the PD-rebalance pass's stage-split signal.
        self.slo_snapshot: dict | None = None


class ServeController:
    """Named detached actor; one per cluster (ray: controller.py:86)."""

    def __init__(self):
        self._lock = threading.RLock()
        # app -> {"route_prefix", "ingress", "deployments": {name: state}}
        self._apps: dict[str, dict] = {}
        self._http_host = "127.0.0.1"
        self._http_port = 0
        # SLO autoscaling kill-switch override (set_autoscale_enabled
        # RPC: same-run A/B without touching this process's env);
        # None = follow RAY_TPU_SERVE_AUTOSCALE.
        self._autoscale_override: bool | None = None
        # request_resources demand posting: re-post only when a target
        # changed (dirty) and at most every few seconds.
        self._demand_dirty = False
        self._last_demand_post = 0.0
        # (app, prefill_deployment) -> last pool-ratio shift time.
        self._last_pd_shift: dict[tuple, float] = {}
        # Tier-2 prefix-store directory (serve/prefix_store.py): hash →
        # demoted-subtree entries published by the replicas.  Scrubbed
        # with the app (delete_app) and with each dead replica.
        from ray_tpu.serve.prefix_store import StoreDirectory

        self._prefix_store = StoreDirectory()
        # Multi-LoRA adapter registry (serve/lora.py): model_id →
        # sealed-adapter object ref + version.  Cluster-scoped (an
        # adapter serves any lora-enabled deployment), cleared at
        # graceful_shutdown — the directory holds the primary refs.
        from ray_tpu.serve.lora import AdapterDirectory

        self._lora = AdapterDirectory()
        self._shutdown = threading.Event()
        self._thread = threading.Thread(
            target=self._run_control_loop, daemon=True, name="serve-ctrl")
        self._thread.start()

    # ------------------------------------------------------------ public RPC
    def deploy_app(self, app_name: str, route_prefix: str, ingress: str,
                   deployments: list[dict]) -> None:
        """Declarative (re)deploy of a whole app (ray: serve.run →
        controller.deploy_apps).  Never blocks on replica RPCs: code
        changes hand old replicas to the new state's drain list; config
        changes are applied by the reconcile loop."""
        reconfigures: list[tuple[Any, Any]] = []
        with self._lock:
            app = self._apps.setdefault(
                app_name, {"route_prefix": route_prefix, "ingress": ingress,
                           "deployments": {}})
            app["route_prefix"] = route_prefix
            app["ingress"] = ingress
            new_names = {d["name"] for d in deployments}
            for name, st in list(app["deployments"].items()):
                if name not in new_names:
                    st.deleting = True
                    st.target_replicas = 0
            for d in deployments:
                cur = app["deployments"].get(d["name"])
                if cur is not None and cur.version == d["version"] \
                        and not cur.deleting:
                    # Config-only change: rescale/reconfigure in place
                    # (ray: deployment_state config-change classification).
                    old_user_config = cur.config.user_config
                    cur.config = d["config"]
                    if cur.config.autoscaling_config is None:
                        cur.target_replicas = d["config"].num_replicas
                    if d["config"].user_config is not None and \
                            d["config"].user_config != old_user_config:
                        reconfigures.append((cur, d["config"].user_config))
                    continue
                new_st = _DeploymentState(
                    app_name, d["name"], d["cls"], d["init_args"],
                    d["init_kwargs"], d["config"], d["version"])
                if cur is not None:
                    cur.superseded = True
                    # Old replicas keep serving until the new version is up.
                    new_st.draining.update(cur.replicas)
                    new_st.draining.update(cur.draining)
                app["deployments"][d["name"]] = new_st
            # Post the INITIAL demand floor too: a fresh deploy whose
            # min_replicas exceed current capacity needs nodes before
            # any scale decision ever changes a target.
            self._demand_dirty = True
        for st, user_config in reconfigures:
            self._reconfigure_in_place(st, user_config)

    def _reconfigure_in_place(self, st: _DeploymentState, user_config) -> None:
        import ray_tpu

        with self._lock:
            handles = [rec["handle"] for rec in st.replicas.values()
                       if rec["state"] == "RUNNING"]
        refs = [h.reconfigure.remote(user_config) for h in handles]
        for ref in refs:
            try:
                ray_tpu.get(ref, timeout=30.0)
            except Exception:  # noqa: BLE001
                logger.warning("reconfigure failed:\n%s",
                               traceback.format_exc())

    def delete_app(self, app_name: str) -> None:
        with self._lock:
            app = self._apps.get(app_name)
            if app is None:
                return
            for st in app["deployments"].values():
                st.deleting = True
                st.target_replicas = 0
        # The deleted app's autoscaler demand floor must shrink too,
        # and its demoted prefix entries must not outlive it (the
        # directory's borrowed refs go here; the replicas drop their
        # primary refs in LLMServer.shutdown during drain).
        self._prefix_store.drop_app(app_name)
        self._demand_dirty = True

    def get_deployment_info(self, app_name: str, deployment: str) -> dict:
        with self._lock:
            st = self._state(app_name, deployment)
            if st is None:
                return {"version": -1, "replicas": [], "max_ongoing": 0}
            running = [rid for rid, rec in st.replicas.items()
                       if rec["state"] == "RUNNING"]
            if not running:
                # During a rolling update the old version keeps serving.
                running = [rid for rid, rec in st.draining.items()
                           if rec["state"] == "RUNNING"]
            return {
                "version": st.membership_version,
                "replicas": running,
                "max_ongoing": st.config.max_ongoing_requests,
            }

    def replica_metrics(self, app_name: str | None = None,
                        deployment: str | None = None,
                        full_ids: bool = False) -> dict:
        """Per-replica metrics incl. the user callable's own stats()
        (e.g. the LLM engine's KV-cache hit/preempt counters and its
        prefix-cache summary) — the serve state API's detail surface
        (ray: serve application details' replica_details).  Fanned out
        OUTSIDE the lock: a slow replica must not wedge the control
        loop.  `deployment` narrows the fan-out to one deployment (the
        cache-aware router polls this per handle); `full_ids` keys
        replicas by their complete actor id so callers can join against
        membership from get_deployment_info."""
        import ray_tpu

        with self._lock:
            targets = []
            for an, app in self._apps.items():
                if app_name is not None and an != app_name:
                    continue
                for dname, st in app["deployments"].items():
                    if deployment is not None and dname != deployment:
                        continue
                    for rid, rec in st.replicas.items():
                        if rec["state"] == "RUNNING":
                            targets.append((an, dname, rid,
                                            rec["handle"]))
        out: dict = {}
        refs = []
        for an, dname, rid, handle in targets:
            try:
                refs.append((an, dname, rid,
                             handle.get_metrics.remote()))
            except Exception:  # noqa: BLE001 - replica mid-restart
                pass
        for an, dname, rid, ref in refs:
            try:
                m = ray_tpu.get(ref, timeout=5.0)
            except Exception:  # noqa: BLE001
                m = {"error": "unreachable"}
            key = rid if full_ids else rid[:12]
            out.setdefault(an, {}).setdefault(dname, {})[key] = m
        return out

    # --------------------------------------------- prefix-store verbs
    # Thin RPC surface over the StoreDirectory (serve/prefix_store.py):
    # replicas publish/withdraw demoted subtrees, the miss path looks
    # up the deepest stored prefix, and handles poll the summary for
    # store-aware routing.  All logic lives in the directory.
    def prefix_store_publish(self, app: str, meta: dict, ref) -> dict:
        # A replica of a deleted app still drains and may demote: what
        # it published after delete_app's scrub, nothing would scrub.
        # Under the lock, so a publish lands before the app is marked
        # (and is scrubbed with it) or after (and is refused).
        with self._lock:
            rec = self._apps.get(app)
            if rec is None or all(
                    st.deleting for st in rec["deployments"].values()):
                return {"ok": False, "live": []}
            return self._prefix_store.publish(app, meta, ref)

    def prefix_store_lookup(self, app: str, hashes: list, page: int,
                            seed, weight_version: int | None = None,
                            min_depth: int = 0):
        return self._prefix_store.lookup(
            app, hashes, page, seed, weight_version=weight_version,
            min_depth=min_depth)

    def prefix_store_forget(self, app: str, replica: str | None = None,
                            below_version: int | None = None,
                            hashes: list | None = None) -> int:
        return self._prefix_store.forget(
            app, replica=replica, below_version=below_version,
            hashes=hashes)

    def prefix_store_summary(self, app: str) -> dict:
        return self._prefix_store.summary(app)

    def prefix_store_stats(self) -> dict:
        return self._prefix_store.stats()

    # ------------------------------------------------ multi-LoRA verbs
    # Thin RPC surface over the AdapterDirectory (serve/lora.py):
    # drivers publish/withdraw adapters, replicas look them up for the
    # page-in miss path.  All logic lives in the directory.
    def lora_publish(self, model_id: str, meta: dict, ref) -> dict:
        return self._lora.publish(model_id, meta, ref)

    def lora_lookup(self, model_id: str):
        return self._lora.lookup(model_id)

    def lora_forget(self, model_id: str) -> bool:
        return self._lora.forget(model_id)

    def lora_summary(self) -> dict:
        return self._lora.summary()

    def lora_stats(self) -> dict:
        return self._lora.stats()

    def get_app_routes(self) -> dict:
        """route_prefix -> (app, ingress deployment); polled by proxies
        (ray: long-poll route table push)."""
        with self._lock:
            return {app["route_prefix"]: (name, app["ingress"])
                    for name, app in self._apps.items()
                    if any(not st.deleting
                           for st in app["deployments"].values())}

    def status(self) -> dict:
        """Serve status tree (ray: serve.status / ServeStatusSchema)."""
        with self._lock:
            out = {}
            for app_name, app in self._apps.items():
                deps = {}
                for name, st in app["deployments"].items():
                    running = sum(1 for r in st.replicas.values()
                                  if r["state"] == "RUNNING")
                    deps[name] = {
                        "status": ("DELETING" if st.deleting else
                                   "HEALTHY" if running >= st.target_replicas
                                   else "UPDATING"),
                        "replicas": running,
                        "target_replicas": st.target_replicas,
                    }
                alive = any(not st.deleting
                            for st in app["deployments"].values())
                out[app_name] = {
                    "status": "RUNNING" if alive and all(
                        d["status"] == "HEALTHY" for d in deps.values())
                    else "DELETING" if not alive else "DEPLOYING",
                    "route_prefix": app["route_prefix"],
                    "deployments": deps,
                }
            return out

    def graceful_shutdown(self) -> None:
        with self._lock:
            for app in self._apps.values():
                for st in app["deployments"].values():
                    st.deleting = True
                    st.target_replicas = 0
        self._prefix_store.clear()
        # Published adapters die with serve (the directory holds their
        # primary refs — dropping the entries releases the arena bytes).
        self._lora.clear()
        # Clear the serve demand floor SYNCHRONOUSLY: serve.shutdown
        # kills this actor within seconds — the throttled reconcile
        # re-post may never run, and a stale floor would make the
        # cluster autoscaler hold nodes for phantom replicas forever.
        try:
            from ray_tpu.autoscaler import request_resources

            request_resources(bundles=[], requester="serve")
        except Exception:  # noqa: BLE001 - no autoscaler wired
            pass

    def wait_for_deployments_ready(self, app_name: str,
                                   timeout_s: float = 60.0) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                app = self._apps.get(app_name)
                if app is not None:
                    states = [st for st in app["deployments"].values()
                              if not st.deleting]
                    if states and all(
                        sum(1 for r in st.replicas.values()
                            if r["state"] == "RUNNING") >= st.target_replicas
                            and st.target_replicas > 0
                            for st in states):
                        return True
            time.sleep(0.05)
        return False

    # --------------------------------------------------------- control loop
    def _state(self, app_name: str, deployment: str) -> _DeploymentState | None:
        app = self._apps.get(app_name)
        if app is None:
            return None
        return app["deployments"].get(deployment)

    def _run_control_loop(self) -> None:
        """ray: controller.py:372 run_control_loop."""
        while not self._shutdown.is_set():
            try:
                self._reconcile_once()
            except Exception:  # noqa: BLE001
                logger.error("reconcile error:\n%s", traceback.format_exc())
            time.sleep(RECONCILE_PERIOD_S)

    def _reconcile_once(self) -> None:
        try:
            self._reconcile_proxies()
        except Exception:  # noqa: BLE001
            logger.warning("proxy reconcile failed:\n%s",
                           traceback.format_exc())
        with self._lock:
            states = [st for app in self._apps.values()
                      for st in app["deployments"].values()]
        for st in states:
            # A state replaced by deploy_app mid-snapshot must not be
            # reconciled: starting replicas into it would leak actors.
            with self._lock:
                if st.superseded or self._state(st.app, st.name) is not st:
                    continue
            self._autoscale(st)
            self._reconcile_deployment(st)
        if self._autoscale_enabled():
            self._maybe_rebalance_pd()
        # Demand posting runs even with autoscaling disabled: a floor
        # posted while enabled must still SHRINK when the switch flips
        # off or an app is deleted — otherwise the autoscaler would
        # hold nodes for replicas that no longer exist.
        self._post_autoscaler_demand()
        self._memory_observe()
        with self._lock:
            for app_name, app in list(self._apps.items()):
                for name, st in list(app["deployments"].items()):
                    if st.deleting and not st.replicas and not st.draining:
                        del app["deployments"][name]
                if not app["deployments"]:
                    del self._apps[app_name]

    # ---------------------------------------------- memory observability
    def _memory_observe(self) -> None:
        """Memory-ledger leg of the reconcile loop (throttled): publish
        the per-deployment tier-2 prefix bytes gauge, and flag directory
        entries whose publishing replica this controller no longer
        knows — their arena objects died with the publisher, so every
        lookup against them can only fail (the sentinel alarm; the
        lazy dead-publisher scrub on the fetch path still does the
        cleanup)."""
        now = time.monotonic()
        if now - getattr(self, "_last_mem_observe", 0.0) < 5.0:
            return
        self._last_mem_observe = now
        try:
            from ray_tpu.utils import metrics as um

            g = um.get_or_create(
                um.Gauge, "serve_prefix_tier2_bytes",
                "Tier-2 prefix-store bytes per deployment",
                tag_keys=("app", "deployment"))
            per = self._prefix_store.bytes_by_deployment()
            # Zero removed series explicitly — gauges have no TTL, and
            # a deleted app must not read as still holding bytes.
            for app, dep in getattr(self, "_tier2_keys", set()) - \
                    set(per):
                g.set(0.0, tags={"app": app, "deployment": dep})
            for (app, dep), b in per.items():
                g.set(float(b), tags={"app": app, "deployment": dep})
            self._tier2_keys = set(per)
        except Exception:  # noqa: BLE001 - metrics must never stall
            pass           # the reconciler
        with self._lock:
            live = {rid for app in self._apps.values()
                    for st in app["deployments"].values()
                    for rid in (*st.replicas, *st.draining)}
        orphan = self._prefix_store.replicas() - live
        warned = getattr(self, "_tier2_orphan_warned", set())
        for rid in orphan - warned:
            t = time.time()
            tracing.emit("memory.leak", t, t, attrs={
                "kind": "tier2_orphan_publisher", "replica": rid})
            logger.warning(
                "leak sentinel: tier-2 prefix entries from unknown "
                "replica %s (publisher gone — entries are "
                "unreachable)", rid)
        self._tier2_orphan_warned = warned | orphan

    # --------------------------------------------------------- proxies
    def _reconcile_proxies(self) -> None:
        """One ProxyActor per ALIVE node, pinned by hard node affinity,
        restarted when dead (ray: serve proxy_state.py reconciliation
        driven by the serve controller).  Throttled: membership changes
        rarely, and each sync costs two control-plane dumps."""
        now = time.monotonic()
        if now - getattr(self, "_last_proxy_sync", 0.0) < 2.0:
            return
        self._last_proxy_sync = now
        import ray_tpu
        from ray_tpu.utils.scheduling_strategies import (
            NodeAffinitySchedulingStrategy)
        from ray_tpu.utils.state import list_actors

        alive_nodes = {n["node_id"] for n in ray_tpu.nodes()
                       if n.get("state") == "ALIVE"}
        live_proxies = {
            a["name"]: a for a in list_actors()
            if (a.get("name") or "").startswith("SERVE_PROXY::")
            and a.get("state") == "ALIVE"}
        from ray_tpu.serve.proxy import ProxyActor

        for node_id in alive_nodes:
            name = f"SERVE_PROXY::{node_id}"
            if name in live_proxies:
                continue
            try:
                ray_tpu.remote(ProxyActor).options(
                    name=name, get_if_exists=True, lifetime="detached",
                    max_concurrency=64, num_cpus=0,
                    scheduling_strategy=NodeAffinitySchedulingStrategy(
                        node_id, soft=False),
                ).remote(self._controller_self_id(),
                         self._http_host, self._http_port)
            except Exception:  # noqa: BLE001
                logger.warning("proxy start on %s failed:\n%s",
                               node_id[:12], traceback.format_exc())

    def _controller_self_id(self) -> str:
        from ray_tpu.runtime_context import get_runtime_context

        return get_runtime_context().get_actor_id()

    def set_http_options(self, host: str, port: int) -> None:
        import ray_tpu

        changed = (host, port) != (self._http_host, self._http_port)
        self._http_host = host
        self._http_port = port
        if changed:
            # Existing proxies hold the old bind options: kill them so
            # the reconcile loop recreates them with the new ones.
            for name in self.list_proxies():
                try:
                    ray_tpu.kill(ray_tpu.get_actor(name))
                except Exception:  # noqa: BLE001
                    pass

    def list_proxies(self) -> list[str]:
        from ray_tpu.utils.state import list_actors

        return sorted(a["name"] for a in list_actors()
                      if (a.get("name") or "").startswith("SERVE_PROXY::")
                      and a.get("state") == "ALIVE")

    def _autoscale_enabled(self) -> bool:
        """RAY_TPU_SERVE_AUTOSCALE kill switch, overridable live via
        the set_autoscale_enabled RPC (same-run A/B: the env of a
        long-lived controller actor can't be flipped from a driver)."""
        if self._autoscale_override is not None:
            return self._autoscale_override
        return slo.autoscale_on()

    def set_autoscale_enabled(self, on: bool | None) -> None:
        """None = follow the env switch; True/False = force."""
        self._autoscale_override = on

    def _autoscale(self, st: _DeploymentState) -> None:
        """The SLO loop: scale on ongoing-request load AND p99
        TTFT / queue-wait attainment (ray: autoscaling_state.py scales
        on ongoing only; the SLO terms consume the same per-replica
        latency windows that feed the stage histograms through
        replica_metrics).  Probes are in-flight ObjectRefs collected on
        a later tick — never a long block."""
        cfg = st.config.autoscaling_config
        if cfg is None or st.deleting or not self._autoscale_enabled():
            return
        import ray_tpu

        if st.probe is not None:
            refs_recs, deadline = st.probe
            refs = [r for _, r in refs_recs]
            ready, _pending = ray_tpu.wait(
                refs, num_returns=len(refs), timeout=0)
            if len(ready) == len(refs) or time.monotonic() > deadline:
                total = 0.0
                ttft: list[float] = []
                queuew: list[float] = []
                for ref in ready:
                    try:
                        m = ray_tpu.get(ref, timeout=1.0)
                    except Exception:  # noqa: BLE001
                        continue
                    if isinstance(m, (int, float)):
                        total += m          # legacy queue-len probe
                        continue
                    if not isinstance(m, dict):
                        continue
                    total += m.get("num_ongoing", 0)
                    qw = (m.get("queue_wait_ms") or {}).get("p99")
                    if qw is not None:
                        queuew.append(qw)
                    s = (m.get("user_stats") or {}).get("slo") or {}
                    t = (s.get("ttft_ms") or {}).get("p99")
                    if t is not None:
                        ttft.append(t)
                    q2 = (s.get("queue_ms") or {}).get("p99")
                    if q2 is not None:
                        queuew.append(q2)
                st.probe = None
                # Tail attainment is per-request, not per-replica:
                # the WORST replica's p99 is the deployment's p99 bound.
                st.slo_snapshot = {
                    "total_ongoing": total,
                    "p99_ttft_ms": max(ttft) if ttft else None,
                    "p99_queue_ms": max(queuew) if queuew else None,
                    "n": len(refs_recs), "t": time.monotonic()}
                self._apply_autoscale_decision(st, cfg, total,
                                               len(refs_recs))
            return
        with self._lock:
            running = [rec for rec in st.replicas.values()
                       if rec["state"] == "RUNNING"]
        if not running:
            return
        refs_recs = [(rec, rec["handle"].get_metrics.remote())
                     for rec in running]
        st.probe = (refs_recs, time.monotonic() + 5.0)

    def _apply_autoscale_decision(self, st, cfg, total: float,
                                  n_running: int) -> None:
        snap = st.slo_snapshot or {}
        desired, reason = slo.slo_desired(
            cfg, n_running, total, snap.get("p99_ttft_ms"),
            snap.get("p99_queue_ms"))
        now = time.monotonic()
        prev = st.target_replicas
        if desired > st.target_replicas:
            if now - st.last_scale_up >= cfg.upscale_delay_s:
                st.target_replicas = desired
                st.last_scale_up = now
        elif desired < st.target_replicas:
            if now - st.last_scale_down >= cfg.downscale_delay_s:
                st.target_replicas = desired
                st.last_scale_down = now
        else:
            st.last_scale_up = st.last_scale_down = now
        if st.target_replicas != prev:
            # Flight-recorder span: WHY capacity changed, with the
            # metrics that drove it (a trace of the spike shows the
            # breach → scale → recovery chain).
            if tracing.ENABLED:
                tracing.emit(
                    "serve.scale", time.time(),
                    attrs={"app": st.app, "deployment": st.name,
                           "from": prev, "to": st.target_replicas,
                           "reason": reason,
                           "total_ongoing": round(total, 1),
                           "p99_ttft_ms": snap.get("p99_ttft_ms"),
                           "p99_queue_ms": snap.get("p99_queue_ms")})
            self._demand_dirty = True

    def _post_autoscaler_demand(self) -> None:
        """Post the autoscaled deployments' aggregate replica demand as
        a request_resources floor (requester-scoped: never clobbers
        elastic training's demand) so the autoscaler v2 reconciler
        provisions nodes for replicas the cluster can't place yet.
        Throttled: re-posts only after a target changed, at most every
        2s.  Best-effort — no autoscaler, no harm."""
        now = time.monotonic()
        if not self._demand_dirty or now - self._last_demand_post < 2.0:
            return
        self._demand_dirty = False
        self._last_demand_post = now
        bundles = []
        with self._lock:
            for app in self._apps.values():
                for st in app["deployments"].values():
                    if st.config.autoscaling_config is None \
                            or st.deleting:
                        continue
                    cpu = st.config.ray_actor_options.get(
                        "num_cpus", 0.1)
                    bundles.extend({"CPU": cpu}
                                   for _ in range(st.target_replicas))
        try:
            from ray_tpu.autoscaler import request_resources

            request_resources(bundles=bundles, requester="serve")
        except Exception:  # noqa: BLE001 - no autoscaler wired
            pass

    def _maybe_rebalance_pd(self) -> None:
        """Prefill:decode pool-ratio knob for disaggregated LLM apps:
        shift ONE replica of budget from the underloaded pool to the
        overloaded one when the stage split says so (serve/slo.py
        pd_rebalance) — a knob no single-pool autoscaler has, because
        it needs the prefill-vs-decode stage attribution.  Cooldown
        10s per edge; both pools must be autoscaled and have fresh
        probe snapshots."""
        with self._lock:
            edges = []
            for app_name, app in self._apps.items():
                deps = app["deployments"]
                for name, st in deps.items():
                    kw = st.init_kwargs or {}
                    if kw.get("role") != "prefill":
                        continue
                    dd = kw.get("decode_deployment")
                    dd = getattr(dd, "deployment_name", dd)
                    dst = deps.get(dd) if isinstance(dd, str) else None
                    if dst is not None:
                        edges.append((app_name, name, st, dst))
        now = time.monotonic()
        for app_name, name, pre, dec in edges:
            pcfg, dcfg = pre.config.autoscaling_config, \
                dec.config.autoscaling_config
            if pcfg is None or dcfg is None or pre.deleting \
                    or dec.deleting:
                continue
            psnap, dsnap = pre.slo_snapshot, dec.slo_snapshot
            if not psnap or not dsnap:
                continue
            # Freshness + zero-load gates (the slo_desired discipline):
            # a stale or idle-app snapshot's p99 tail must not churn
            # pool budget after traffic stops.
            if min(psnap.get("t", 0.0), dsnap.get("t", 0.0)) \
                    < now - 10.0:
                continue
            if psnap.get("total_ongoing", 0) \
                    + dsnap.get("total_ongoing", 0) <= 0:
                continue
            if now - self._last_pd_shift.get((app_name, name), 0.0) \
                    < 10.0:
                continue
            shift = slo.pd_rebalance(psnap, dsnap, pre.target_replicas,
                                     dec.target_replicas, pcfg, dcfg)
            if not shift:
                continue
            src, dst = (pre, dec) if shift > 0 else (dec, pre)
            with self._lock:
                src.target_replicas -= 1
                dst.target_replicas += 1
                # Cooldown stamps that keep the shift from being
                # immediately REVERTED by the per-pool loop: the source
                # must not upscale straight back (last_scale_up) and
                # the destination must not downscale straight back
                # (last_scale_down).
                src.last_scale_up = dst.last_scale_down = now
            self._last_pd_shift[(app_name, name)] = now
            self._demand_dirty = True
            if tracing.ENABLED:
                tracing.emit(
                    "serve.pd_rebalance", time.time(),
                    attrs={"app": app_name, "prefill": pre.name,
                           "decode": dec.name,
                           "shift": "prefill->decode" if shift > 0
                           else "decode->prefill",
                           "prefill_p99_queue_ms":
                           psnap.get("p99_queue_ms"),
                           "decode_p99_queue_ms":
                           dsnap.get("p99_queue_ms"),
                           "prefill_target": pre.target_replicas,
                           "decode_target": dec.target_replicas})

    def _reconcile_deployment(self, st: _DeploymentState) -> None:
        """Start/stop replicas toward target; poll pending inits and
        health checks (ray: deployment_state.py update loop)."""
        self._poll_starting(st)
        self._poll_health(st)

        with self._lock:
            running = {rid: rec for rid, rec in st.replicas.items()
                       if rec["state"] == "RUNNING"}
            starting = sum(1 for rec in st.replicas.values()
                           if rec["state"] == "STARTING")
            n = len(running) + starting
            target = st.target_replicas
        if n < target:
            for _ in range(target - n):
                self._start_replica(st)
        elif len(running) > target:
            extra = list(running)[target - len(running):] if target else \
                list(running)
            for rid in extra[:len(running) - target]:
                self._remove_replica(st, rid, drain=True)
        # Rolling update: once the new version serves, retire the old.
        with self._lock:
            new_up = any(rec["state"] == "RUNNING"
                         for rec in st.replicas.values())
            drain_now = (list(st.draining.items())
                         if (new_up and len(running) >= target) or st.deleting
                         else [])
            for rid, _rec in drain_now:
                st.draining.pop(rid, None)
        for _rid, rec in drain_now:
            self._stop_replica(rec, drain=True,
                               timeout=st.config.graceful_shutdown_timeout_s)

    def _poll_starting(self, st: _DeploymentState) -> None:
        """Flip STARTING→RUNNING when the init probe resolves (non-blocking;
        ray: replica startup polling in deployment_state)."""
        import ray_tpu

        with self._lock:
            pending = [(rid, rec) for rid, rec in st.replicas.items()
                       if rec["state"] == "STARTING"]
        for rid, rec in pending:
            ready, _ = ray_tpu.wait([rec["init_ref"]], timeout=0)
            if ready:
                try:
                    ray_tpu.get(ready[0], timeout=1.0)
                    with self._lock:
                        rec["state"] = "RUNNING"
                        rec["last_health"] = time.monotonic()
                        st.membership_version += 1
                except Exception:  # noqa: BLE001
                    logger.error("replica init failed:\n%s",
                                 traceback.format_exc())
                    self._remove_replica(st, rid, drain=False)
            elif time.monotonic() > rec["init_deadline"]:
                logger.error("replica %s init timed out", rid[:12])
                self._remove_replica(st, rid, drain=False)

    def _poll_health(self, st: _DeploymentState) -> None:
        """Issue/collect health probes without blocking (ray:
        deployment_state health-check polling)."""
        import ray_tpu

        with self._lock:
            running = [(rid, rec) for rid, rec in st.replicas.items()
                       if rec["state"] == "RUNNING"]
        for rid, rec in running:
            ref = rec.get("health_ref")
            if ref is not None:
                ready, _ = ray_tpu.wait([ref], timeout=0)
                if ready:
                    rec["health_ref"] = None
                    try:
                        ray_tpu.get(ready[0], timeout=1.0)
                        rec["last_health"] = time.monotonic()
                    except Exception:  # noqa: BLE001
                        logger.warning(
                            "replica %s failed health check; replacing",
                            rid[:12])
                        self._remove_replica(st, rid, drain=False)
                elif time.monotonic() > rec["health_deadline"]:
                    logger.warning("replica %s health check timed out",
                                   rid[:12])
                    self._remove_replica(st, rid, drain=False)
            elif time.monotonic() - rec.get("last_health", 0) \
                    >= st.config.health_check_period_s:
                rec["health_ref"] = rec["handle"].check_health.remote()
                rec["health_deadline"] = time.monotonic() + \
                    st.config.health_check_timeout_s

    def _start_replica(self, st: _DeploymentState) -> None:
        import ray_tpu
        from ray_tpu.serve.replica import Replica

        actor_opts = dict(st.config.ray_actor_options)
        actor_opts.setdefault("num_cpus", 0.1)
        actor_opts["max_concurrency"] = max(
            8, st.config.max_ongoing_requests + 2)
        try:
            handle = ray_tpu.remote(Replica).options(**actor_opts).remote(
                st.cls, st.init_args, st.init_kwargs,
                st.config.max_ongoing_requests, st.config.user_config,
                app_name=st.app, deployment=st.name,
                max_queued_requests=getattr(
                    st.config, "max_queued_requests", -1))
        except Exception:  # noqa: BLE001
            logger.error("replica start failed:\n%s", traceback.format_exc())
            return
        rid = handle.actor_id
        init_ref = handle.check_health.remote()
        with self._lock:
            if st.superseded or self._state(st.app, st.name) is not st:
                # Lost a race with a redeploy: don't leak the actor.
                ray_tpu.kill(handle)
                return
            st.replicas[rid] = {
                "handle": handle, "state": "STARTING",
                "init_ref": init_ref,
                "init_deadline": time.monotonic() + REPLICA_INIT_TIMEOUT_S,
                "health_ref": None, "health_deadline": 0.0,
                "last_health": time.monotonic()}

    def _remove_replica(self, st: _DeploymentState, rid: str,
                        drain: bool) -> None:
        with self._lock:
            rec = st.replicas.pop(rid, None)
            st.membership_version += 1
        # A removed replica's demoted prefix entries are doomed (its
        # arena objects die with the owning process — every future pull
        # would fail): scrub them so lookups don't chase dead refs.
        # Drained replicas withdraw themselves too (LLMServer.shutdown);
        # this covers crashes and health-check kills.
        self._prefix_store.forget(st.app, replica=rid)
        if rec is not None:
            rec["state"] = "STOPPING"
            self._stop_replica(rec, drain=drain,
                               timeout=st.config.graceful_shutdown_timeout_s)

    def _stop_replica(self, rec: dict, drain: bool = True,
                      timeout: float = 5.0) -> None:
        import ray_tpu

        if drain:
            try:
                ray_tpu.get(rec["handle"].prepare_for_shutdown.remote(),
                            timeout=timeout)
            except Exception:  # noqa: BLE001
                pass
        try:
            ray_tpu.kill(rec["handle"])
        except Exception:  # noqa: BLE001
            pass


def new_version() -> str:
    return uuid.uuid4().hex[:12]
