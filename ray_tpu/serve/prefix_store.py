"""Cluster prefix-cache economy: tiered KV store with cross-replica
prefix sharing.

The per-engine radix prefix cache (serve/kv_blocks.py) caps the
cluster's aggregate cache at ONE engine's HBM pool: a replica that
misses re-prefills even when a sibling — or the object plane — already
holds the exact KV pages.  This module composes the two proofs the
earlier rounds established (KV pages travel the object plane
token-identically; caches must be policy-versioned) into a three-tier
store:

  - **Tier 1** — the engine's HBM radix tree, unchanged.
  - **Tier 2** — cold subtrees demoted leaf-first into SEALED arena
    objects: one object per demoted leaf, holding the KV of the whole
    path root..leaf in the kv_export page layout
    ([2, L, depth, kvh, page, hd]), indexed by the chained blake2b
    block hashes the router already gossips (kv_router.chain_hash — a
    hash h_i commits to the entire prefix through block i, so index
    membership alone proves which slice of the object serves a prompt).
  - **Tier 3** — arena disk spill, for free: sealed objects under
    memory pressure spill like any other object and page back in on
    pull.

Two halves, both dependency-light so the layering invariant holds
(core primitives + public facades + serve siblings only):

  - **StoreDirectory** (controller-side): hash → entry index over the
    published objects.  Every entry is tagged with the publishing
    engine's `seed` and `weight_version`, so an RLHF weight swap
    INVALIDATES instead of corrupting — a version-mismatched entry is
    never returned by lookup.  The directory holds a borrowed ObjectRef
    per entry; dropping an entry releases it, and the owner's free path
    scrubs every node's replica (the add_location invariant — pulls go
    through the normal `ray_tpu.get`, never around the announcement).
  - **PrefixStoreClient** (replica-side): owns the published objects'
    primary refs, publishes demoted subtrees (the engine's demotion
    callback), and runs the miss path: on a shallow local radix match,
    look up the deepest cluster-resident prefix and — gated by the cost
    model below — pull + graft it into the local pool instead of
    re-prefilling.

Cost model: prefill FLOPs avoided vs migration cost.  The seed
constant is the measured ~4.7 ms/migration figure from the PD-disagg
rounds (RAY_TPU_PREFIX_STORE_MIGRATE_MS); the per-token prefill cost
and pull bandwidth are env-tunable too, and a deployment can override
all three through its `prefix_store` config dict.

Kill switch: RAY_TPU_PREFIX_STORE=0 (read per request — same-run A/B),
plus the per-request payload key {"prefix_store": false}.  Failpoint
sites: serve.prefix_demote (publish leg), serve.prefix_fetch (pull
leg), serve.prefix_graft (engine-loop graft, armed in serve/llm.py).
Flight-recorder spans ride the same three legs.
"""
from __future__ import annotations

import logging
import os
import threading
import time

from ray_tpu import tracing
from ray_tpu.serve.kv_router import (matched_depth,  # noqa: F401
                                     prefix_store_on)

logger = logging.getLogger(__name__)

# Named actor the client resolves lazily (literal, NOT imported from
# serve/controller.py: the controller imports this module for its
# directory, and the reverse import would cycle).
_CONTROLLER_NAME = "SERVE_CONTROLLER"

# Cost-model seed constants (env-tunable; per-deployment overrides ride
# the `prefix_store` config dict).  MIGRATE_MS is the measured fixed
# cost of one KV migration through the object plane (~4.7 ms on the
# bench box: put + lookup RT + pull dispatch); PREFILL_US_PER_TOKEN is
# the prefill compute a grafted token avoids; BW_GBPS prices the pull's
# byte volume (same-host direct-shm pulls run far above this — the
# default is deliberately the conservative cross-node figure).
_DEFAULT_MIGRATE_MS = 4.7
_DEFAULT_PREFILL_US_PER_TOKEN = 40.0
_DEFAULT_BW_GBPS = 2.0


# prefix_store_on is DEFINED in kv_router with its sibling
# cluster-serving switches (one copy — the legs must never drift) and
# re-exported here for the natural import site.

def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


def _object_plane_ready() -> bool:
    """True when this process can put/get arena objects: an
    initialized driver OR a connected worker (replicas are workers —
    ray_tpu.is_initialized() is a DRIVER-side flag and stays False in
    them)."""
    import ray_tpu

    if ray_tpu.is_initialized():
        return True
    try:
        from ray_tpu.runtime_context import get_runtime_context

        get_runtime_context()
        return True
    except Exception:  # noqa: BLE001 - no worker in this process
        return False


def migration_worth_it(tokens_saved: int, nbytes: int,
                       config: dict | None = None) -> bool:
    """Graft only when the prefill time avoided beats the migration
    cost (fixed per-migration overhead + the object's bytes at pull
    bandwidth).  Config keys override the env knobs override the seed
    constants."""
    cfg = config or {}
    migrate_ms = cfg.get("migrate_ms", _env_float(
        "RAY_TPU_PREFIX_STORE_MIGRATE_MS", _DEFAULT_MIGRATE_MS))
    us_per_tok = cfg.get("prefill_us_per_token", _env_float(
        "RAY_TPU_PREFIX_STORE_PREFILL_US_PER_TOKEN",
        _DEFAULT_PREFILL_US_PER_TOKEN))
    bw_gbps = cfg.get("bw_gbps", _env_float(
        "RAY_TPU_PREFIX_STORE_BW_GBPS", _DEFAULT_BW_GBPS))
    benefit_ms = tokens_saved * us_per_tok / 1000.0
    cost_ms = migrate_ms + nbytes / max(bw_gbps, 1e-6) / 1e6
    return benefit_ms > cost_ms


class StoreDirectory:
    """Controller-side index of the cluster's demoted prefix objects.

    One instance lives on the ServeController (thread-safe: the
    controller is a threaded actor); tests may also instantiate one
    directly and hand it to a PrefixStoreClient, which then calls it
    in-process instead of over RPC.

    Entries are keyed by the demoted LEAF's chained hash; the index
    maps EVERY hash along the entry's chain to (leaf, depth), so a
    prompt matching only part of a demoted path still finds the entry
    and grafts the matching slice.  Byte budget
    (RAY_TPU_PREFIX_STORE_MAX_BYTES) evicts oldest-published first —
    dropping an entry releases the directory's borrowed ref; the
    publisher's own ref (and ultimately the owner free path, which
    scrubs every announced replica location) does the rest.
    """

    def __init__(self, max_bytes: int | None = None):
        self._lock = threading.Lock()
        self._max_bytes = max_bytes if max_bytes is not None else \
            _env_int("RAY_TPU_PREFIX_STORE_MAX_BYTES", 1 << 30)
        # app -> {"entries": {leaf_hash: entry}, "index": {hash: (leaf, depth)}}
        self._apps: dict[str, dict] = {}
        self._bytes = 0
        self.published = 0
        self.evicted = 0
        self.forgotten = 0
        self.lookups = 0
        self.lookup_hits = 0

    # ------------------------------------------------------------ write
    def publish(self, app: str, meta: dict, ref) -> dict:
        """Register one demoted subtree.  `meta` carries the chain
        hashes (root..leaf), page size, engine seed, weight version,
        byte size, and the publishing replica's id; `ref` is the sealed
        arena object (kv_export layout, depth == len(hashes)).

        Returns {"ok": bool, "live": [leaf hashes]} — `ok` is False
        when the entry did NOT survive registration (e.g. it was
        immediately evicted by the byte cap): the publisher must then
        KEEP its tier-1 copy.  `live` lists every entry the directory
        still holds for this replica, so the publisher can drop the
        primary refs of entries the directory evicted/forgot since —
        without this reconciliation the byte cap would bound only the
        index while the arena bytes leaked until replica shutdown."""
        # The ref arrives nested (one-element list) when it crosses the
        # controller RPC: a top-level ObjectRef arg would be resolved
        # to the whole KV array before execution, making the directory
        # hold tier-2 bytes host-side instead of a borrowed ref.
        if isinstance(ref, list):
            ref = ref[0]
        hashes = [int(h) for h in meta["hashes"]]
        if not hashes:
            return {"ok": False, "live": []}
        leaf = hashes[-1]
        entry = {
            "ref": ref,
            "hashes": hashes,
            "page": int(meta["page"]),
            "seed": meta.get("seed"),
            "weight_version": int(meta.get("weight_version", 0)),
            "nbytes": int(meta.get("nbytes", 0)),
            "replica": meta.get("replica"),
            "deployment": meta.get("deployment"),
            "t": time.monotonic(),
        }
        replica = meta.get("replica")
        with self._lock:
            if entry["nbytes"] > self._max_bytes:
                # An entry that can NEVER fit must not evict healthy
                # siblings on its way to being evicted itself.
                a = self._apps.get(app)
                live = [h for h, e in (a["entries"].items() if a
                                       else ()) if e["replica"] == replica]
                return {"ok": False, "live": live}
            a = self._apps.setdefault(app, {"entries": {}, "index": {}})
            old = a["entries"].pop(leaf, None)
            if old is not None:
                self._bytes -= old["nbytes"]
            a["entries"][leaf] = entry
            self._bytes += entry["nbytes"]
            self._reindex_locked(a)
            self.published += 1
            self._evict_over_cap_locked()
            # The cap may have evicted the very entry being published
            # (oldest, or larger than the whole budget): report that —
            # a True here would make the engine drop the LAST copy.
            a = self._apps.get(app)
            ok = a is not None and a["entries"].get(leaf) is entry
            live = [h for h, e in (a["entries"].items() if a else ())
                    if e["replica"] == replica]
        return {"ok": ok, "live": live}

    def _reindex_locked(self, a: dict) -> None:
        idx: dict[int, tuple[int, int]] = {}
        for leaf, e in a["entries"].items():
            for i, h in enumerate(e["hashes"]):
                idx.setdefault(h, (leaf, i + 1))
        a["index"] = idx

    def _evict_over_cap_locked(self) -> None:
        while self._bytes > self._max_bytes:
            oldest = None
            for app, a in self._apps.items():
                for leaf, e in a["entries"].items():
                    if oldest is None or e["t"] < oldest[2]["t"]:
                        oldest = (app, leaf, e)
            if oldest is None:
                return
            app, leaf, e = oldest
            a = self._apps[app]
            del a["entries"][leaf]
            self._bytes -= e["nbytes"]
            self._reindex_locked(a)
            self.evicted += 1

    def forget(self, app: str, replica: str | None = None,
               below_version: int | None = None,
               hashes: list | None = None) -> int:
        """Drop entries by replica / weight-version bound / explicit
        leaf hashes.  Returns the number dropped."""
        drop_hashes = {int(h) for h in hashes} if hashes else None
        n = 0
        with self._lock:
            a = self._apps.get(app)
            if a is None:
                return 0
            for leaf, e in list(a["entries"].items()):
                if replica is not None and e["replica"] != replica:
                    continue
                if below_version is not None \
                        and e["weight_version"] >= below_version:
                    continue
                if drop_hashes is not None and leaf not in drop_hashes:
                    continue
                del a["entries"][leaf]
                self._bytes -= e["nbytes"]
                n += 1
            if n:
                self._reindex_locked(a)
                self.forgotten += n
            if not a["entries"]:
                self._apps.pop(app, None)
        return n

    def drop_app(self, app: str) -> int:
        with self._lock:
            a = self._apps.pop(app, None)
            if a is None:
                return 0
            n = len(a["entries"])
            self._bytes -= sum(e["nbytes"] for e in a["entries"].values())
            self.forgotten += n
        return n

    def drop_replica(self, replica: str) -> int:
        """Scrub a dead replica's entries everywhere (its objects die
        with the owning process — lookups against them would only
        fail)."""
        n = 0
        for app in list(self._apps):
            n += self.forget(app, replica=replica)
        return n

    def clear(self) -> int:
        with self._lock:
            n = sum(len(a["entries"]) for a in self._apps.values())
            self._apps.clear()
            self._bytes = 0
            self.forgotten += n
        return n

    # ------------------------------------------------------------- read
    def lookup(self, app: str, hashes: list, page: int, seed,
               weight_version: int | None = None,
               min_depth: int = 0) -> dict | None:
        """Deepest stored prefix of a prompt's hash chain, filtered by
        page/seed/weight_version (a mismatched entry is skipped, never
        returned — the RLHF-swap safety contract).  `min_depth` is the
        caller's local radix depth: only a STRICTLY deeper stored
        prefix is worth a migration."""
        with self._lock:
            self.lookups += 1
            a = self._apps.get(app)
            if a is None:
                return None
            for i in range(len(hashes) - 1, min_depth - 1, -1):
                hit = a["index"].get(int(hashes[i]))
                if hit is None:
                    continue
                leaf, _d = hit
                e = a["entries"].get(leaf)
                if e is None:
                    continue
                if e["page"] != page:
                    continue
                if seed is not None and e["seed"] is not None \
                        and e["seed"] != seed:
                    continue
                if weight_version is not None \
                        and e["weight_version"] != weight_version:
                    continue
                self.lookup_hits += 1
                return {"ref": e["ref"], "depth": i + 1,
                        "entry_depth": len(e["hashes"]),
                        "nbytes": e["nbytes"], "hash": leaf,
                        "weight_version": e["weight_version"],
                        "replica": e["replica"]}
        return None

    def summary(self, app: str) -> dict:
        """The app's cluster-resident prefix hashes, grouped by page
        size — the router-side view (handle.py polls this next to the
        replica summaries so scoring can see prefixes no live radix
        tree holds)."""
        with self._lock:
            a = self._apps.get(app)
            pages: dict[int, list[int]] = {}
            n = 0
            if a is not None:
                n = len(a["entries"])
                for h, (leaf, _d) in a["index"].items():
                    e = a["entries"].get(leaf)
                    if e is not None:
                        pages.setdefault(e["page"], []).append(h)
            return {"pages": pages, "entries": n}

    def bytes_by_deployment(self) -> dict[tuple[str, str], int]:
        """(app, deployment) -> tier-2 bytes — the per-deployment gauge
        the serve controller publishes (memory-ledger observability)."""
        out: dict[tuple[str, str], int] = {}
        with self._lock:
            for app, a in self._apps.items():
                for e in a["entries"].values():
                    key = (app, e.get("deployment") or "?")
                    out[key] = out.get(key, 0) + e["nbytes"]
        return out

    def replicas(self) -> set[str]:
        """Every replica id with at least one live entry (the serve
        controller's tier-2 orphan check compares these against its
        live replica set)."""
        with self._lock:
            return {e["replica"] for a in self._apps.values()
                    for e in a["entries"].values()
                    if e.get("replica")}

    def stats(self) -> dict:
        with self._lock:
            return {
                "apps": len(self._apps),
                "entries": sum(len(a["entries"])
                               for a in self._apps.values()),
                "bytes": self._bytes,
                "published": self.published,
                "evicted": self.evicted,
                "forgotten": self.forgotten,
                "lookups": self.lookups,
                "lookup_hits": self.lookup_hits,
            }


class PrefixStoreClient:
    """Replica-side half: publishes demoted subtrees and runs the
    miss-path fetch/graft.  Owns the primary ObjectRef of every object
    this replica published — `close()` (replica shutdown / app delete)
    drops them all and tells the directory to forget, so tier-2 never
    outlives its app (the kv_check leak contract)."""

    def __init__(self, *, app: str, deployment: str, replica_id: str,
                 seed, page: int, config: dict | None = None,
                 directory: StoreDirectory | None = None):
        self._app = app or "default"
        self._deployment = deployment
        self._replica_id = replica_id
        self._seed = seed
        self._page = page
        self._cfg = dict(config or {})
        self._directory = directory
        self._ctrl = None
        self._ctrl_retry_at = 0.0
        self._lock = threading.Lock()
        # leaf hash -> (ref, weight_version, nbytes): the primary refs.
        self._objects: dict[int, tuple] = {}
        # Graft coalescing: entry hash -> Event for the in-flight pull;
        # concurrent requests for one hot prefix must not pull the
        # object once each — followers wait and then prefix-hit the
        # leader's grafted blocks in tier 1.
        self._graft_inflight: dict[int, threading.Event] = {}
        self._closed = False
        self.published = 0
        self.publish_bytes = 0
        self.fetches = 0
        self.fetch_bytes = 0
        self.grafts = 0
        self.graft_tokens = 0
        self.fallbacks = 0
        self.stale_rejected = 0
        self.lookup_misses = 0
        self.cost_skipped = 0

    # -------------------------------------------------------- transport
    def _controller(self):
        if self._directory is not None:
            return None
        if not _object_plane_ready():
            return None
        import ray_tpu

        with self._lock:
            if self._ctrl is not None:
                return self._ctrl
            if time.monotonic() < self._ctrl_retry_at:
                return None
        try:
            ctrl = ray_tpu.get_actor(_CONTROLLER_NAME)
        except Exception:  # noqa: BLE001 - serve not running
            with self._lock:
                self._ctrl_retry_at = time.monotonic() + 5.0
            return None
        with self._lock:
            self._ctrl = ctrl
        return ctrl

    def _call(self, verb: str, *args, timeout: float = 10.0,
              default=None, **kwargs):
        """Directory call: in-process when a directory was injected
        (tests), otherwise through the controller's prefix_store_*
        RPC verbs."""
        if self._directory is not None:
            return getattr(self._directory, verb)(*args, **kwargs)
        ctrl = self._controller()
        if ctrl is None:
            return default
        import ray_tpu

        try:
            ref = getattr(ctrl, "prefix_store_" + verb).remote(
                *args, **kwargs)
            return ray_tpu.get(ref, timeout=timeout)
        except Exception:  # noqa: BLE001 - controller restarting
            with self._lock:
                self._ctrl = None
                self._ctrl_retry_at = time.monotonic() + 5.0
            return default

    # ---------------------------------------------------------- publish
    def publish(self, entry: dict) -> bool:
        """Demotion callback (runs on the engine's export thread):
        seal the subtree's host KV into an arena object and register it
        with the directory.  Returns True when tier 2 holds the entry —
        the engine's cue that evicting the tier-1 leaf loses nothing.
        entry: {tokens, kv, hashes, depth, page, weight_version, and
        the fetch's `pieces` / `fetch_ms` for the span}.
        (The serve.prefix_demote failpoint fires on the ENGINE side of
        this callback — llm.py _demote_one — so the fault window covers
        any publisher.)"""
        if self._closed or not prefix_store_on():
            return False
        h = int(entry["hashes"][-1])
        version = int(entry.get("weight_version", 0))
        kv = entry["kv"]
        t0 = time.time()
        with self._lock:
            cur = self._objects.get(h)
        if cur is not None and cur[1] == version:
            # Already sealed under this version: reuse the object, but
            # ALWAYS re-register with the directory — its copy of the
            # entry may be gone (byte-cap eviction, a failed-fetch
            # scrub, a restarted controller), and returning True on the
            # local cache alone would let the engine drop the LAST
            # remaining copy of the prefix.
            ref, nbytes = cur[0], cur[2]
        elif _object_plane_ready():
            import ray_tpu
            from ray_tpu import memledger

            with memledger.tag(
                    "prefix_tier2",
                    label=f"serve/prefix_store.py tier2 "
                          f"{self._deployment}"):
                ref = ray_tpu.put(kv)
            nbytes = int(kv.nbytes)
        elif self._directory is not None:
            # In-process directory with no object plane (unit tests):
            # the host array itself is the payload.
            ref, nbytes = kv, int(kv.nbytes)
        else:
            return False
        meta = {"hashes": [int(x) for x in entry["hashes"]],
                "page": int(entry["page"]), "seed": self._seed,
                "weight_version": version, "nbytes": nbytes,
                "replica": self._replica_id,
                "deployment": self._deployment}
        # Nest the ref so it survives the RPC as a ref (top-level
        # ObjectRef args resolve to values before execution — the
        # directory would end up holding the KV bytes themselves).
        reply = self._call("publish", self._app, meta, [ref],
                           default=None)
        ok = bool(reply and reply.get("ok"))
        if tracing.ENABLED:
            tracing.emit("serve.prefix_demote", t0, attrs={
                "bytes": nbytes, "depth": int(entry["depth"]),
                "weight_version": version, "ok": ok,
                # how the engine's export thread fetched the KV
                "pieces": entry.get("pieces"),
                "fetch_ms": entry.get("fetch_ms")})
        if not ok:
            del ref
            with self._lock:
                self._objects.pop(h, None)
            return False
        with self._lock:
            if self._closed:
                # Shutdown raced the publish: withdraw immediately so
                # the object can't outlive the app.
                self._objects.pop(h, None)
                ok = False
            else:
                if cur is None:
                    self.published += 1
                    self.publish_bytes += nbytes
                self._objects[h] = (ref, version, nbytes)
                # Reconcile against the directory's view: entries it
                # evicted/forgot since our last publish are unreachable
                # — holding their primary refs would leak arena bytes
                # past the configured cap until replica shutdown.
                live = {int(x) for x in reply.get("live", ())}
                live.add(h)
                for stale in [k for k in self._objects
                              if k not in live]:
                    del self._objects[stale]
        if not ok:
            self._call("forget", self._app, hashes=[h], timeout=5.0)
        return bool(ok)

    # ------------------------------------------------------------ fetch
    def maybe_graft(self, engine, prompt: list, *,
                    salt: int = 0) -> dict:
        """The miss path (blocking; callers run it off the event loop):
        compare the local radix match against the cluster directory and
        — when the cost model approves — pull the stored prefix and
        graft it into the engine's pool.  Every failure degrades to a
        local prefill, never fails the request.  `salt` is the
        request's adapter KV identity (serve/lora.adapter_salt): the
        chain hashes — and with them the directory lookup and the
        graft's radix commit — are salt-distinct, so a stored prefix
        only ever serves the (adapter, version) that computed it."""
        from ray_tpu.serve import kv_router

        out = {"grafted": 0}
        page = engine.page
        hashes = kv_router.prompt_hashes(prompt, page, salt)
        if not hashes:
            return out
        local_summary = engine._mgr.prefix_summary()
        local = matched_depth(hashes, frozenset(local_summary["hashes"]))
        max_gain = (len(hashes) - local) * page
        min_tokens = int(self._cfg.get("min_tokens", page))
        # Pre-gate on the BEST-CASE gain: when even a full-depth hit
        # couldn't beat the migration cost, skip the directory RT
        # entirely (the lookup is a controller round trip).
        if max_gain < min_tokens \
                or not migration_worth_it(max_gain, 0, self._cfg):
            return out
        entry = self._call("lookup", self._app, [int(h) for h in hashes],
                           page, self._seed, engine.weight_version,
                           min_depth=local, default=None)
        if not entry:
            self.lookup_misses += 1
            return out
        depth = int(entry["depth"])
        tokens_saved = (depth - local) * page
        if tokens_saved < min_tokens or not migration_worth_it(
                tokens_saved, int(entry.get("nbytes", 0)), self._cfg):
            self.cost_skipped += 1
            return out
        h = int(entry["hash"])
        with self._lock:
            leader = self._graft_inflight.get(h)
            if leader is None:
                self._graft_inflight[h] = threading.Event()
            # else: follower — wait below, outside the lock.
        if leader is not None:
            leader.wait(timeout=60.0)
            return {"grafted": 0, "reason": "coalesced"}
        from ray_tpu import failpoints

        pulled = False
        try:
            try:
                if failpoints.ACTIVE:
                    failpoints.fire("serve.prefix_fetch")
                import numpy as np

                from ray_tpu.object_ref import ObjectRef

                with tracing.span("serve.prefix_fetch", attrs={
                        "depth": depth, "local_depth": local,
                        "bytes": int(entry.get("nbytes", 0)),
                        "replica": entry.get("replica")}):
                    payload = entry["ref"]
                    if isinstance(payload, ObjectRef):
                        import ray_tpu

                        payload = ray_tpu.get(payload, timeout=30.0)
                    blob = np.asarray(payload)
                pulled = True
                self.fetches += 1
                self.fetch_bytes += int(blob.nbytes)
                kv = blob[:, :, :depth]
                with tracing.span("serve.prefix_graft", attrs={
                        "tokens": depth * page,
                        "saved": tokens_saved}):
                    res = engine.kv_graft(
                        list(prompt[:depth * page]), kv,
                        kv_len=depth * page,
                        weight_version=entry.get("weight_version"),
                        salt=salt,
                    ).result(timeout=60.0)
                del blob, kv
            except BaseException:  # noqa: BLE001 - degrade, never fail
                self.fallbacks += 1
                if not pulled:
                    # A FAILED PULL is the dead-publisher signature —
                    # scrub the doomed entry (the publisher re-registers
                    # on its next demotion if it is in fact alive).
                    # Post-pull failures (a busy engine timing out the
                    # graft) say nothing about the entry: keep it.
                    self._call("forget", self._app,
                               hashes=[entry["hash"]], timeout=5.0)
                return out
        finally:
            with self._lock:
                ev = self._graft_inflight.pop(h, None)
            if ev is not None:
                ev.set()
        if res.get("grafted"):
            self.grafts += 1
            self.graft_tokens += tokens_saved
            return res
        if res.get("reason") == "stale_version":
            self.stale_rejected += 1
        else:
            self.fallbacks += 1
        return res

    # -------------------------------------------------------- lifecycle
    def invalidate(self, current_version: int) -> int:
        """Live weight swap: every entry published under an OLDER
        weight version is stale — drop the primary refs and tell the
        directory to forget (lookup's version filter already refuses
        them; this reclaims the arena bytes too)."""
        dropped = 0
        with self._lock:
            for h, (ref, v, nbytes) in list(self._objects.items()):
                if v < current_version:
                    del self._objects[h]
                    dropped += 1
        if dropped:
            self._call("forget", self._app, replica=self._replica_id,
                       below_version=current_version, timeout=5.0)
        return dropped

    def object_count(self) -> int:
        with self._lock:
            return len(self._objects)

    def close(self) -> None:
        """Replica shutdown / app delete: drop every published object's
        primary ref and withdraw from the directory — demoted subtrees
        must not outlive their app."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            had = bool(self._objects)
            self._objects.clear()
        if had:
            self._call("forget", self._app, replica=self._replica_id,
                       timeout=5.0)

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": True,
                "objects": len(self._objects),
                "object_bytes": sum(o[2]
                                    for o in self._objects.values()),
                "published": self.published,
                "publish_bytes": self.publish_bytes,
                "fetches": self.fetches,
                "fetch_bytes": self.fetch_bytes,
                "grafts": self.grafts,
                "graft_tokens": self.graft_tokens,
                "fallbacks": self.fallbacks,
                "stale_rejected": self.stale_rejected,
                "lookup_misses": self.lookup_misses,
                "cost_skipped": self.cost_skipped,
            }
