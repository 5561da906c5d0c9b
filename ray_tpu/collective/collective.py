"""Collective groups over the object plane (the gloo-analog backend).

Analog of ray: python/ray/util/collective/collective.py — same public
functions, same group-name semantics.  This is the DCN control-plane
path — for device collectives inside a slice use jax.lax collectives
under pjit/shard_map (ray_tpu.parallel), which XLA schedules over ICI
(SURVEY §2.4).

Backends (ISSUE 5):

- **ring / tree** (default): bandwidth-optimal pipelined schedules in
  `ring.py`.  Large tensors (>= RAY_TPU_COLLECTIVE_RING_MIN_BYTES) take
  the ring reduce-scatter + allgather — 2*N*(world-1)/world bytes per
  rank, chunks hopping peer-to-peer as object-plane puts, reduce
  overlapped against transport; small tensors take a binomial tree
  (2*ceil(log2 world) hops, payload inline).  The named `_Rendezvous`
  actor carries only neighbor mailbox matching and seq bookkeeping —
  never bulk payload.
- **legacy gather** (RAY_TPU_RING_COLLECTIVES=0): the original
  "gather all world_size refs, reduce locally" path — O(world*N) bytes
  pulled per rank — kept selectable for same-run A/B.

Async variants (`allreduce_async`, ...) return a wait()-able
`CollectiveWork`; per group, ops execute on a dedicated thread in
submission (seq) order, so a train step can kick off its host-side
sync and overlap the next step's input pipeline.

Every exchange is deadline-bounded: a rank that crashes mid-collective
surfaces on the survivors as a diagnostic error naming the missing
rank(s), never a hang.

Opt-in phase tracer: `ray_tpu.profiling.collective_trace()` /
`collective_breakdown_us()` — per-collective send/pull/reduce/wait
accumulation plus sent/recv byte counters (the schedule-shape proof).
"""
from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any

import numpy as np

import ray_tpu
from ray_tpu import failpoints, memledger, profiling, tracing
from ray_tpu.collective import ring as _ring
from ray_tpu.collective.ring import _env_float, _env_int

# Process-global group registry (ray: collective.py GroupManager:40 is a
# process singleton).  NOT thread-local: actor methods may run on any
# thread of the actor's pool (max_concurrency > 1).
_registry_lock = threading.Lock()
_registry: dict[str, "_GroupState"] = {}

_TRUTHY = ("1", "true", "yes", "on")


def _ring_enabled() -> bool:
    """Kill switch: RAY_TPU_RING_COLLECTIVES=0 restores the legacy
    gather path (same-run A/B; read at call time so a live process can
    flip it)."""
    return os.environ.get(
        "RAY_TPU_RING_COLLECTIVES", "1").lower() in _TRUTHY


def _ring_min_bytes() -> int:
    return _env_int("RAY_TPU_COLLECTIVE_RING_MIN_BYTES", 256 * 1024)


class _Rendezvous:
    """Named actor: neighbor mailbox + per-(seq, op) contribution
    matching.  Async actor so waiting ranks don't block each other (the
    reference's rendezvous is the NCCL unique-id store, collective_group/
    nccl_collective_group.py _rendezvous helpers).  On the ring/tree
    paths it never touches bulk payload — only refs and small inline
    arrays ride through it."""

    def __init__(self, world_size: int):
        import asyncio

        self.world_size = world_size
        # (seq, op) -> {"refs": {rank: obj}, "event": asyncio.Event,
        #               "taken": int, "error": str | None}
        self.pending: dict = {}
        self.p2p: dict = {}
        self.asyncio = asyncio

    async def configure(self, world_size: int) -> None:
        """Re-arm for a (re-)created group: a mismatched world_size means a
        new incarnation reused this detached actor's name — old pending
        slots would release collectives early or hand back stale refs."""
        if world_size != self.world_size:
            self.world_size = world_size
            self.pending.clear()
            self.p2p.clear()

    def _slot(self, key):
        slot = self.pending.get(key)
        if slot is None:
            slot = {"refs": {}, "event": self.asyncio.Event(), "taken": 0,
                    "error": None}
            self.pending[key] = slot
        return slot

    async def exchange(self, key, rank: int, ref,
                       timeout_s: float | None = None) -> dict:
        """Deposit rank's contribution; wait for all; return all refs.
        Deadline-bounded: on timeout each waiter raises a diagnostic
        naming the ranks that never arrived (satellite: a crashed rank
        must not block its peers forever)."""
        key = tuple(key)
        slot = self._slot(key)
        slot["refs"][rank] = ref
        if len(slot["refs"]) == self.world_size:
            slot["event"].set()
        try:
            if timeout_s is None:
                await slot["event"].wait()
            else:
                await self.asyncio.wait_for(slot["event"].wait(),
                                            timeout_s)
        except self.asyncio.TimeoutError:
            present = sorted(slot["refs"])
            missing = sorted(set(range(self.world_size))
                             - set(slot["refs"]))
            # Late arrivals must not complete against a half-abandoned
            # slot; drop it so they fail fast on their own timeout.
            self.pending.pop(key, None)
            raise TimeoutError(
                f"collective exchange {key} timed out after {timeout_s}s:"
                f" missing ranks {missing} (present: {present}, "
                f"world_size {self.world_size})") from None
        if slot["error"]:
            raise RuntimeError(slot["error"])
        refs = dict(slot["refs"])
        slot["taken"] += 1
        if slot["taken"] == self.world_size:
            self.pending.pop(key, None)
        return refs

    def _p2p_queue(self, key):
        q = self.p2p.get(tuple(key))
        if q is None:
            # asyncio.Queue gives FIFO matching of repeated sends with the
            # same (src, dst, tag) — no lost messages on rapid re-send.
            q = self.asyncio.Queue()
            self.p2p[tuple(key)] = q
        return q

    async def put_p2p(self, key, ref) -> None:
        await self._p2p_queue(key).put(ref)

    async def take_p2p(self, key, timeout_s: float | None = None):
        """Take one mailbox message; deadline-bounded with a diagnostic
        naming the key (whose src rank never delivered) on timeout."""
        key = tuple(key)
        q = self._p2p_queue(key)
        try:
            if timeout_s is None:
                msg = await q.get()
            else:
                msg = await self.asyncio.wait_for(q.get(), timeout_s)
        except self.asyncio.TimeoutError:
            if q.empty():
                self.p2p.pop(key, None)
            raise TimeoutError(
                f"collective p2p take {key} timed out after "
                f"{timeout_s}s: the sending rank never deposited "
                f"(crashed mid-collective? ranks disagreeing on the "
                f"schedule — e.g. heterogeneous tensor sizes straddling "
                f"RAY_TPU_COLLECTIVE_RING_MIN_BYTES?)") from None
        if q.empty():
            self.p2p.pop(key, None)
        if isinstance(msg, dict) and msg.get("__drained__"):
            raise RuntimeError(msg["__drained__"])
        return msg

    async def swap(self, put_key, msg, take_key,
                   timeout_s: float | None = None):
        """One ring hop's mailbox work in ONE round trip: deposit the
        outgoing message, then await the incoming one.  Every rank's
        swap deposits before it waits, so the ring always progresses."""
        await self._p2p_queue(put_key).put(msg)
        return await self.take_p2p(take_key, timeout_s)

    async def drain(self, reason: str) -> int:
        """Fail every parked waiter with `reason` and clear all slots —
        destroy_collective_group calls this before killing the actor so
        blocked peers get a diagnostic error instead of ActorDiedError."""
        n = 0
        for slot in self.pending.values():
            slot["error"] = reason
            slot["event"].set()
            n += 1
        for q in self.p2p.values():
            # One marker per parked getter is enough; extras are GC'd
            # with the actor.
            for _ in range(8):
                q.put_nowait({"__drained__": reason})
            n += 1
        self.pending.clear()
        self.p2p.clear()
        return n

    async def stats(self) -> dict:
        return {"pending_slots": len(self.pending),
                "p2p_queues": len(self.p2p),
                "world_size": self.world_size}


class CollectiveWork:
    """Handle returned by the *_async collectives: `wait()`/`result()`
    block for (and return) the collective's result; exceptions from the
    schedule (timeouts naming missing ranks, ConnectionLost, ...)
    re-raise here."""

    def __init__(self, fut, seq: int):
        self._fut = fut
        self.seq = seq

    def wait(self, timeout: float | None = None):
        return self._fut.result(timeout)

    # ray.get-style alias
    def result(self, timeout: float | None = None):
        return self.wait(timeout)

    def done(self) -> bool:
        return self._fut.done()


class _GroupState:
    def __init__(self, name: str, world_size: int, rank: int, rendezvous,
                 timeout_s: float):
        self.name = name
        self.world_size = world_size
        self.rank = rank
        self.rendezvous = rendezvous
        self.seq = 0
        self.timeout_s = timeout_s
        self.pipeline_chunks = _env_int(
            "RAY_TPU_COLLECTIVE_PIPELINE_CHUNKS", 4)
        self.pipeline_min_bytes = _env_int(
            "RAY_TPU_COLLECTIVE_PIPELINE_MIN_BYTES", 1 * 1024 * 1024)
        self._lock = threading.Lock()
        # Ordered op pool per group: async and sync collectives share
        # it, so with the default single worker execution order == seq
        # (submission) order.  RAY_TPU_COLLECTIVE_INFLIGHT_OPS>1 lets
        # INDEPENDENT async ops overlap (op k+1's reduce-scatter under
        # op k's allgather — mailbox keys are seq-scoped, so concurrent
        # ops never cross-talk); results still arrive on their own
        # CollectiveWork regardless of completion order.
        self.inflight_ops = max(1, _env_int(
            "RAY_TPU_COLLECTIVE_INFLIGHT_OPS", 1))
        self._ops = ThreadPoolExecutor(
            max_workers=self.inflight_ops,
            thread_name_prefix=f"col-{name}-r{rank}")
        # Prefetch pool: a hop's sub-chunk pulls run concurrently (their
        # round trips overlap) while the reduce consumes them in order —
        # transport of sub-chunk k+1 overlaps the reduce of k.
        self.prefetcher = ThreadPoolExecutor(
            max_workers=max(2, self.pipeline_chunks),
            thread_name_prefix=f"col-pf-{name}-r{rank}")

    def submit(self, fn) -> CollectiveWork:
        """Assign the next seq under the lock and queue `fn(seq)` on the
        ordered op thread.  The caller's trace context is captured HERE
        (API-call time, caller thread) and re-installed around the op —
        the op thread otherwise has no idea which request/step asked."""
        ctx = tracing.capture() if tracing.ENABLED else None

        def run(seq: int):
            with tracing.context(ctx):
                return fn(seq)

        with self._lock:
            self.seq += 1
            seq = self.seq
            fut = self._ops.submit(run, seq)
        return CollectiveWork(fut, seq)

    def close(self) -> None:
        self._ops.shutdown(wait=False)
        self.prefetcher.shutdown(wait=False)


def _groups() -> dict:
    return _registry


def init_collective_group(world_size: int, rank: int,
                          backend: str = "object_store",
                          group_name: str = "default",
                          timeout_s: float | None = None) -> None:
    """Join a collective group; call from every participating actor/task
    (ray: collective.py:120).

    Re-using a group NAME for a new incarnation requires
    `destroy_collective_group` in between (it drains and kills the
    rendezvous, so the re-create binds a FRESH actor — the train restart
    loop does this).  Without a destroy, a same-world re-init reuses the
    detached rendezvous via get_if_exists and `configure` can only scrub
    stale slots when world_size CHANGED: an unconditional clear would
    race a concurrent group creation (rank A's first deposits landing
    while rank B's configure still runs would be wiped)."""
    if rank < 0 or rank >= world_size:
        raise ValueError(f"rank {rank} out of range for world {world_size}")
    if timeout_s is None:
        timeout_s = _env_float("RAY_TPU_COLLECTIVE_TIMEOUT_S", 120.0)
    rdv = ray_tpu.remote(_Rendezvous).options(
        name=f"collective_rdv:{group_name}", get_if_exists=True,
        lifetime="detached",
        max_concurrency=max(64, world_size * 8),
        num_cpus=0).remote(world_size)
    # A stale rendezvous (same name, earlier group incarnation) must not
    # carry its old world_size or pending slots into this group.
    ray_tpu.get(rdv.configure.remote(world_size))
    with _registry_lock:
        old = _registry.pop(group_name, None)
        _registry[group_name] = _GroupState(group_name, world_size, rank,
                                            rdv, timeout_s)
    if old is not None:
        old.close()


def create_collective_group(actors: list, world_size: int, ranks: list[int],
                            backend: str = "object_store",
                            group_name: str = "default") -> None:
    """Driver-side declaration (ray: collective.py create_collective_group):
    each actor must expose an `init_collective_group(world_size, rank,
    backend, group_name)` method (typically calling this module's
    init_collective_group)."""
    refs = [a.init_collective_group.remote(world_size, r, backend, group_name)
            for a, r in zip(actors, ranks)]
    ray_tpu.get(refs)


def deregister_collective_group(group_name: str = "default") -> None:
    """Local-only teardown: drop THIS process's group state (op threads,
    prefetch pool) without touching the shared rendezvous.  The elastic
    train path uses it at a membership-epoch change: the DRIVER destroys
    the stale epoch's group cluster-wide (draining parked waiters);
    each surviving worker only needs to forget its local handle before
    joining the next epoch's group."""
    with _registry_lock:
        g = _registry.pop(group_name, None)
    if g is not None:
        g.close()


def destroy_collective_group(group_name: str = "default",
                             reason: str | None = None) -> None:
    """Tear down the group cluster-wide (ray: collective.py
    destroy_collective_group).  Call only after all ranks are done —
    or, at an elastic epoch change, to UNPARK ranks still waiting on a
    collective with a dead peer: `reason` becomes the diagnostic every
    parked waiter raises (default names the destroy itself).

    Works from ANY process: the pre-round-10 version only killed the
    rendezvous when the calling process had the group in its local
    registry — a driver that formed the group via create_collective_group
    (whose registry is in the ACTORS, not here) leaked the detached
    actor and all its pending slots forever.  Now the named actor is
    resolved directly, drained (parked waiters get a diagnostic error,
    slots are cleared), then killed."""
    with _registry_lock:
        g = _registry.pop(group_name, None)
    rdv = g.rendezvous if g is not None else None
    if g is not None:
        g.close()
    if rdv is None:
        try:
            rdv = ray_tpu.get_actor(f"collective_rdv:{group_name}")
        except Exception:  # noqa: BLE001 - never created / already gone
            rdv = None
    if rdv is not None:
        try:
            ray_tpu.get(rdv.drain.remote(
                reason or f"collective group {group_name!r} destroyed"),
                timeout=10.0)
        except Exception:  # noqa: BLE001 - best effort before the kill
            pass
        try:
            ray_tpu.kill(rdv)
        except Exception:  # noqa: BLE001 - another rank already killed it
            pass
        # Wait (bounded) for the name to release: an immediate re-create
        # of the same group would otherwise get_if_exists the DYING
        # actor and fail its first ops (the controller hides the actor
        # only once it is marked DEAD).
        import time as _t

        deadline = _t.monotonic() + 10.0
        while _t.monotonic() < deadline:
            try:
                ray_tpu.get_actor(f"collective_rdv:{group_name}")
            except Exception:  # noqa: BLE001 - gone
                break
            _t.sleep(0.1)


def get_rank(group_name: str = "default") -> int:
    g = _groups().get(group_name)
    return g.rank if g else -1


def get_collective_group_size(group_name: str = "default") -> int:
    g = _groups().get(group_name)
    return g.world_size if g else -1


def _group(group_name: str) -> _GroupState:
    g = _groups().get(group_name)
    if g is None:
        raise RuntimeError(
            f"collective group {group_name!r} not initialized in this "
            f"process; call init_collective_group first")
    return g


# ------------------------------------------------------------ legacy path
def _exchange(g: _GroupState, op: str, value, seq: int) -> dict:
    if failpoints.ACTIVE:
        failpoints.fire("collective.chunk_send")
    with memledger.tag("collective_chunk",
                       label="collective/collective.py exchange"):
        ref = ray_tpu.put(value)
    # Refs ride inside a list: a bare ObjectRef argument is resolved to its
    # value before dispatch (task dependency resolution), but the
    # rendezvous must pass the *ref* through untouched (same wrapping trick
    # as ray: util/collective passing refs in containers).
    refs = ray_tpu.get(g.rendezvous.exchange.remote(
        (op, seq), g.rank, [ref], g.timeout_s),
        timeout=g.timeout_s + 30.0)
    return {r: ray_tpu.get(refs[r][0]) for r in sorted(refs)}


_REDUCE_OPS = {
    "sum": lambda xs: np.sum(xs, axis=0),
    "prod": lambda xs: np.prod(xs, axis=0),
    "max": lambda xs: np.max(xs, axis=0),
    "min": lambda xs: np.min(xs, axis=0),
}


def _gather_parts(g: _GroupState, tag: str, value, seq: int,
                  rec: dict | None) -> dict:
    """Legacy transport: every rank's ref through the rendezvous, every
    rank pulls all of them — O(world*N) bytes per rank, which is exactly
    what the tracer shows vs the ring."""
    parts = _exchange(g, tag, value, seq)
    if rec is not None:
        rec["sent_bytes"] += getattr(value, "nbytes", 0)
        rec["recv_bytes"] += sum(
            getattr(v, "nbytes", 0) for r, v in parts.items()
            if r != g.rank)
        rec["hops"] += 1
    return parts


def _legacy_reduce(parts: dict, op: str, rec: dict | None) -> np.ndarray:
    if failpoints.ACTIVE:
        failpoints.fire("collective.reduce")
    import time as _t

    t0 = _t.monotonic()
    out = _REDUCE_OPS[op](np.stack(list(parts.values())))
    if rec is not None:
        rec["reduce_us"] += (_t.monotonic() - t0) * 1e6
    return out


# --------------------------------------------------------- schedule pick
def _pick_schedule(nbytes: int) -> str:
    if not _ring_enabled():
        return "gather"
    return "ring" if nbytes >= _ring_min_bytes() else "tree"


def _traced(g: _GroupState, schedule: str, op: str, tensor,
            seq: int, fn):
    """Run one collective body with phase accounting around it: the
    opt-in one-shot tracer when armed, and — always, unless
    RAY_TPU_TRACE=0 — a flight-recorder span per op carrying the same
    send/pull/reduce/wait phase sums the schedules already stamp into
    the record (the per-collective attribution of "which phase ate
    this train step")."""
    rec = profiling.consume_collective_arm()
    armed = rec is not None
    if not armed and tracing.ENABLED:
        rec = profiling.blank_collective_rec()
    if rec is not None:
        rec.update(schedule=schedule, op=op,
                   bytes=int(getattr(tensor, "nbytes", 0)),
                   world=g.world_size, rank=g.rank, seq=seq)
    t_span0 = time.time()
    err = None
    try:
        return fn(rec)
    except BaseException as e:  # noqa: BLE001 - recorded, re-raised
        err = type(e).__name__
        raise
    finally:
        if armed:
            # publish also bridges the record into the recorder.
            profiling.publish_collective_trace(rec)
        elif rec is not None:
            attrs = {k: rec[k] for k in
                     ("schedule", "op", "bytes", "world", "rank", "seq",
                      "hops", "sent_bytes", "recv_bytes") if k in rec}
            for k in profiling.COLLECTIVE_PHASES:
                if rec.get(k):
                    attrs[k] = round(rec[k], 1)
            if err:
                attrs["error"] = err
            tracing.emit(f"collective.{op}", t_span0, attrs=attrs)


# ------------------------------------------------------------- public API
def allreduce(tensor, group_name: str = "default", op: str = "sum"):
    """ray: collective.py:258.  Returns the reduced array (numpy in,
    numpy out; jax arrays are accepted and returned as numpy)."""
    return allreduce_async(tensor, group_name, op).wait()


def allreduce_async(tensor, group_name: str = "default",
                    op: str = "sum") -> CollectiveWork:
    """Async allreduce: returns a wait()-able CollectiveWork so the
    caller overlaps the DCN sync with other work (train: next step's
    input pipeline).  Per group, ops run in submission order."""
    g = _group(group_name)
    x = np.asarray(tensor)
    schedule = _pick_schedule(x.nbytes)

    def run(seq: int):
        def body(rec):
            if schedule == "ring":
                return _ring.ring_allreduce(g, x, op, seq, rec)
            if schedule == "tree":
                return _ring.tree_allreduce(g, x, op, seq, rec)
            return _legacy_reduce(
                _gather_parts(g, f"allreduce:{op}", x, seq, rec), op,
                rec)
        return _traced(g, schedule, f"allreduce:{op}", x, seq, body)

    return g.submit(run)


def allgather(tensor, group_name: str = "default") -> list:
    return allgather_async(tensor, group_name).wait()


def allgather_async(tensor,
                    group_name: str = "default") -> CollectiveWork:
    """NOTE: the ring path (>= RAY_TPU_COLLECTIVE_RING_MIN_BYTES)
    requires same-shape tensors on every rank (MPI_Allgather contract);
    heterogeneous shapes need the legacy path
    (RAY_TPU_RING_COLLECTIVES=0)."""
    g = _group(group_name)
    x = np.asarray(tensor)
    schedule = _pick_schedule(x.nbytes)
    if schedule == "tree":
        schedule = "gather"      # below the ring threshold the legacy
        # exchange IS the latency-optimal allgather (1 matched exchange)

    def run(seq: int):
        def body(rec):
            if schedule == "ring":
                return _ring.ring_allgather(g, x, seq, rec)
            parts = _gather_parts(g, "allgather", x, seq, rec)
            return [parts[r] for r in sorted(parts)]
        return _traced(g, schedule, "allgather", x, seq, body)

    return g.submit(run)


def reducescatter(tensor, group_name: str = "default", op: str = "sum"):
    """Each rank gets its 1/world slice of the reduction (ray:
    collective.reducescatter)."""
    return reducescatter_async(tensor, group_name, op).wait()


def reducescatter_async(tensor, group_name: str = "default",
                        op: str = "sum") -> CollectiveWork:
    g = _group(group_name)
    x = np.asarray(tensor)
    schedule = _pick_schedule(x.nbytes)

    def run(seq: int):
        def body(rec):
            if schedule == "ring":
                return _ring.ring_reducescatter(g, x, op, seq, rec)
            if schedule == "tree":
                # Latency regime: tree-allreduce then slice — same hop
                # count as a dedicated halving schedule at these sizes,
                # zero extra code paths to verify.
                reduced = _ring.tree_allreduce(g, x, op, seq, rec)
                return np.array_split(reduced, g.world_size,
                                      axis=0)[g.rank]
            parts = _gather_parts(g, f"reducescatter:{op}", x, seq, rec)
            reduced = _legacy_reduce(parts, op, rec)
            chunks = np.array_split(reduced, g.world_size, axis=0)
            return chunks[g.rank]
        return _traced(g, schedule, f"reducescatter:{op}", x, seq, body)

    return g.submit(run)


def broadcast(tensor, src_rank: int = 0, group_name: str = "default"):
    return broadcast_async(tensor, src_rank, group_name).wait()


def broadcast_async(tensor, src_rank: int = 0,
                    group_name: str = "default") -> CollectiveWork:
    g = _group(group_name)
    # Non-src ranks don't know the payload size, so broadcast can't be
    # size-gated consistently: tree whenever ring collectives are on.
    schedule = "tree" if _ring_enabled() else "gather"
    x = np.asarray(tensor) if g.rank == src_rank else None

    def run(seq: int):
        def body(rec):
            if schedule == "tree":
                return _ring.tree_broadcast(g, x, src_rank, seq, rec)
            parts = _gather_parts(
                g, f"broadcast:{src_rank}",
                x if g.rank == src_rank else np.zeros(0), seq, rec)
            return parts[src_rank]
        return _traced(g, schedule, f"broadcast:{src_rank}",
                       x if x is not None else np.zeros(0), seq, body)

    return g.submit(run)


class _MappedWork(CollectiveWork):
    """CollectiveWork whose result is `fn(inner result)` — computed once
    on the first wait (on the WAITER's thread, not the group op thread:
    unpacking must not serialize behind other queued collectives)."""

    _UNSET = object()

    def __init__(self, inner: CollectiveWork, fn):
        self._inner = inner
        self._fn = fn
        self.seq = inner.seq
        self._out = _MappedWork._UNSET

    def wait(self, timeout: float | None = None):
        if self._out is _MappedWork._UNSET:
            self._out = self._fn(self._inner.wait(timeout))
        return self._out

    def done(self) -> bool:
        return self._inner.done()


def broadcast_pytree(tree, src_rank: int = 0,
                     group_name: str = "default"):
    return broadcast_pytree_async(tree, src_rank, group_name).wait()


def broadcast_pytree_async(tree, src_rank: int = 0,
                           group_name: str = "default") -> CollectiveWork:
    """Broadcast a whole pytree of arrays as ONE transport (the online
    RLHF weight-sync path: a llama param tree is hundreds of leaves —
    per-leaf broadcasts would pay the tree/ring hop latency per leaf;
    packing them into a single contiguous byte buffer pays it once and
    lets the ring/tree schedule see one large tensor).

    Contract: every rank passes a tree of the SAME structure and leaf
    shapes/dtypes — non-src ranks' trees serve as the unpack template
    (natural for weight sync, where each receiver already holds the
    previous weights).  Returns the src tree's values unflattened into
    the caller's structure; leaves come back as numpy arrays on non-src
    ranks (src gets its own tree back untouched).  A byte-size mismatch
    (structures drifted) raises a diagnostic instead of mis-slicing."""
    import jax

    g = _group(group_name)      # fail fast on the caller's thread
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if g.rank == src_rank:
        # Device leaves: kick every transfer before materializing any
        # (a synchronous per-leaf fetch serializes one device→host
        # sync per leaf — the very cost packing exists to avoid; same
        # pattern as the serve KV-export path).
        for x in leaves:
            try:
                x.copy_to_host_async()
            except AttributeError:
                pass
    arrs = [np.ascontiguousarray(x) for x in leaves]
    total = sum(a.nbytes for a in arrs)
    if g.rank == src_rank:
        payload = np.empty(total, np.uint8)
        off = 0
        for a in arrs:
            n = a.nbytes
            if n:
                payload[off:off + n] = a.reshape(-1).view(np.uint8)
            off += n
    else:
        payload = None
    work = broadcast_async(payload, src_rank, group_name)

    def unpack(flat):
        if g.rank == src_rank:
            return tree
        flat = np.asarray(flat).reshape(-1).view(np.uint8)
        if flat.nbytes != total:
            raise RuntimeError(
                f"broadcast_pytree: received {flat.nbytes} bytes but "
                f"this rank's template tree holds {total} — src and "
                "receiver param trees have drifted (different model "
                "config / stale template?)")
        out, off = [], 0
        for a in arrs:
            n = a.nbytes
            out.append(flat[off:off + n].view(a.dtype).reshape(a.shape))
            off += n
        return jax.tree_util.tree_unflatten(treedef, out)

    return _MappedWork(work, unpack)


def barrier(group_name: str = "default") -> None:
    g = _group(group_name)

    def run(seq: int):
        _exchange(g, "barrier", np.zeros(0), seq)

    g.submit(run).wait()


def send(tensor, dst_rank: int, group_name: str = "default",
         tag: int = 0) -> None:
    """P2P send (ray: collective.send)."""
    g = _group(group_name)
    with memledger.tag("collective_chunk",
                       label="collective/collective.py send"):
        ref = ray_tpu.put(np.asarray(tensor))
    ray_tpu.get(g.rendezvous.put_p2p.remote(
        (g.rank, dst_rank, tag), [ref]), timeout=g.timeout_s + 30.0)


def recv(src_rank: int, group_name: str = "default", tag: int = 0):
    """P2P recv (ray: collective.recv)."""
    g = _group(group_name)
    wrapped = ray_tpu.get(g.rendezvous.take_p2p.remote(
        (src_rank, g.rank, tag), g.timeout_s),
        timeout=g.timeout_s + 30.0)
    return ray_tpu.get(wrapped[0])
