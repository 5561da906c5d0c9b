"""Ring/tree DCN collective tests (ISSUE 5).

Covers: ring-vs-legacy numeric parity across dtypes and ops, the new
reducescatter/allgather/broadcast paths, async-collective ordering under
concurrent groups, the per-collective phase tracer's byte accounting
(the 2*N*(world-1)/world schedule proof), the per-exchange timeout
diagnostics (missing ranks named, not a hang), destroy_collective_group
cleanup from a registry-less driver, and a 3-node end-to-end allreduce
at 64 MiB over the in-process Cluster (real per-node arenas + the
same-host direct-shm pull path + replica GC).
"""
import json
import time

import numpy as np

import ray_tpu

pytestmark = []


def _set_path_env(path: str):
    """Schedule-forcing env for the three backends (read at call time
    by the collective module)."""
    import os

    if path == "gather":
        os.environ["RAY_TPU_RING_COLLECTIVES"] = "0"
    else:
        os.environ["RAY_TPU_RING_COLLECTIVES"] = "1"
        os.environ["RAY_TPU_COLLECTIVE_RING_MIN_BYTES"] = (
            str(1 << 30) if path == "tree" else "16")


@ray_tpu.remote
class Member:
    def init_collective_group(self, world_size, rank, backend, group_name):
        from ray_tpu import collective as col

        col.init_collective_group(world_size, rank, backend, group_name,
                                  timeout_s=60.0)
        self.rank = rank
        return rank

    def allreduce(self, group, arr, op, path):
        from ray_tpu import collective as col

        _set_path_env(path)
        return col.allreduce(arr, group_name=group, op=op)

    def traced_allreduce(self, group, arr, path):
        from ray_tpu import collective as col
        from ray_tpu import profiling

        _set_path_env(path)
        with profiling.collective_trace() as rec:
            out = col.allreduce(arr, group_name=group)
        return out, profiling.collective_breakdown_us(rec)

    def reducescatter(self, group, arr, op, path):
        from ray_tpu import collective as col

        _set_path_env(path)
        return col.reducescatter(arr, group_name=group, op=op)

    def allgather(self, group, arr, path):
        from ray_tpu import collective as col

        _set_path_env(path)
        return col.allgather(arr, group_name=group)

    def broadcast(self, group, arr, src, path):
        from ray_tpu import collective as col

        _set_path_env(path)
        return col.broadcast(arr, src_rank=src, group_name=group)

    def async_burst(self, groups, n_ops, path):
        """Interleave async allreduces across several groups; returns
        per-group result list (ordering proof: op i carries value i)."""
        from ray_tpu import collective as col

        _set_path_env(path)
        works = {g: [] for g in groups}
        for i in range(n_ops):
            for g in groups:
                works[g].append(col.allreduce_async(
                    np.full(256, float(i + 1) * (self.rank + 1),
                            np.float32), group_name=g))
        return {g: [float(w.wait(60)[0]) for w in ws]
                for g, ws in works.items()}

    def init_short_group(self, world_size, rank, group_name,
                         timeout_s):
        from ray_tpu import collective as col

        col.init_collective_group(world_size, rank, "object_store",
                                  group_name, timeout_s=timeout_s)
        return True

    def barrier_alone(self, group):
        from ray_tpu import collective as col

        try:
            col.barrier(group)
            return None
        except Exception as e:  # noqa: BLE001
            return repr(e)

    def allreduce_alone(self, group, path):
        from ray_tpu import collective as col

        _set_path_env(path)
        try:
            col.allreduce(np.ones(1 << 14, np.float32), group_name=group)
            return None
        except Exception as e:  # noqa: BLE001
            return repr(e)


def _group(ray_shared, n, name):
    from ray_tpu import collective as col

    ws = [Member.options(num_cpus=0.5).remote() for _ in range(n)]
    col.create_collective_group(ws, n, list(range(n)), group_name=name)
    return ws


def _cleanup(ws, *names):
    from ray_tpu import collective as col

    for w in ws:
        ray_tpu.kill(w)
    for name in names:
        col.destroy_collective_group(name)


DTYPES = [np.float32, np.int32]
try:
    import ml_dtypes

    DTYPES.append(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover - jax always ships ml_dtypes
    pass


def test_ring_parity_dtypes_ops(ray_shared):
    """Ring / tree / legacy produce identical results for every dtype
    and op (integer-valued data: exact under any reduction order)."""
    ws = _group(ray_shared, 3, "par")
    try:
        for dtype in DTYPES:
            for op in ("sum", "min", "max"):
                arrs = [(np.arange(777) % 5 + r + 1).astype(dtype)
                        for r in range(3)]
                ref = None
                for path in ("gather", "ring", "tree"):
                    outs = ray_tpu.get(
                        [w.allreduce.remote("par", arrs[r], op, path)
                         for r, w in enumerate(ws)], timeout=120)
                    for o in outs:
                        if path != "gather":
                            # ring/tree preserve the input dtype (MPI
                            # semantics); the legacy np.sum path
                            # promotes small ints to int64 — a
                            # pre-existing numpy artifact.
                            assert o.dtype == np.dtype(dtype), path
                        if ref is None:
                            ref = o
                        np.testing.assert_array_equal(
                            np.asarray(o, np.float64),
                            np.asarray(ref, np.float64),
                            err_msg=f"{dtype} {op} {path}")
                    ref = outs[0]
    finally:
        _cleanup(ws, "par")


def test_ring_reducescatter_allgather_broadcast(ray_shared):
    ws = _group(ray_shared, 3, "rsagbc")
    try:
        x = np.arange(10, dtype=np.float64)
        full = 3 * x
        exp_chunks = np.array_split(full, 3)
        for path in ("gather", "ring", "tree"):
            rs = ray_tpu.get(
                [w.reducescatter.remote("rsagbc", x, "sum", path)
                 for w in ws], timeout=120)
            for r in range(3):
                np.testing.assert_array_equal(rs[r], exp_chunks[r],
                                              err_msg=path)
        for path in ("gather", "ring"):
            ag = ray_tpu.get(
                [w.allgather.remote("rsagbc", np.full(300, float(r)),
                                    path)
                 for r, w in enumerate(ws)], timeout=120)
            for per in ag:
                assert [int(p[0]) for p in per] == [0, 1, 2]
        for path in ("gather", "ring"):
            for src in (0, 2):
                bc = ray_tpu.get(
                    [w.broadcast.remote(
                        "rsagbc",
                        np.array([99.0]) if r == src else np.zeros(1),
                        src, path)
                     for r, w in enumerate(ws)], timeout=120)
                assert all(float(b[0]) == 99.0 for b in bc), (path, src)
    finally:
        _cleanup(ws, "rsagbc")


def test_async_ordering_concurrent_groups(ray_shared):
    """Async ops execute in submission (seq) order per group, and two
    groups sharing the same actors don't cross-talk."""
    from ray_tpu import collective as col

    ws = [Member.options(num_cpus=0.5).remote() for _ in range(2)]
    col.create_collective_group(ws, 2, [0, 1], group_name="ga")
    col.create_collective_group(ws, 2, [0, 1], group_name="gb")
    try:
        res = ray_tpu.get(
            [w.async_burst.remote(["ga", "gb"], 5, "ring") for w in ws],
            timeout=120)
        # op i allreduces full(256, (i+1)*(rank+1)) -> sum = (i+1)*3
        expect = [float((i + 1) * 3) for i in range(5)]
        for per_rank in res:
            assert per_rank["ga"] == expect
            assert per_rank["gb"] == expect
    finally:
        _cleanup(ws, "ga", "gb")


def test_tracer_byte_schedule(ray_shared):
    """The phase tracer's byte counters prove the schedule shape: ring
    moves 2*N*(world-1)/world bytes per rank; the legacy gather pulls
    O(world*N)."""
    ws = _group(ray_shared, 3, "tr")
    try:
        x = np.ones(1 << 20, np.float32)          # 4 MiB
        n = x.nbytes
        outs = ray_tpu.get(
            [w.traced_allreduce.remote("tr", x, "ring") for w in ws],
            timeout=120)
        for out, br in outs:
            assert out[0] == 3.0
            assert br["schedule"] == "ring"
            expect = 2 * n * 2 // 3
            assert abs(br["sent_bytes"] - expect) <= n // 100, br
            assert abs(br["recv_bytes"] - expect) <= n // 100, br
            assert br["hops"] == 4                 # 2 RS + 2 AG swaps
        outs = ray_tpu.get(
            [w.traced_allreduce.remote("tr", x, "gather") for w in ws],
            timeout=120)
        for out, br in outs:
            assert br["schedule"] == "gather"
            assert br["sent_bytes"] == n
            assert br["recv_bytes"] == 2 * n       # (world-1)*N pulled
    finally:
        _cleanup(ws, "tr")


def test_exchange_timeout_names_missing_ranks(ray_shared):
    """A rank whose peers never arrive gets a diagnostic error naming
    the missing ranks — never a hang (satellite fix).  Only rank 0 ever
    joins, with a 5s deadline; the barrier (legacy exchange) and the
    ring path both surface diagnostics."""
    ws = [Member.options(num_cpus=0.5).remote() for _ in range(1)]
    assert ray_tpu.get(
        ws[0].init_short_group.remote(2, 0, "lone", 5.0), timeout=60)
    err = ray_tpu.get(ws[0].barrier_alone.remote("lone"), timeout=90)
    assert err is not None, "lone barrier should not succeed"
    assert "missing ranks [1]" in err, err
    err = ray_tpu.get(ws[0].allreduce_alone.remote("lone", "ring"),
                      timeout=120)
    assert err is not None
    assert "timed out" in err, err
    _cleanup(ws, "lone")


def test_destroy_cleans_up_from_driver(ray_shared):
    """destroy_collective_group works from a process whose registry
    never saw the group (the driver that used create_collective_group):
    the detached rendezvous actor is drained and killed, not leaked."""
    from ray_tpu import collective as col

    ws = _group(ray_shared, 2, "dstr")
    ray_tpu.get([w.allreduce.remote("dstr", np.ones(4), "sum", "ring")
                 for w in ws], timeout=120)
    col.destroy_collective_group("dstr")
    deadline = time.monotonic() + 30
    while True:
        try:
            ray_tpu.get_actor("collective_rdv:dstr")
        except Exception:
            break       # gone — the detached actor no longer leaks
        assert time.monotonic() < deadline, \
            "rendezvous actor still registered after destroy"
        time.sleep(0.5)
    for w in ws:
        ray_tpu.kill(w)


def test_three_node_cluster_64mib_allreduce():
    """End-to-end over real per-node arenas: 3 ranks on 3 in-process
    cluster nodes, 64 MiB ring allreduce (same-host direct-shm pulls
    underneath), ring-vs-legacy parity, and full replica GC afterwards
    (the round-10 add_location fix: cross-node replicas are scrubbed
    when the owner frees)."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu import collective as col

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    cluster = Cluster(config_json=json.dumps(
        {"object_store_memory": 768 * 1024 * 1024}))
    cluster.start_head()
    for i in range(3):
        cluster.add_node(resources={"CPU": 2, f"rk{i}": 1})
    try:
        ray_tpu.init(address=cluster.address)
        cluster.wait_for_nodes(3)

        class Rank:
            def init_collective_group(self, world, rank, backend, name):
                from ray_tpu import collective as c2

                c2.init_collective_group(world, rank, backend, name,
                                         timeout_s=120.0)
                self.rank = rank
                return rank

            def run(self, group, ring):
                import os

                from ray_tpu import collective as c2

                os.environ["RAY_TPU_RING_COLLECTIVES"] = \
                    "1" if ring else "0"
                x = np.full(16 << 20, float(self.rank + 1), np.float32)
                out = c2.allreduce(x, group_name=group)
                return float(out[0]), float(out[-1]), out.shape

            def arena(self):
                from ray_tpu._private.worker import global_worker

                core = global_worker()
                reply, _ = core.call(core.agent_addr, "store_stats",
                                     {"sweep": True}, timeout=30.0)
                return (reply.get("used"), reply.get("num_objects"),
                        reply.get("swept_dead_pins", 0))

        mk = ray_tpu.remote(Rank)
        ws = [mk.options(num_cpus=0.5,
                         resources={f"rk{i}": 0.5}).remote()
              for i in range(3)]
        col.create_collective_group(ws, 3, [0, 1, 2], group_name="big")
        for ring in (True, False):
            outs = ray_tpu.get([w.run.remote("big", ring) for w in ws],
                               timeout=400)
            for first, last, shape in outs:
                assert first == 6.0 and last == 6.0
                assert shape == (16 << 20,)
        col.destroy_collective_group("big")
        # Replica GC: every node's arena converges to empty (sent
        # chunks freed by refcount, replicas scrubbed via the owner's
        # location directory), with zero dead-process pins.
        deadline = time.monotonic() + 60
        while True:
            stats = ray_tpu.get([w.arena.remote() for w in ws],
                                timeout=60)
            if all(num == 0 for _, num, _ in stats):
                break
            if time.monotonic() > deadline:
                raise AssertionError(f"arena did not drain: {stats}")
            time.sleep(1.0)
        assert all(pins == 0 for _, _, pins in stats), stats
    finally:
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
