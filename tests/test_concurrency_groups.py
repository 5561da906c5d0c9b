"""Actor concurrency groups: named per-group concurrency limits
(reference: ray concurrency groups,
src/ray/core_worker/transport/concurrency_group_manager.cc; python API
@ray.remote(concurrency_groups=...) + @ray.method(concurrency_group=...)).
"""
import time

import pytest

import ray_tpu


@pytest.fixture
def cluster(ray_shared):
    @ray_tpu.remote
    def warm():
        return 1

    ray_tpu.get([warm.remote() for _ in range(3)])


def test_group_isolated_from_saturated_default(cluster):
    """Group A (default) saturated; group B ("io") still serves — the
    VERDICT acceptance scenario."""
    @ray_tpu.remote(concurrency_groups={"io": 2})
    class Worker:
        def slow(self):
            time.sleep(1.0)
            return "slow"

        @ray_tpu.method(concurrency_group="io")
        def ping(self):
            return "pong"

    w = Worker.remote()
    assert ray_tpu.get(w.ping.remote()) == "pong"   # warm the actor
    slow_refs = [w.slow.remote() for _ in range(3)]  # default cap 1 → 3s
    time.sleep(0.2)                                  # let slow() occupy
    t0 = time.perf_counter()
    assert ray_tpu.get(w.ping.remote()) == "pong"
    io_latency = time.perf_counter() - t0
    assert io_latency < 0.9, (
        f"io group gated behind default group: {io_latency:.2f}s")
    assert ray_tpu.get(slow_refs) == ["slow"] * 3


def test_group_capacity_limits_parallelism(cluster):
    """A group's limit bounds ITS concurrency: 4 calls into a cap-2
    group take ~2 waves."""
    @ray_tpu.remote(concurrency_groups={"pool": 2})
    class Worker:
        @ray_tpu.method(concurrency_group="pool")
        def work(self):
            time.sleep(0.5)
            return 1

    w = Worker.remote()
    ray_tpu.get(w.work.remote())
    t0 = time.perf_counter()
    assert sum(ray_tpu.get([w.work.remote() for _ in range(4)])) == 4
    wall = time.perf_counter() - t0
    assert 0.85 < wall < 2.5, f"cap-2 group took {wall:.2f}s for 4x0.5s"


def test_per_call_group_override(cluster):
    """options(concurrency_group=...) routes a single call."""
    @ray_tpu.remote(concurrency_groups={"fast": 2})
    class Worker:
        def blocked(self):
            time.sleep(1.0)
            return "b"

        def quick(self):
            return "q"

    w = Worker.remote()
    ray_tpu.get(w.quick.remote())
    block_ref = w.blocked.remote()          # occupies default group
    time.sleep(0.2)
    t0 = time.perf_counter()
    out = ray_tpu.get(
        w.quick.options(concurrency_group="fast").remote())
    assert out == "q"
    assert time.perf_counter() - t0 < 0.7
    assert ray_tpu.get(block_ref) == "b"


def test_async_actor_concurrency_groups(cluster):
    """Async actors: per-group semaphores bound coroutine concurrency."""
    @ray_tpu.remote(concurrency_groups={"io": 8})
    class AsyncWorker:
        async def slow(self):
            import asyncio

            await asyncio.sleep(0.8)
            return "s"

        @ray_tpu.method(concurrency_group="io")
        async def ping(self):
            return "pong"

    a = AsyncWorker.options(max_concurrency=1).remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    slow_ref = a.slow.remote()              # occupies default (cap 1)
    time.sleep(0.2)
    t0 = time.perf_counter()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    assert time.perf_counter() - t0 < 0.6
    assert ray_tpu.get(slow_ref) == "s"


def test_method_num_returns_declaration(cluster):
    """@ray_tpu.method(num_returns=N) flows through the handle."""
    @ray_tpu.remote
    class A:
        @ray_tpu.method(num_returns=2)
        def pair(self):
            return 1, 2

    a = A.remote()
    r1, r2 = a.pair.remote()
    assert ray_tpu.get([r1, r2]) == [1, 2]
