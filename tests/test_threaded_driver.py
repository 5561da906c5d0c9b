"""Multithreaded-driver stress: the public API hammered concurrently
from many threads of ONE driver process.

The reference supports multithreaded drivers as a first-class pattern
(ray: python/ray/tests/test_multithreading.py); here the adversarial
surface is the sync fast path's lazily-attached t_event CAS
(worker.py _get_objects_fast), the IO-thread handoff, and per-handle
actor ordering under thread interleaving."""
import concurrent.futures
import threading

import numpy as np
import pytest

import ray_tpu


@pytest.fixture
def cluster(ray_shared):
    @ray_tpu.remote
    def warm():
        return 1

    ray_tpu.get([warm.remote() for _ in range(4)], timeout=120)


def test_concurrent_submit_get(cluster):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    def worker(tid):
        out = []
        for i in range(40):
            out.append(ray_tpu.get(add.remote(tid * 1000, i),
                                   timeout=120))
        return out

    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        results = list(pool.map(worker, range(8)))
    for tid, out in enumerate(results):
        assert out == [tid * 1000 + i for i in range(40)]


def test_concurrent_get_same_pending_ref(cluster):
    """8 threads block on the SAME unresolved ref: they must share one
    wake event (the t_event CAS) and all observe the fill."""
    @ray_tpu.remote
    def slow():
        import time
        time.sleep(1.0)
        return 42

    for _ in range(3):      # repeat: the race window is per-entry
        ref = slow.remote()
        barrier = threading.Barrier(8)

        def getter():
            barrier.wait(timeout=30)
            return ray_tpu.get(ref, timeout=120)

        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futs = [pool.submit(getter) for _ in range(8)]
            assert [f.result(timeout=120) for f in futs] == [42] * 8
        del ref


def test_concurrent_actor_calls_from_threads(cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.v = 0

        def inc(self):
            self.v += 1
            return self.v

        def value(self):
            return self.v

    c = Counter.remote()

    def caller(_):
        return [ray_tpu.get(c.inc.remote(), timeout=120)
                for _ in range(25)]

    with concurrent.futures.ThreadPoolExecutor(6) as pool:
        all_vals = sum(pool.map(caller, range(6)), [])
    # every increment applied exactly once, no duplicates or losses
    assert sorted(all_vals) == list(range(1, 151))
    assert ray_tpu.get(c.value.remote(), timeout=60) == 150
    ray_tpu.kill(c)


def test_concurrent_put_get_mixed_sizes(cluster):
    def worker(tid):
        rng = np.random.default_rng(tid)
        small = rng.integers(0, 255, 512, dtype=np.uint8)
        big = rng.integers(0, 255, 300_000, dtype=np.uint8)  # arena path
        refs = [ray_tpu.put(small), ray_tpu.put(big)]
        got_small = ray_tpu.get(refs[0], timeout=120)
        got_big = ray_tpu.get(refs[1], timeout=120)
        assert np.array_equal(got_small, small)
        assert np.array_equal(got_big, big)
        return True

    with concurrent.futures.ThreadPoolExecutor(6) as pool:
        assert all(pool.map(worker, range(6)))


def test_concurrent_wait_overlapping_sets(cluster):
    @ray_tpu.remote
    def quick(i):
        return i

    refs = [quick.remote(i) for i in range(60)]

    def waiter(offset):
        remaining = refs[offset:offset + 40]
        done_total = 0
        while remaining:
            done, remaining = ray_tpu.wait(
                remaining, num_returns=min(10, len(remaining)),
                timeout=120)
            if not done:
                pytest.fail(f"wait() made no progress with "
                            f"{len(remaining)} refs outstanding")
            done_total += len(done)
        return done_total

    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        counts = list(pool.map(waiter, [0, 10, 20, 5]))
    assert counts == [40, 40, 40, 40]
    assert ray_tpu.get(refs, timeout=120) == list(range(60))
