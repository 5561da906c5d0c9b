"""Actor API tests (analog of ray: python/ray/tests/test_actor.py)."""
import gc
import time

import pytest


def test_counter_ordering(ray_shared):
    ray_tpu = ray_shared

    @ray_tpu.remote
    class Counter:
        def __init__(self, start=0):
            self.v = start

        def inc(self, d=1):
            self.v += d
            return self.v

    c = Counter.remote(100)
    refs = [c.inc.remote() for _ in range(20)]
    assert ray_tpu.get(refs) == list(range(101, 121))


def test_actor_state_isolated(ray_shared):
    ray_tpu = ray_shared

    @ray_tpu.remote
    class Holder:
        def __init__(self):
            self.items = []

        def add(self, x):
            self.items.append(x)
            return len(self.items)

    a, b = Holder.remote(), Holder.remote()
    ray_tpu.get([a.add.remote(1), a.add.remote(2)])
    assert ray_tpu.get(b.add.remote(9)) == 1


def test_named_actor(ray_shared):
    ray_tpu = ray_shared

    @ray_tpu.remote
    class Svc:
        def ping(self):
            return "pong"

    creator = Svc.options(name="svc-test").remote()
    h = ray_tpu.get_actor("svc-test")
    assert ray_tpu.get(h.ping.remote()) == "pong"
    # Named actors survive the creating handle going out of scope: this
    # runtime has no distributed handle counting, so killing on the
    # creator's drop would break other processes' get_actor handles
    # (ray instead counts every handle; divergence documented in
    # actor.py).  They live until ray_tpu.kill / shutdown.
    del creator
    gc.collect()
    time.sleep(0.3)
    assert ray_tpu.get(h.ping.remote()) == "pong"
    ray_tpu.kill(h)
    d = Svc.options(name="svc-detached", lifetime="detached").remote()
    del d
    h2 = ray_tpu.get_actor("svc-detached")
    assert ray_tpu.get(h2.ping.remote()) == "pong"
    ray_tpu.kill(h2)


def test_get_actor_missing(ray_shared):
    ray_tpu = ray_shared
    with pytest.raises(ValueError):
        ray_tpu.get_actor("does-not-exist-xyz")


def test_async_actor_concurrency(ray_shared):
    ray_tpu = ray_shared

    @ray_tpu.remote
    class AsyncActor:
        async def work(self, i):
            import asyncio
            await asyncio.sleep(0.2)
            return i

    a = AsyncActor.remote()
    ray_tpu.get(a.work.remote(-1))       # warm: actor created, addr cached
    t0 = time.monotonic()
    out = ray_tpu.get([a.work.remote(i) for i in range(5)])
    elapsed = time.monotonic() - t0
    assert out == list(range(5))
    # Concurrent: five 0.2s sleeps must overlap, not serialize to 1s.
    assert elapsed < 0.9, elapsed


def test_actor_error(ray_shared):
    ray_tpu = ray_shared

    @ray_tpu.remote
    class Bad:
        def fail(self):
            raise KeyError("nope")

        def ok(self):
            return 1

    b = Bad.remote()
    with pytest.raises(ray_tpu.TaskError):
        ray_tpu.get(b.fail.remote())
    # Actor survives its own exceptions.
    assert ray_tpu.get(b.ok.remote()) == 1


def test_handle_passing(ray_shared):
    ray_tpu = ray_shared

    @ray_tpu.remote
    class Store:
        def __init__(self):
            self.v = 0

        def set(self, v):
            self.v = v

        def get(self):
            return self.v

    @ray_tpu.remote
    def writer(handle, v):
        import ray_tpu as rt
        rt.get(handle.set.remote(v))
        return True

    s = Store.remote()
    assert ray_tpu.get(writer.remote(s, 42))
    assert ray_tpu.get(s.get.remote()) == 42


def test_kill_actor(ray_shared):
    ray_tpu = ray_shared

    @ray_tpu.remote
    class Victim:
        def ping(self):
            return 1

    v = Victim.remote()
    assert ray_tpu.get(v.ping.remote()) == 1
    ray_tpu.kill(v)
    with pytest.raises(ray_tpu.ActorError):
        ray_tpu.get(v.ping.remote(), timeout=10)


def test_actor_num_returns(ray_shared):
    ray_tpu = ray_shared

    @ray_tpu.remote
    class Multi:
        def pair(self):
            return "a", "b"

    m = Multi.remote()
    r1, r2 = m.pair.options(num_returns=2).remote()
    assert ray_tpu.get([r1, r2]) == ["a", "b"]


def test_threaded_actor_max_concurrency(ray_shared):
    ray_tpu = ray_shared

    @ray_tpu.remote(max_concurrency=4)
    class Slow:
        def work(self):
            time.sleep(0.3)
            return 1

    s = Slow.remote()
    ray_tpu.get(s.work.remote())         # warm
    t0 = time.monotonic()
    assert sum(ray_tpu.get([s.work.remote() for _ in range(4)])) == 4
    assert time.monotonic() - t0 < 1.1


def test_retransmitted_call_does_not_reexecute(ray_shared):
    """Transport retries must not double-apply stateful methods: a
    resend of an already-executed seqno is answered from the receiver's
    reply cache (exactly-once observable effects; ray: sequence-number
    dedup in the actor scheduling queue).  Regression: a retried batch
    whose originals were mid-flight re-ran four incr() calls and shifted
    every later result."""
    import ray_tpu
    from ray_tpu._private.ids import TaskID
    from ray_tpu._private.worker import _empty_args_frames, global_worker

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.v = 0

        def inc(self):
            self.v += 1
            return self.v

    c = Counter.remote()
    assert ray_tpu.get([c.inc.remote() for _ in range(3)]) == [1, 2, 3]

    core = global_worker()
    st = core._actor_state(c._actor_id)
    assert st.address, "actor address should be resolved after calls"

    # Hand-craft a retransmit of seqno 0 (what _send_actor_batch does
    # after a connection flap: same caller, same seqno, fresh task id).
    header = {"task_id": TaskID.from_random().hex(),
              "function_id": "", "num_returns": 1, "resources": {},
              "owner_addr": core.address, "arg_refs": [],
              "bundle_key": None, "name": "",
              "actor_id": c._actor_id, "method": "inc",
              "caller": core.worker_id, "seqno": 0}
    reply, _ = core.call(st.address, "actor_call", header,
                         _empty_args_frames(), timeout=30.0)
    assert reply.get("status") != "error", reply

    # The counter must NOT have advanced: next real call returns 4.
    assert ray_tpu.get(c.inc.remote()) == 4


def _held_back(ray_tpu, nap):
    """(seconds until 12 short calls queued behind a 2 s call return,
    seconds for 40 calls of 0.5 s sent 5 ms apart)."""
    from ray_tpu._private.worker import global_worker

    # the IO loop held for a moment, so that the calls below are queued
    # together when the outbox is drained (the first rides the fused
    # path alone)
    global_worker()._post_to_loop(lambda: time.sleep(0.3))
    t0 = time.perf_counter()
    first, slow = nap.remote(0.01), nap.remote(2.0)
    fast = [nap.remote(0.01) for _ in range(12)]
    assert ray_tpu.get([first] + fast, timeout=30) == [0.01] * 13
    behind = time.perf_counter() - t0
    assert ray_tpu.get(slow, timeout=30) == 2.0
    t0 = time.perf_counter()
    refs = []
    for _ in range(40):
        refs.append(nap.remote(0.5))
        time.sleep(0.005)
    assert ray_tpu.get(refs, timeout=30) == [0.5] * 40
    return behind, time.perf_counter() - t0


def test_unbatched_calls_reply_on_their_own(ray_shared):
    """Queued calls to one actor share an RPC whose ONE reply waits for
    the slowest of them, and sixteen such RPCs may be in flight.  A call
    sent `.options(unbatched=True)` (a serve request to a replica) is
    held by neither: short calls queued with a long one return at once,
    and forty long ones overlap."""
    ray_tpu = ray_shared

    @ray_tpu.remote(max_concurrency=64)
    class Sleeper:
        async def nap(self, s):
            import asyncio

            await asyncio.sleep(s)
            return s

    a = Sleeper.remote()
    ray_tpu.get(a.nap.remote(0.0))
    # the same calls without the option read 2.3 s and 1.0 s here
    behind, forty = _held_back(ray_tpu, a.nap.options(unbatched=True))
    assert behind < 1.2 and forty < 0.9
