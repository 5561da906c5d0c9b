"""Distributed reference-counting: borrow pins and releases.

Analog of ray: python/ray/tests/test_reference_counting*.py — objects
shipped as task args are pinned for the task's duration; refs a worker
keeps (borrows) hold the object alive until the borrower drops them
(ray: reference_count.cc borrower protocol).
"""
import gc
import time

import numpy as np

import ray_tpu


def _wait(cond, timeout=10.0, msg=""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"condition never held: {msg}")


def test_borrow_released_after_task(ray_shared):
    from ray_tpu._private.worker import global_worker

    core = global_worker()

    @ray_tpu.remote
    def consume(wrapped):
        # wrapped[0] stays an un-resolved ref (nested in a container)
        return 1

    ref = ray_tpu.put(np.zeros(1024))
    oid = ref.binary()
    for _ in range(3):
        assert ray_tpu.get(consume.remote([ref])) == 1
    # All submission pins must drain once replies are in.
    _wait(lambda: core.owned[oid].borrowers == 0,
          msg=f"borrowers={core.owned[oid].borrowers}")
    assert core.owned[oid].local_refs >= 1
    del ref
    gc.collect()
    _wait(lambda: oid not in core.owned, msg="object not freed after del")


def test_fire_and_forget_return_not_leaked(ray_shared):
    """A return ref dropped before the reply arrives must not resurrect
    the owned record, and the executor's contained pins must release
    (regression: _on_task_reply used setdefault and pinned forever)."""
    from ray_tpu._private.worker import global_worker

    core = global_worker()

    inner = ray_tpu.put(np.ones(512))
    inner_oid = inner.binary()

    @ray_tpu.remote
    def wrap(x):
        time.sleep(0.3)
        return [x]     # return value CONTAINS the ref → contained pin

    ret = wrap.remote(inner)
    ret_oid = ret.binary()
    del ret            # dropped before the task replies
    gc.collect()
    # Reply lands → record must not come back, pins must drain.
    _wait(lambda: ret_oid not in core.owned,
          msg="fire-and-forget return record resurrected")
    _wait(lambda: core.owned[inner_oid].borrowers == 0,
          msg="contained pin never released")
    del inner
    gc.collect()
    _wait(lambda: inner_oid not in core.owned, msg="inner not freed")


def test_executing_worker_cache_does_not_pin(ray_shared):
    """After a task completes, the executing worker's cached copies of
    its arg values must not keep pinning refs nested inside them
    (regression: borrower memory cache held nested ObjectRef instances
    forever, so remove_borrow never fired)."""
    from ray_tpu._private.worker import global_worker

    core = global_worker()

    inner = ray_tpu.put(np.ones(300_000))              # stored object
    container = ray_tpu.put([inner, np.zeros(300_000)])  # nests the ref
    inner_oid = inner.binary()
    container_oid = container.binary()

    @ray_tpu.remote
    def use(c):
        import ray_tpu as rt_mod
        return float(rt_mod.get(c[0]).sum())

    assert ray_tpu.get(use.remote(container)) == 300_000.0
    # The worker's borrow of `inner` (registered when it deserialized the
    # container) must drain once its caches are evicted post-task; what
    # remains is exactly the container record's own contained pin.
    _wait(lambda: core.owned[inner_oid].borrowers == 1,
          msg=f"inner borrowers={core.owned[inner_oid].borrowers}",
          timeout=15.0)
    _wait(lambda: core.owned[container_oid].borrowers == 0,
          msg="container borrow never released", timeout=15.0)
    del container, inner
    gc.collect()
    _wait(lambda: inner_oid not in core.owned, msg="inner leaked")
    _wait(lambda: container_oid not in core.owned, msg="container leaked")


def test_borrow_held_by_actor_pins_object(ray_shared):
    from ray_tpu._private.worker import global_worker

    core = global_worker()

    @ray_tpu.remote
    class Holder:
        def hold(self, wrapped):
            self.kept = wrapped
            return 1

        def peek(self):
            return ray_tpu.get(self.kept[0])[0]

        def drop(self):
            self.kept = None
            gc.collect()
            return 1

    h = Holder.remote()
    ref = ray_tpu.put(np.full(2048, 7.0))
    oid = ref.binary()
    assert ray_tpu.get(h.hold.remote([ref])) == 1
    del ref
    gc.collect()
    time.sleep(0.5)
    # The actor's borrow keeps the object alive after the owner dropped it.
    assert oid in core.owned, "borrowed object freed while actor holds it"
    assert ray_tpu.get(h.peek.remote()) == 7.0
    assert ray_tpu.get(h.drop.remote()) == 1
    _wait(lambda: oid not in core.owned,
          msg="object not freed after borrower dropped it", timeout=15.0)
