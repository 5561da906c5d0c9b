"""Compiled-DAG execution over mutable shm channels.

Mirrors ray: python/ray/dag/tests/experimental/test_accelerated_dag.py —
compiled graphs execute repeatedly over pre-allocated channels with ZERO
per-call task submissions (compiled_dag_node.py:479 + do_exec_tasks).
"""
import time

import pytest

import ray_tpu
from ray_tpu.dag import InputNode, MultiOutputNode


@ray_tpu.remote
class Adder:
    def __init__(self, inc):
        self.inc = inc

    def add(self, x):
        if isinstance(x, str):
            raise ValueError(f"bad input {x!r}")
        return x + self.inc

    def add2(self, x, y):
        return x + y

    def ping(self):
        return "pong"


def _owned_count():
    from ray_tpu._private.worker import global_worker

    return len(global_worker().owned)


def test_compiled_chain_zero_submissions(ray_shared):
    a, b, c = Adder.remote(1), Adder.remote(10), Adder.remote(100)
    with InputNode() as inp:
        dag = c.add.bind(b.add.bind(a.add.bind(inp)))
    compiled = dag.experimental_compile()
    try:
        assert compiled._channel_mode, "channel compilation must engage"
        # Warm-up execution (claims reader slots end-to-end).
        assert compiled.execute(0).get() == 111
        before = _owned_count()
        for i in range(50):
            ref = compiled.execute(i)
            assert ref.get() == i + 111
        # The accelerated-DAG property: repeated execution creates no
        # tasks and therefore no owned return objects.
        assert _owned_count() == before
    finally:
        compiled.teardown()
    for h in (a, b, c):
        ray_tpu.kill(h)


def test_compiled_latency_vs_remote_chain(ray_shared):
    a, b, c = Adder.remote(1), Adder.remote(10), Adder.remote(100)
    # Warm the actors through the normal path first.
    assert ray_tpu.get(c.add.remote(ray_tpu.get(
        b.add.remote(ray_tpu.get(a.add.remote(0)))))) == 111

    n = 30
    lat_remote = []
    for i in range(n):
        t0 = time.perf_counter()
        r = ray_tpu.get(c.add.remote(ray_tpu.get(
            b.add.remote(ray_tpu.get(a.add.remote(i))))))
        lat_remote.append(time.perf_counter() - t0)
        assert r == i + 111

    with InputNode() as inp:
        dag = c.add.bind(b.add.bind(a.add.bind(inp)))
    compiled = dag.experimental_compile()
    try:
        compiled.execute(0).get()   # warm-up: claim slots, start loops
        lat_dag = []
        for i in range(n):
            t0 = time.perf_counter()
            assert compiled.execute(i).get() == i + 111
            lat_dag.append(time.perf_counter() - t0)
    finally:
        compiled.teardown()
    med = sorted(lat_dag)[n // 2]
    med_remote = sorted(lat_remote)[n // 2]
    # VERDICT bar: >=10x lower per-iteration latency than the .remote
    # chain (median vs median to shrug off suite-load outliers).
    assert med * 10 <= med_remote, (med, med_remote)
    for h in (a, b, c):
        ray_tpu.kill(h)


def test_compiled_error_propagation_and_recovery(ray_shared):
    a, b = Adder.remote(1), Adder.remote(10)
    with InputNode() as inp:
        dag = b.add.bind(a.add.bind(inp))
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(5).get() == 16
        with pytest.raises(ValueError, match="bad input"):
            compiled.execute("boom").get()
        # The pipeline stays live after a user exception.
        assert compiled.execute(7).get() == 18
    finally:
        compiled.teardown()
    ray_tpu.kill(a)
    ray_tpu.kill(b)


def test_compiled_multi_output_and_input_attrs(ray_shared):
    a, b = Adder.remote(1), Adder.remote(10)
    with InputNode() as inp:
        dag = MultiOutputNode([a.add.bind(inp["x"]),
                               b.add2.bind(inp["x"], inp["y"])])
    compiled = dag.experimental_compile()
    try:
        assert compiled._channel_mode
        assert compiled.execute(x=3, y=4).get() == [4, 7]
        assert compiled.execute(x=0, y=9).get() == [1, 9]
    finally:
        compiled.teardown()
    ray_tpu.kill(a)
    ray_tpu.kill(b)


def test_teardown_releases_actor_and_channels(ray_shared):
    import glob

    a = Adder.remote(1)
    with InputNode() as inp:
        dag = a.add.bind(inp)
    compiled = dag.experimental_compile()
    assert compiled.execute(1).get() == 2
    names = list(compiled._channels)
    assert names and all(
        glob.glob(f"/dev/shm/rtchan_{n}") for n in names)
    compiled.teardown()
    # Channels unlinked; the actor serves normal calls again.
    assert not any(glob.glob(f"/dev/shm/rtchan_{n}") for n in names)
    assert ray_tpu.get(a.ping.remote()) == "pong"
    ray_tpu.kill(a)


def test_uncompilable_graph_falls_back(ray_shared):
    @ray_tpu.remote
    def double(x):
        return x * 2

    a = Adder.remote(5)
    with InputNode() as inp:
        dag = a.add.bind(double.bind(inp))   # task node => legacy path
    compiled = dag.experimental_compile()
    assert not compiled._channel_mode
    assert ray_tpu.get(compiled.execute(3)) == 11
    ray_tpu.kill(a)


def test_compiled_dag_across_nodes():
    """A 3-stage compiled DAG whose stages live on TWO cluster nodes:
    the compiler picks DCN net channels for cross-node edges (ray:
    torch_tensor_nccl_channel.py cross-worker channels) and shm for
    same-node ones; semantics (ordering, depth-1 backpressure, error
    propagation) are transport-independent."""
    from ray_tpu.cluster_utils import Cluster

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    cluster = Cluster()
    cluster.start_head()
    n1 = cluster.add_node(resources={"CPU": 2, "first": 1})
    n2 = cluster.add_node(resources={"CPU": 2, "second": 1})
    ray_tpu.init(address=cluster.address)
    try:
        cluster.wait_for_nodes(2)
        a = Adder.options(resources={"first": 0.1}).remote(1)
        b = Adder.options(resources={"second": 0.1}).remote(10)
        c = Adder.options(resources={"first": 0.1}).remote(100)
        ray_tpu.get([a.ping.remote(), b.ping.remote(), c.ping.remote()])
        with InputNode() as inp:
            dag = c.add.bind(b.add.bind(a.add.bind(inp)))
        compiled = dag.experimental_compile()
        try:
            assert compiled._channel_mode, "channel compilation must engage"
            # The a->b and b->c edges span nodes (wherever the driver's
            # agent landed), so net channels must actually be in play.
            assert compiled._net_edges >= 2, compiled._net_edges
            for i in range(10):
                assert compiled.execute(i).get(timeout=60) == i + 111
            # Error propagation crosses transports too.
            with pytest.raises(ValueError, match="bad input"):
                compiled.execute("boom").get(timeout=60)
            assert compiled.execute(5).get(timeout=60) == 116
        finally:
            compiled.teardown()
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
