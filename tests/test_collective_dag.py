"""Collective-group + DAG tests.

Mirrors ray: python/ray/util/collective/tests/ (allreduce/broadcast/
send-recv across actors) and python/ray/dag/tests/ (bind/execute,
compiled DAGs).
"""
import numpy as np

import ray_tpu


@ray_tpu.remote
class CollectiveWorker:
    def __init__(self):
        self.rank = -1

    def init_collective_group(self, world_size, rank, backend, group_name):
        from ray_tpu import collective as col

        col.init_collective_group(world_size, rank, backend, group_name)
        self.rank = rank
        return rank

    def do_allreduce(self, group_name):
        from ray_tpu import collective as col

        x = np.full((4,), float(self.rank + 1))
        return col.allreduce(x, group_name=group_name)

    def do_allgather(self, group_name):
        from ray_tpu import collective as col

        return col.allgather(np.array([self.rank]), group_name=group_name)

    def do_reducescatter(self, group_name):
        from ray_tpu import collective as col

        x = np.arange(4, dtype=np.float64)
        return col.reducescatter(x, group_name=group_name)

    def do_broadcast(self, group_name):
        from ray_tpu import collective as col

        x = np.array([42.0]) if self.rank == 0 else np.array([0.0])
        return col.broadcast(x, src_rank=0, group_name=group_name)

    def do_send(self, dst, group_name):
        from ray_tpu import collective as col

        col.send(np.array([self.rank * 100.0]), dst, group_name=group_name)
        return True

    def do_recv(self, src, group_name):
        from ray_tpu import collective as col

        return col.recv(src, group_name=group_name)


def _cleanup(workers, group_name):
    """Explicitly release worker actors + the group's rendezvous so the
    shared cluster's CPUs free deterministically (GC kill is async)."""
    for w in workers:
        ray_tpu.kill(w)
    try:
        ray_tpu.kill(ray_tpu.get_actor(f"collective_rdv:{group_name}"))
    except ValueError:
        pass


def test_collective_allreduce_allgather(ray_shared):
    from ray_tpu import collective as col

    workers = [CollectiveWorker.remote() for _ in range(2)]
    col.create_collective_group(workers, 2, [0, 1], group_name="g1")

    out = ray_tpu.get([w.do_allreduce.remote("g1") for w in workers])
    np.testing.assert_allclose(out[0], np.full((4,), 3.0))
    np.testing.assert_allclose(out[1], np.full((4,), 3.0))

    gathered = ray_tpu.get([w.do_allgather.remote("g1") for w in workers])
    for g in gathered:
        assert [int(x[0]) for x in g] == [0, 1]

    rs = ray_tpu.get([w.do_reducescatter.remote("g1") for w in workers])
    np.testing.assert_allclose(rs[0], np.array([0.0, 2.0]))   # 2x[0,1]
    np.testing.assert_allclose(rs[1], np.array([4.0, 6.0]))   # 2x[2,3]

    bc = ray_tpu.get([w.do_broadcast.remote("g1") for w in workers])
    assert bc[0][0] == 42.0 and bc[1][0] == 42.0
    _cleanup(workers, "g1")


def test_collective_send_recv(ray_shared):
    from ray_tpu import collective as col

    workers = [CollectiveWorker.remote() for _ in range(2)]
    col.create_collective_group(workers, 2, [0, 1], group_name="g2")
    r_send = workers[0].do_send.remote(1, "g2")
    r_recv = workers[1].do_recv.remote(0, "g2")
    assert ray_tpu.get(r_send)
    assert ray_tpu.get(r_recv)[0] == 0.0
    _cleanup(workers, "g2")


def test_dag_function_chain(ray_shared):
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    def plus_one(x):
        return x + 1

    @ray_tpu.remote
    def times_two(x):
        return x * 2

    with InputNode() as inp:
        dag = times_two.bind(plus_one.bind(inp))

    assert ray_tpu.get(dag.execute(3)) == 8
    assert ray_tpu.get(dag.execute(10)) == 22


def test_dag_actor_methods_and_compile(ray_shared):
    from ray_tpu.dag import InputNode, MultiOutputNode

    @ray_tpu.remote
    class Stage:
        def __init__(self, mult):
            self.mult = mult
            self.calls = 0

        def fwd(self, x):
            self.calls += 1
            return x * self.mult

        def ncalls(self):
            return self.calls

    a = Stage.remote(2)
    b = Stage.remote(10)
    with InputNode() as inp:
        dag = b.fwd.bind(a.fwd.bind(inp))

    compiled = dag.experimental_compile()
    outs = [ray_tpu.get(compiled.execute(i)) for i in range(5)]
    assert outs == [i * 20 for i in range(5)]
    # While compiled, the execution loop occupies each actor (ray: the
    # compiled-DAG loop holds the actor); regular calls resume after
    # teardown.
    compiled.teardown()
    assert ray_tpu.get(a.ncalls.remote()) == 5

    # multi-output fan-out
    with InputNode() as inp:
        fan = MultiOutputNode([a.fwd.bind(inp), b.fwd.bind(inp)])
    r1, r2 = fan.execute(7)
    assert ray_tpu.get(r1) == 14
    assert ray_tpu.get(r2) == 70
    ray_tpu.kill(a)
    ray_tpu.kill(b)


def test_dag_input_attribute(ray_shared):
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    def add(x, y):
        return x + y

    with InputNode() as inp:
        dag = add.bind(inp["a"], inp["b"])
    assert ray_tpu.get(dag.execute(a=2, b=5)) == 7
