"""util shims: multiprocessing.Pool and the joblib backend.

Mirrors ray: python/ray/util/multiprocessing tests + util/joblib tests
(drop-in Pool surface; joblib parallel_backend("ray") running sklearn-ish
workloads as tasks).
"""
import pytest

import ray_tpu


def _sq(x):
    return x * x


def _add(a, b):
    return a + b


def test_pool_map_apply(ray_shared):
    from ray_tpu.utils.multiprocessing import Pool

    with Pool(processes=2) as p:
        assert p.map(_sq, range(10)) == [x * x for x in range(10)]
        assert p.apply(_add, (3, 4)) == 7
        assert p.starmap(_add, [(1, 2), (3, 4)]) == [3, 7]


def test_pool_async_and_imap(ray_shared):
    from ray_tpu.utils.multiprocessing import Pool

    with Pool(processes=2) as p:
        ar = p.map_async(_sq, range(6))
        assert ar.get(timeout=60) == [0, 1, 4, 9, 16, 25]
        assert ar.ready() and ar.successful()
        assert list(p.imap(_sq, range(5), chunksize=2)) == [0, 1, 4, 9, 16]
        assert sorted(p.imap_unordered(_sq, range(5), chunksize=2)) == \
            [0, 1, 4, 9, 16]
        one = p.apply_async(_add, (10, 20))
        assert one.get(timeout=60) == 30


def test_pool_closed_rejects(ray_shared):
    from ray_tpu.utils.multiprocessing import Pool

    p = Pool(processes=1)
    p.close()
    with pytest.raises(ValueError):
        p.map(_sq, [1])


def test_joblib_backend(ray_shared):
    joblib = pytest.importorskip("joblib")
    from ray_tpu.utils.joblib_backend import register_ray_tpu

    register_ray_tpu()
    with joblib.parallel_backend("ray_tpu"):
        out = joblib.Parallel(n_jobs=2)(
            joblib.delayed(_sq)(i) for i in range(8))
    assert out == [i * i for i in range(8)]


def test_dask_on_ray_tpu_scheduler(ray_shared):
    """Raw dask-graph execution (ray: util/dask/scheduler.py ray_dask_get)
    — the graph format is plain data, so the scheduler tests without dask
    installed."""
    import operator

    from ray_tpu.utils.dask import get

    dsk = {
        "a": 1,
        "b": (operator.add, "a", 10),
        "c": (operator.mul, "b", "b"),
        "d": (sum, ["a", "b", "c"]),
        # nested inner task executes worker-side
        "e": (operator.add, (operator.mul, "a", 100), "b"),
    }
    assert get(dsk, "d") == 1 + 11 + 121
    assert get(dsk, ["b", ["c", "e"]]) == [11, [121, 111]]
    # literals pass through untouched
    assert get({"x": "not-a-key"}, "x") == "not-a-key"


def test_gbdt_trainer_gates_cleanly(ray_shared):
    """XGBoostTrainer (ray: train/xgboost) builds the full data-parallel
    run; with xgboost absent from this image the workers surface a clear
    ImportError naming the runtime_env escape hatch."""
    from ray_tpu import data as rd
    from ray_tpu.train import ScalingConfig, XGBoostTrainer

    ds = rd.from_items([{"x": float(i), "label": float(i % 2)}
                        for i in range(20)])
    trainer = XGBoostTrainer(
        params={"objective": "binary:logistic"},
        num_boost_round=2,
        scaling_config=ScalingConfig(num_workers=1),
        datasets={"train": ds})
    result = trainer.fit()
    try:
        import xgboost  # noqa: F401

        assert result.error is None
        assert result.metrics["boost_rounds"] == 2
    except ImportError:
        assert result.error is not None
        assert "xgboost" in str(result.error)


def test_train_dataset_shards(ray_shared, tmp_path):
    """train.get_dataset_shard streams each worker its split (ray:
    DataParallelTrainer + streaming_split): together the two workers
    consume every row exactly once."""
    from ray_tpu import data as rd
    from ray_tpu import train

    out_dir = str(tmp_path)

    def loop(config):
        shard = train.get_dataset_shard("train")
        rank = train.get_context().get_world_rank()
        total = 0
        for batch in shard.iter_batches(batch_size=8):
            total += int(batch["id"].sum())
        with open(f"{config['out_dir']}/rank{rank}.txt", "w") as f:
            f.write(str(total))
        train.report({"total": total})

    ds = rd.range(32, parallelism=4)
    trainer = train.JaxTrainer(
        loop, train_loop_config={"out_dir": out_dir},
        scaling_config=train.ScalingConfig(num_workers=2),
        datasets={"train": ds})
    result = trainer.fit()
    assert result.error is None
    import glob

    totals = [int(open(p).read())
              for p in glob.glob(f"{out_dir}/rank*.txt")]
    assert len(totals) == 2
    assert sum(totals) == sum(range(32))


def test_queue_nowait_and_batches(ray_shared):
    from ray_tpu.utils.queue import Empty, Full, Queue

    q = Queue(maxsize=3)
    q.put_nowait(1)
    q.put_nowait_batch([2, 3])
    assert q.full()
    assert q.size() == 3
    with pytest.raises(Full):
        q.put_nowait(4)
    with pytest.raises(Full):
        q.put_nowait_batch([4])          # all-or-nothing
    assert q.get_nowait() == 1
    assert q.get_nowait_batch(2) == [2, 3]
    with pytest.raises(Empty):
        q.get_nowait()
    with pytest.raises(Empty):
        q.get_nowait_batch(1)
    q.shutdown()


def test_actor_pool_free_pop_push(ray_shared):
    import ray_tpu
    from ray_tpu.utils import ActorPool

    @ray_tpu.remote
    class W:
        def work(self, x):
            return x + 1

    actors = [W.remote() for _ in range(2)]
    pool = ActorPool(actors)
    assert pool.has_free()
    a = pool.pop_idle()
    assert a is not None
    pool.push(a)
    pool.submit(lambda ac, v: ac.work.remote(v), 1)
    pool.submit(lambda ac, v: ac.work.remote(v), 2)
    pool.submit(lambda ac, v: ac.work.remote(v), 3)   # queues (2 actors)
    assert not pool.has_free()
    out = [pool.get_next(timeout=60) for _ in range(3)]
    assert out == [2, 3, 4]
    assert pool.has_free()
    for ac in actors:
        ray_tpu.kill(ac)
