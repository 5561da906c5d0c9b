"""Behavioral Dataset tests: semantics vs numpy ground truth on
MULTI-BLOCK datasets (round-4 verdict weak #5: the parity batches were
smoke-tested — one assert each; these check the math).

Reference analogs: ray python/ray/data/tests/test_all_to_all.py
(groupby/aggregate ground truth), test_split.py (split_at_indices
semantics at block boundaries)."""
import numpy as np
import pytest

from ray_tpu.data import from_items, range as data_range


def _multiblock(n=100, blocks=7, seed=3):
    """n rows spread over `blocks` blocks with a non-trivial value col."""
    rng = np.random.default_rng(seed)
    vals = rng.normal(10.0, 5.0, n)
    keys = rng.integers(0, 5, n)
    items = [{"k": int(keys[i]), "v": float(vals[i])} for i in range(n)]
    ds = from_items(items, parallelism=blocks)
    return ds, keys, vals


class TestAggregationGroundTruth:
    def test_global_aggregates(self, ray_shared):
        ds, _, vals = _multiblock()
        assert ds.count() == 100
        assert np.isclose(ds.sum("v"), vals.sum())
        assert np.isclose(ds.min("v"), vals.min())
        assert np.isclose(ds.max("v"), vals.max())
        assert np.isclose(ds.mean("v"), vals.mean())
        assert np.isclose(ds.std("v"), vals.std(ddof=1))

    def test_aggregate_multi(self, ray_shared):
        ds, _, vals = _multiblock()
        out = ds.aggregate(lo=("v", "min"), hi=("v", "max"),
                           total=("v", "sum"))
        assert np.isclose(out["lo"], vals.min())
        assert np.isclose(out["hi"], vals.max())
        assert np.isclose(out["total"], vals.sum())

    def test_groupby_ground_truth(self, ray_shared):
        ds, keys, vals = _multiblock()
        got = {r["k"]: r for r in ds.groupby("k").mean("v").take_all()}
        for k in np.unique(keys):
            expect = vals[keys == k].mean()
            assert np.isclose(got[int(k)]["mean(v)"], expect), (k, got)

    def test_groupby_count_sums_to_total(self, ray_shared):
        ds, keys, _ = _multiblock()
        rows = ds.groupby("k").count().take_all()
        cc = next(c for c in rows[0] if c.startswith("count"))
        assert sum(r[cc] for r in rows) == 100
        for r in rows:
            assert r[cc] == int((keys == r["k"]).sum())

    def test_unique_multiblock(self, ray_shared):
        ds, keys, _ = _multiblock()
        assert sorted(ds.unique("k")) == sorted(
            int(x) for x in np.unique(keys))

    def test_sort_ground_truth_across_blocks(self, ray_shared):
        ds, _, vals = _multiblock()
        got = [r["v"] for r in ds.sort("v").take_all()]
        assert np.allclose(got, np.sort(vals))
        got_desc = [r["v"] for r in
                    ds.sort("v", descending=True).take_all()]
        assert np.allclose(got_desc, np.sort(vals)[::-1])


class TestSplitSemantics:
    def test_split_at_indices_row_exact(self, ray_shared):
        """Pieces hold EXACTLY their row ranges even when cuts land
        mid-block (blocks of ~15 rows, cuts at 7/23/88)."""
        ds = data_range(100, parallelism=7)
        pieces = ds.split_at_indices([7, 23, 88])
        rows = [[r["id"] for r in p.take_all()] for p in pieces]
        assert rows[0] == list(range(0, 7))
        assert rows[1] == list(range(7, 23))
        assert rows[2] == list(range(23, 88))
        assert rows[3] == list(range(88, 100))

    def test_split_at_indices_keeps_interior_blocks_by_ref(self, ray_shared):
        """The round-5 redesign: interior blocks move by REFERENCE (no
        row rewrite).  A single piece covering whole blocks shares block
        count with the source."""
        ds = data_range(90, parallelism=9)       # 9 blocks x 10 rows
        ds.materialize()
        pieces = ds.split_at_indices([30])       # cut at a block edge
        pieces[0].materialize()
        pieces[1].materialize()
        assert len(pieces[0]._materialized) == 3
        assert len(pieces[1]._materialized) == 6
        # block-boundary cut: the pieces reuse the SOURCE block refs
        src = {r.hex() for r in ds._materialized}
        for p in pieces:
            for r in p._materialized:
                assert r.hex() in src

    def test_split_at_indices_empty_and_clamped(self, ray_shared):
        ds = data_range(10, parallelism=3)
        pieces = ds.split_at_indices([0, 5, 5, 50])
        counts = [p.count() for p in pieces]
        assert counts == [0, 5, 0, 5, 0]

    def test_split_proportionately_ground_truth(self, ray_shared):
        ds = data_range(100, parallelism=6)
        a, b, c = ds.split_proportionately([0.3, 0.5])
        assert (a.count(), b.count(), c.count()) == (30, 50, 20)
        got = [r["id"] for r in a.take_all()] + \
              [r["id"] for r in b.take_all()] + \
              [r["id"] for r in c.take_all()]
        assert got == list(range(100))

    def test_train_test_split_partition(self, ray_shared):
        ds = data_range(50, parallelism=4)
        train, test = ds.train_test_split(0.25)
        # floor semantics: the train cut lands at int(50 * 0.75) == 37
        assert train.count() == 37 and test.count() == 13
        ids = sorted(r["id"] for r in train.take_all()) + \
            sorted(r["id"] for r in test.take_all())
        assert sorted(ids) == list(range(50))


class TestRandomSampleStatistics:
    def test_seeded_sample_varies_across_blocks(self, ray_shared):
        """Round-4 advisor medium: with a seed, every block drew the
        IDENTICAL keep-mask.  Multi-block sampling must not keep the
        same row positions in each block."""
        n_blocks, per_block = 8, 64
        ds = data_range(n_blocks * per_block, parallelism=n_blocks)
        kept = [r["id"] for r in
                ds.random_sample(0.5, seed=7).take_all()]
        positions = [set() for _ in range(n_blocks)]
        for i in kept:
            positions[i // per_block].add(i % per_block)
        distinct = {frozenset(p) for p in positions}
        assert len(distinct) > 1, "identical keep-mask in every block"

    def test_seeded_sample_deterministic(self, ray_shared):
        ds = data_range(200, parallelism=4)
        a = [r["id"] for r in ds.random_sample(0.4, seed=11).take_all()]
        b = [r["id"] for r in ds.random_sample(0.4, seed=11).take_all()]
        assert a == b

    def test_sample_fraction_bounds(self, ray_shared):
        ds = data_range(400, parallelism=4)
        kept = ds.random_sample(0.5, seed=3).count()
        assert 120 <= kept <= 280, kept       # ~Binomial(400, .5)
        assert ds.random_sample(0.0).count() == 0
        assert ds.random_sample(1.0).count() == 400
        with pytest.raises(ValueError):
            ds.random_sample(1.5)
