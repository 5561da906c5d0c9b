"""Preprocessors: distributed fit + batch/dataset transform.

Mirrors ray: python/ray/data/tests/test_preprocessors*.py — fit
statistics over a Dataset (distributed via map_batches partials), then
transform datasets, standalone batches, and compose with Chain.
"""
import numpy as np
import pytest

import ray_tpu
from ray_tpu import data
from ray_tpu.data.preprocessor import PreprocessorNotFittedException
from ray_tpu.data.preprocessors import (Chain, Concatenator,
                                        CountVectorizer,
                                        CustomKBinsDiscretizer,
                                        FeatureHasher, HashingVectorizer,
                                        LabelEncoder, MaxAbsScaler,
                                        MinMaxScaler, MultiHotEncoder,
                                        Normalizer, OneHotEncoder,
                                        OrdinalEncoder, PowerTransformer,
                                        RobustScaler, SimpleImputer,
                                        StandardScaler, Tokenizer,
                                        UniformKBinsDiscretizer)


def test_standard_scaler_distributed_fit(ray_shared):
    vals = np.arange(20, dtype=np.float64)
    ds = data.from_items([{"a": float(v), "b": 1.0} for v in vals])
    sc = StandardScaler(["a"]).fit(ds)
    assert sc.stats_["a"]["mean"] == pytest.approx(vals.mean())
    assert sc.stats_["a"]["std"] == pytest.approx(vals.std())
    out = sc.transform(ds).to_numpy()
    assert out["a"].mean() == pytest.approx(0.0, abs=1e-9)
    assert out["a"].std() == pytest.approx(1.0)
    assert np.all(out["b"] == 1.0)          # untouched column


def test_unfitted_raises(ray_shared):
    with pytest.raises(PreprocessorNotFittedException):
        StandardScaler(["a"]).transform_batch({"a": np.ones(3)})


def test_minmax_maxabs_robust(ray_shared):
    ds = data.from_items([{"a": float(v)} for v in [-4, -2, 0, 2, 4, 6]])
    mm = MinMaxScaler(["a"]).fit(ds)
    out = mm.transform_batch({"a": np.array([-4.0, 6.0])})
    assert out["a"].tolist() == [0.0, 1.0]
    ma = MaxAbsScaler(["a"]).fit(ds)
    assert ma.transform_batch({"a": np.array([6.0])})["a"][0] == 1.0
    rs = RobustScaler(["a"]).fit(ds)
    assert rs.transform_batch(
        {"a": np.array([rs.stats_["a"]["median"]])})["a"][0] == 0.0


def test_encoders(ray_shared):
    rows = [{"color": c, "label": l}
            for c, l in [("red", "x"), ("blue", "y"), ("red", "x"),
                         ("green", "z")]]
    ds = data.from_items(rows)
    oe = OrdinalEncoder(["color"]).fit(ds)
    enc = oe.transform_batch({"color": np.array(["blue", "green", "red",
                                                 "??"])})
    assert enc["color"].tolist() == [0, 1, 2, -1]   # sorted categories

    le = LabelEncoder("label").fit(ds)
    b = le.transform_batch({"label": np.array(["x", "z"])})
    rt_back = le.inverse_transform_batch(b)
    assert rt_back["label"].tolist() == ["x", "z"]

    oh = OneHotEncoder(["color"]).fit(ds)
    b = oh.transform_batch({"color": np.array(["red", "blue"])})
    assert "color" not in b
    assert b["color_red"].tolist() == [1, 0]
    assert b["color_blue"].tolist() == [0, 1]
    assert b["color_green"].tolist() == [0, 0]


def test_multihot_encoder(ray_shared):
    ds = data.from_items([{"tags": ["a", "b"]}, {"tags": ["b", "c", "b"]}])
    mh = MultiHotEncoder(["tags"]).fit(ds)
    out = mh.transform_batch(
        {"tags": np.array([["a"], ["b", "b", "c"]], dtype=object)})
    assert out["tags"].shape == (2, 3)
    assert out["tags"][0].tolist() == [1, 0, 0]
    assert out["tags"][1].tolist() == [0, 2, 1]


def test_simple_imputer(ray_shared):
    ds = data.from_items([{"a": 1.0}, {"a": 3.0}, {"a": float("nan")}])
    im = SimpleImputer(["a"], strategy="mean").fit(ds)
    out = im.transform_batch({"a": np.array([np.nan, 5.0])})
    assert out["a"].tolist() == [2.0, 5.0]
    const = SimpleImputer(["a"], strategy="constant", fill_value=9.0)
    assert const.transform_batch(
        {"a": np.array([np.nan])})["a"][0] == 9.0
    mf = SimpleImputer(["c"], strategy="most_frequent").fit(
        data.from_items([{"c": "x"}, {"c": "y"}, {"c": "x"}]))
    assert mf.stats_["c"] == "x"


def test_nan_is_not_a_category(ray_shared):
    ds = data.from_items([{"a": 1.0}, {"a": float("nan")},
                          {"a": 2.0}, {"a": float("nan")}])
    oe = OrdinalEncoder(["a"]).fit(ds)
    assert len(oe.stats_["a"]) == 2          # 1.0 and 2.0 only


def test_constant_imputer_fits_all_missing_column(ray_shared):
    """Chain fits every stage; a constant imputer must not run (or
    crash in) the most_frequent aggregation."""
    ds = data.from_items([{"a": float("nan")}, {"a": float("nan")}])
    chain = Chain(SimpleImputer(["a"], strategy="constant", fill_value=7.0))
    out = chain.fit_transform(ds).to_numpy()
    assert out["a"].tolist() == [7.0, 7.0]
    with pytest.raises(ValueError, match="no non-missing"):
        SimpleImputer(["a"], strategy="most_frequent").fit(ds)


def test_discretizers(ray_shared):
    ds = data.from_items([{"a": float(v)} for v in np.arange(0, 10)])
    ud = UniformKBinsDiscretizer(["a"], bins=3).fit(ds)
    out = ud.transform(ds).to_numpy()["a"]
    assert out.min() == 0 and out.max() == 2
    cd = CustomKBinsDiscretizer(["a"], {"a": [0, 2, 5, 10]})
    got = cd.transform_batch({"a": np.array([1.0, 3.0, 7.0])})
    assert got["a"].tolist() == [0, 1, 2]


def test_stateless_transforms(ray_shared):
    nm = Normalizer(["v"], norm="l2")
    out = nm.transform_batch({"v": np.array([[3.0, 4.0]])})
    assert out["v"][0].tolist() == [0.6, 0.8]

    pt = PowerTransformer(["a"], power=0.5, method="box-cox")
    got = pt.transform_batch({"a": np.array([4.0])})
    assert got["a"][0] == pytest.approx((2.0 - 1) / 0.5)

    cat = Concatenator(["x", "y"], output_column_name="f")
    got = cat.transform_batch({"x": np.array([1.0, 2.0]),
                               "y": np.array([[3.0], [4.0]])})
    assert got["f"].shape == (2, 2)
    assert "x" not in got and "y" not in got

    tk = Tokenizer(["t"])
    got = tk.transform_batch({"t": np.array(["a b", "c"])})
    assert got["t"][0] == ["a", "b"]


def test_vectorizers_and_hasher(ray_shared):
    ds = data.from_items([{"t": "red red blue"}, {"t": "green blue"}])
    cv = CountVectorizer(["t"]).fit(ds)
    out = cv.transform_batch({"t": np.array(["red blue blue"])})
    vocab = cv.stats_["t"]
    row = out["t"][0]
    assert row[vocab["red"]] == 1 and row[vocab["blue"]] == 2

    hv = HashingVectorizer(["t"], num_features=8)
    out = hv.transform_batch({"t": np.array(["red red"])})
    assert out["t"].shape == (1, 8) and out["t"].sum() == 2

    fh = FeatureHasher(["tok"], num_features=4)
    out = fh.transform_batch(
        {"tok": np.array([{"a": 2, "b": 1}], dtype=object)})
    assert out["hashed_features"].shape == (1, 4)
    assert out["hashed_features"].sum() == 3.0


def test_chain_and_dataset_roundtrip(ray_shared):
    ds = data.from_items([{"a": float(v), "c": "u" if v % 2 else "v"}
                          for v in np.arange(8)])
    chain = Chain(SimpleImputer(["a"], strategy="mean"),
                  StandardScaler(["a"]),
                  OrdinalEncoder(["c"]))
    out = chain.fit_transform(ds).to_numpy()
    assert out["a"].mean() == pytest.approx(0.0, abs=1e-9)
    assert set(out["c"].tolist()) == {0, 1}
    # transform_batch composes identically
    b = chain.transform_batch({"a": np.array([0.0]),
                               "c": np.array(["u"])})
    assert b["c"][0] == 0


def test_preprocessor_pickles_through_tasks(ray_shared):
    """A fitted preprocessor ships to workers (AIR pattern: fit on the
    driver, transform inside map_batches tasks)."""
    ds = data.from_items([{"a": float(v)} for v in np.arange(10)])
    sc = StandardScaler(["a"]).fit(ds)

    @ray_tpu.remote
    def apply(p, vals):
        return p.transform_batch({"a": np.asarray(vals)})["a"].tolist()

    got = ray_tpu.get(apply.remote(sc, [0.0, 9.0]))
    assert got[0] == pytest.approx(-got[1])
