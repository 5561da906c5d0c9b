"""Data source breadth + per-operator stats: images, binary files,
TFRecords (crc-verified round-trip), and ds.stats() (reference:
python/ray/data/datasource/{image,binary,tfrecords}_datasource.py +
data/_internal/stats.py).
"""
import numpy as np
import pytest

from ray_tpu import data as rdata


def test_read_images(ray_shared, tmp_path):
    from PIL import Image

    for i in range(3):
        arr = np.full((8, 6, 3), i * 40, np.uint8)
        Image.fromarray(arr).save(tmp_path / f"img{i}.png")
    ds = rdata.read_images(str(tmp_path))
    rows = ds.take_all()
    assert len(rows) == 3
    rows.sort(key=lambda r: r["path"])
    for i, r in enumerate(rows):
        img = np.asarray(r["image"], np.uint8).reshape(r["shape"])
        assert img.shape == (8, 6, 3)
        assert int(img[0, 0, 0]) == i * 40


def test_read_binary_files(ray_shared, tmp_path):
    payloads = {f"f{i}.bin": bytes([i]) * (100 + i) for i in range(3)}
    for name, data in payloads.items():
        (tmp_path / name).write_bytes(data)
    rows = rdata.read_binary_files(str(tmp_path)).take_all()
    assert len(rows) == 3
    for r in rows:
        name = r["path"].rsplit("/", 1)[-1]
        assert r["bytes"] == payloads[name]


def test_tfrecord_roundtrip(ray_shared, tmp_path):
    records = [f"record-{i}".encode() * (i + 1) for i in range(7)]
    ds = rdata.from_items([{"record": r} for r in records])
    out = tmp_path / "tfr"
    ds.write_tfrecords(str(out))
    back = rdata.read_tfrecords(str(out)).take_all()
    assert sorted(r["record"] for r in back) == sorted(records)


def test_tfrecord_corruption_detected(ray_shared, tmp_path):
    ds = rdata.from_items([{"record": b"x" * 64}])
    out = tmp_path / "tfr"
    ds.write_tfrecords(str(out))
    f = next(out.iterdir())
    raw = bytearray(f.read_bytes())
    raw[20] ^= 0xFF                      # flip a payload byte
    f.write_bytes(bytes(raw))
    with pytest.raises(Exception, match="corrupt"):
        rdata.read_tfrecords(str(out), verify=True).take_all()


def test_dataset_stats(ray_shared):
    ds = rdata.range(1000, parallelism=4).map_batches(
        lambda b: {"id": b["id"] * 2}).filter(lambda r: r["id"] % 4 == 0)
    assert "not been executed" in ds.stats()
    ds.take_all()
    st = ds.stats()
    assert "Input" in st and "tasks=" in st and "blocks_out=" in st
    # Every operator ran tasks and completed.
    for line in st.splitlines():
        assert "done" in line, st


class TestRound4Connectors:
    def test_read_sql_sqlite(self, ray_shared, tmp_path):
        import sqlite3

        db = str(tmp_path / "t.db")
        conn = sqlite3.connect(db)
        conn.execute("CREATE TABLE kv (k TEXT, v INTEGER)")
        conn.executemany("INSERT INTO kv VALUES (?, ?)",
                         [("a", 1), ("b", 2), ("c", 3)])
        conn.commit()
        conn.close()
        import ray_tpu.data as rd

        out = rd.read_sql("SELECT k, v FROM kv ORDER BY v",
                          lambda: sqlite3.connect(db)).take_all()
        assert out == [{"k": "a", "v": 1}, {"k": "b", "v": 2},
                       {"k": "c", "v": 3}]

    def test_avro_roundtrip(self, ray_shared, tmp_path):
        from ray_tpu.data.datasource import write_avro
        import ray_tpu.data as rd

        schema = {"type": "record", "name": "R", "fields": [
            {"name": "id", "type": "long"},
            {"name": "name", "type": "string"},
            {"name": "score", "type": "double"},
            {"name": "tags", "type": {"type": "array",
                                      "items": "string"}},
            {"name": "note", "type": ["null", "string"]},
        ]}
        rows = [{"id": i, "name": f"n{i}", "score": i * 0.5,
                 "tags": ["x", f"t{i}"], "note": None if i % 2 else f"m{i}"}
                for i in range(20)]
        path = str(tmp_path / "r.avro")
        write_avro(rows, schema, path)
        got = rd.read_avro(path).take_all()
        assert len(got) == len(rows)
        for g, r in zip(got, rows):
            assert g["id"] == r["id"] and g["name"] == r["name"]
            assert abs(g["score"] - r["score"]) < 1e-9
            assert list(g["tags"]) == r["tags"]     # arrow -> ndarray
            assert g["note"] == r["note"]

    def test_read_webdataset(self, ray_shared, tmp_path):
        import io
        import tarfile

        shard = str(tmp_path / "shard-000.tar")
        with tarfile.open(shard, "w") as tf:
            for key in ("s0", "s1"):
                for ext, payload in (("jpg", b"IMG" + key.encode()),
                                     ("cls", key[-1].encode())):
                    data = payload
                    info = tarfile.TarInfo(f"{key}.{ext}")
                    info.size = len(data)
                    tf.addfile(info, io.BytesIO(data))
        import ray_tpu.data as rd

        rows = rd.read_webdataset(shard).take_all()
        assert [r["__key__"] for r in rows] == ["s0", "s1"]
        assert rows[0]["jpg"] == b"IMGs0" and rows[1]["cls"] == b"1"

    def test_from_huggingface_local(self, ray_shared):
        import datasets as hfds
        import ray_tpu.data as rd

        hf = hfds.Dataset.from_dict(
            {"text": [f"doc {i}" for i in range(10)],
             "label": list(range(10))})
        out = rd.from_huggingface(hf)
        assert out.count() == 10
        assert sorted(r["label"] for r in out.take_all()) == list(range(10))
