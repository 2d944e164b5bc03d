"""ray_tpu.data — Dataset/blocks/readers/streaming execution.

Reference test analogue: `python/ray/data/tests/test_dataset.py` (creation,
map/map_batches, split, shuffle, iteration semantics).
"""

import os
import time

import numpy as np
import pytest

from ray_tpu import data as rd


@pytest.fixture(scope="module")
def ray(ray_shared):
    return ray_shared


def test_range_count_take(ray):
    ds = rd.range(100, parallelism=5)
    assert ds.count() == 100
    assert [r["id"] for r in ds.take(5)] == [0, 1, 2, 3, 4]
    assert ds.num_blocks() == 5


def test_from_items_rows(ray):
    ds = rd.from_items([{"x": i, "y": -i} for i in range(10)], parallelism=3)
    rows = ds.take_all()
    assert len(rows) == 10
    assert rows[3] == {"x": 3, "y": -3}


def test_from_numpy_schema(ray):
    ds = rd.from_numpy(np.ones((12, 4), np.float32), parallelism=4)
    assert ds.count() == 12
    schema = ds.schema()
    assert schema == {"value": "float32"}


def test_map_batches(ray):
    ds = rd.range(64, parallelism=4).map_batches(
        lambda b: {"id": b["id"] * 2})
    vals = [r["id"] for r in ds.take_all()]
    assert vals == [i * 2 for i in range(64)]


def test_map_batches_batch_size(ray):
    seen = []

    def fn(b):
        # runs in a worker; record batch length via output
        return {"id": b["id"], "n": np.full(len(b["id"]), len(b["id"]))}

    ds = rd.range(10, parallelism=1).map_batches(fn, batch_size=4)
    ns = [r["n"] for r in ds.take_all()]
    assert ns == [4, 4, 4, 4, 4, 4, 4, 4, 2, 2]


def test_map_filter_flat_map_fuse(ray):
    ds = (rd.range(20, parallelism=2)
          .map(lambda r: {"id": r["id"] + 1})
          .filter(lambda r: r["id"] % 2 == 0)
          .flat_map(lambda r: [{"id": r["id"]}, {"id": -r["id"]}]))
    vals = [r["id"] for r in ds.take_all()]
    assert vals[:4] == [2, -2, 4, -4]
    assert len(vals) == 20


def test_iter_batches_spans_blocks(ray):
    ds = rd.range(25, parallelism=4)  # ragged blocks: 7,6,6,6
    batches = list(ds.iter_batches(batch_size=10))
    assert [len(b["id"]) for b in batches] == [10, 10, 5]
    assert list(batches[0]["id"]) == list(range(10))
    batches = list(ds.iter_batches(batch_size=10, drop_last=True))
    assert [len(b["id"]) for b in batches] == [10, 10]


def test_iter_batches_local_shuffle(ray):
    ds = rd.range(100, parallelism=4)
    flat = np.concatenate([b["id"] for b in ds.iter_batches(
        batch_size=10, local_shuffle_buffer_size=50, local_shuffle_seed=0)])
    assert len(flat) == 100
    assert set(flat.tolist()) == set(range(100))
    assert flat.tolist() != list(range(100))


def test_split_block_granularity(ray):
    ds = rd.range(100, parallelism=10)
    shards = ds.split(3)
    counts = [s.count() for s in shards]
    assert sum(counts) == 100
    assert max(counts) - min(counts) <= 10  # balanced within a block
    all_ids = sorted(i for s in shards for i in (r["id"] for r in s.take_all()))
    assert all_ids == list(range(100))


def test_split_equal(ray):
    ds = rd.range(101, parallelism=4)
    shards = ds.split(4, equal=True)
    assert [s.count() for s in shards] == [25, 25, 25, 25]


def test_repartition(ray):
    ds = rd.range(30, parallelism=3).repartition(5)
    assert ds.num_blocks() == 5
    assert ds.count() == 30
    assert [r["id"] for r in ds.take_all()] == list(range(30))


def test_random_shuffle(ray):
    ds = rd.range(200, parallelism=4).random_shuffle(seed=7)
    vals = [r["id"] for r in ds.take_all()]
    assert sorted(vals) == list(range(200))
    assert vals != list(range(200))
    # deterministic given the seed
    vals2 = [r["id"] for r in
             rd.range(200, parallelism=4).random_shuffle(seed=7).take_all()]
    assert vals == vals2


def test_sort(ray):
    rng = np.random.default_rng(0)
    items = rng.permutation(50).tolist()
    ds = rd.from_items([{"v": int(v)} for v in items], parallelism=5)
    out = [r["v"] for r in ds.sort(key="v").take_all()]
    assert out == sorted(items)
    out_desc = [r["v"] for r in ds.sort(key="v", descending=True).take_all()]
    assert out_desc == sorted(items, reverse=True)


def test_union_zip_limit(ray):
    a = rd.range(10, parallelism=2)
    b = rd.range(10, parallelism=2).map_batches(lambda x: {"id2": x["id"] + 100})
    assert a.union(rd.range(5, parallelism=1)).count() == 15
    z = a.zip(b)
    rows = z.take_all()
    assert rows[0] == {"id": 0, "id2": 100}
    lim = rd.range(100, parallelism=10).limit(13)
    assert lim.count() == 13
    assert [r["id"] for r in lim.take_all()] == list(range(13))


def test_aggregates(ray):
    ds = rd.range(10, parallelism=3)
    assert ds.sum("id") == 45
    assert ds.min("id") == 0
    assert ds.max("id") == 9
    assert ds.mean("id") == 4.5


def test_add_drop_select_columns(ray):
    ds = (rd.range(5, parallelism=1)
          .add_column("sq", lambda b: b["id"] ** 2)
          .add_column("junk", lambda b: b["id"] * 0))
    assert set(ds.schema().keys()) == {"id", "sq", "junk"}
    ds2 = ds.drop_columns(["junk"])
    assert set(ds2.schema().keys()) == {"id", "sq"}
    ds3 = ds.select_columns(["sq"])
    assert [r["sq"] for r in ds3.take_all()] == [0, 1, 4, 9, 16]


def test_parquet_roundtrip(ray, tmp_path):
    path = str(tmp_path / "pq")
    rd.range(40, parallelism=4).map_batches(
        lambda b: {"id": b["id"], "x": b["id"] * 0.5}).write_parquet(path)
    assert len(os.listdir(path)) == 4
    ds = rd.read_parquet(path)
    assert ds.count() == 40
    assert ds.schema()["x"] == "float64"
    assert [r["id"] for r in ds.take(3)] == [0, 1, 2]


def test_csv_json_text(ray, tmp_path):
    csv = tmp_path / "f.csv"
    csv.write_text("a,b\n1,x\n2,y\n")
    ds = rd.read_csv(str(csv))
    assert ds.count() == 2
    assert ds.take(1)[0]["a"] == 1

    jsonl = tmp_path / "f.jsonl"
    jsonl.write_text('{"k": 1}\n{"k": 2}\n')
    assert [r["k"] for r in rd.read_json(str(jsonl)).take_all()] == [1, 2]

    txt = tmp_path / "f.txt"
    txt.write_text("hello\nworld\n")
    assert [r["text"] for r in rd.read_text(str(txt)).take_all()] == [
        "hello", "world"]


def test_streaming_is_parallel(ray):
    """Blocks must execute concurrently (not serially) through the
    streaming executor."""

    @ray.remote
    def _warm():
        return 0

    # worker spawn costs ~2.3s of jax import apiece on a 1-vCPU host —
    # warm the pool so the assertion measures scheduling, not cold start
    ray.get([_warm.remote() for _ in range(8)], timeout=60)

    def slow(b):
        time.sleep(0.4)
        return b

    ds = rd.range(8, parallelism=8).map_batches(slow)
    t0 = time.perf_counter()
    assert ds.count() == 8
    dt = time.perf_counter() - t0
    assert dt < 8 * 0.4 * 0.6, f"map tasks look serial: {dt:.2f}s"


def test_streaming_bounded_window(ray):
    """iter_batches must not materialize the whole dataset up front: the
    first batch arrives before all blocks could possibly have finished."""

    def slow(b):
        time.sleep(0.3)
        return b

    ds = rd.range(32, parallelism=16).map_batches(slow)
    t0 = time.perf_counter()
    first = next(iter(ds.iter_batches(batch_size=2, prefetch_blocks=4)))
    dt = time.perf_counter() - t0
    assert len(first["id"]) == 2
    assert dt < 16 * 0.3 * 0.5, f"first batch waited for full pipeline: {dt:.2f}s"


def test_lazy_plan_does_not_execute_until_consumed(ray):
    marker = str(time.time())

    def boom(b):
        raise RuntimeError("should not run " + marker)

    ds = rd.range(4, parallelism=2).map_batches(boom)  # no error yet
    assert isinstance(repr(ds), str)
    with pytest.raises(Exception):
        ds.count()


def test_data_iterator_wrapper(ray):
    from ray_tpu.data import DataIterator

    it = DataIterator(rd.range(16, parallelism=2))
    batches = list(it.iter_batches(batch_size=8))
    assert len(batches) == 2
    jb = list(it.iter_jax_batches(batch_size=8))
    assert jb[0]["id"].shape == (8,)


def test_random_shuffle_single_block(ray):
    """Regression: parallelism=1 shuffle must not wrap the block in a
    1-tuple (num_returns=1 stores tuples whole)."""
    ds = rd.range(5, parallelism=1).random_shuffle(seed=0)
    rows = ds.take_all()
    assert sorted(r["id"] for r in rows) == [0, 1, 2, 3, 4]


def test_sort_all_empty_blocks(ray):
    ds = rd.from_items([{"v": 1}], parallelism=1).filter(
        lambda r: False).materialize()
    ds = ds.union(rd.from_items([{"v": 2}], parallelism=1).filter(
        lambda r: False).materialize())
    assert ds.sort(key="v").count() == 0


def test_map_batches_actor_pool_with_class_udf(ray_shared):
    from ray_tpu.data import ActorPoolStrategy
    import ray_tpu.data as rdata

    class AddBase:
        def __init__(self):
            self.base = 100  # expensive setup happens once per actor

        def __call__(self, batch):
            return {"v": batch["v"] + self.base}

    ds = rdata.from_items([{"v": i} for i in range(20)], parallelism=4)
    out = ds.map_batches(AddBase, compute=ActorPoolStrategy(size=2),
                         batch_size=5)
    vals = sorted(r["v"] for r in out.take_all())
    assert vals == [100 + i for i in range(20)]


def test_map_batches_class_without_actors_rejected(ray_shared):
    import ray_tpu.data as rdata

    class Udf:
        def __call__(self, b):
            return b

    with pytest.raises(ValueError, match="ActorPoolStrategy"):
        rdata.range(4).map_batches(Udf)


def test_union(ray_shared):
    import ray_tpu.data as rdata

    a = rdata.from_items([1, 2, 3])
    b = rdata.from_items([4, 5])
    assert sorted(a.union(b).take_all()) == [1, 2, 3, 4, 5]


def test_zip_dict_blocks(ray_shared):
    import ray_tpu.data as rdata

    a = rdata.from_items([{"x": i} for i in range(6)], parallelism=2)
    b = rdata.from_items([{"y": i * 10} for i in range(6)], parallelism=3)
    rows = a.zip(b).take_all()
    assert [(r["x"], r["y"]) for r in rows] == [(i, i * 10)
                                               for i in range(6)]


def test_groupby_map_groups(ray_shared):
    import numpy as np

    import ray_tpu.data as rdata

    ds = rdata.from_items(
        [{"k": i % 3, "v": float(i)} for i in range(12)], parallelism=4)

    def normalize(batch):
        return {"k": batch["k"], "v": batch["v"] - batch["v"].mean()}

    out = ds.groupby("k").map_groups(normalize)
    rows = out.take_all()
    assert len(rows) == 12
    by_k = {}
    for r in rows:
        by_k.setdefault(int(r["k"]), []).append(float(r["v"]))
    for k, vs in by_k.items():
        assert abs(sum(vs)) < 1e-6  # centered within each group


def test_groupby_aggregates(ray_shared):
    import ray_tpu.data as rdata

    ds = rdata.from_items(
        [{"k": "a" if i % 2 else "b", "v": i} for i in range(10)])
    counts = {r["key"]: r["count"]
              for r in ds.groupby("k").count().take_all()}
    assert counts == {"a": 5, "b": 5}
    sums = {r["key"]: r["sum"] for r in ds.groupby("k").sum("v").take_all()}
    assert sums == {"a": 1 + 3 + 5 + 7 + 9, "b": 0 + 2 + 4 + 6 + 8}


@pytest.mark.slow
def test_iter_torch_batches(ray):
    """Torch-tensor batches off columnar blocks (reference:
    ``Dataset.iter_torch_batches``)."""
    import torch

    ds = rd.from_numpy(np.arange(10, dtype=np.float32))
    batches = list(ds.iter_torch_batches(batch_size=4))
    assert all(isinstance(b["value"], torch.Tensor) for b in batches)
    got = torch.cat([b["value"] for b in batches])
    assert torch.equal(got, torch.arange(10, dtype=torch.float32))
    # dtype coercion
    b = next(ds.iter_torch_batches(batch_size=10, dtypes=torch.int64))
    assert b["value"].dtype == torch.int64


def test_write_parquet_csv_json_roundtrip(ray, tmp_path):
    """Distributed write, one file per block, read back equal (reference:
    ``Dataset.write_parquet/write_csv/write_json``)."""
    import pandas as pd

    df = pd.DataFrame({"a": np.arange(7), "b": np.arange(7) * 0.5})
    ds = rd.from_pandas(df, parallelism=2)

    files = ds.write_parquet(str(tmp_path / "pq"))
    assert len(files) == 2
    back = rd.read_parquet(str(tmp_path / "pq")).take_all()
    assert sorted(r["a"] for r in back) == list(range(7))

    files = ds.write_csv(str(tmp_path / "csv"))
    back = rd.read_csv(str(tmp_path / "csv")).take_all()
    assert sorted(int(r["a"]) for r in back) == list(range(7))

    files = ds.write_json(str(tmp_path / "js"))
    back = rd.read_json(str(tmp_path / "js")).take_all()
    assert sorted(int(r["a"]) for r in back) == list(range(7))


def test_train_test_split(ray):
    ds = rd.range(100)
    train, test = ds.train_test_split(0.2)
    assert train.count() == 80 and test.count() == 20
    # shuffled split covers all rows exactly once
    train_s, test_s = rd.range(50).train_test_split(
        0.3, shuffle=True, seed=0)
    def vals(ds):
        return [int(r["id"]) if isinstance(r, dict) else int(r)
                for r in ds.take_all()]

    assert sorted(vals(train_s) + vals(test_s)) == list(range(50))
    with pytest.raises(ValueError):
        ds.train_test_split(1.5)


# ---------------------------------------------------------------------------
# streaming split (reference: _internal/iterator/stream_split_iterator.py)


def test_streaming_split_disjoint_coverage(ray):
    """N shards jointly cover every row exactly once, without an up-front
    materialize (blocks execute lazily as shards claim them)."""
    ds = rd.range(1000, parallelism=10).map_batches(
        lambda b: {"id": b["id"] * 2})
    shards = ds.streaming_split(3)
    seen = []
    for sh in shards:
        for batch in sh.iter_batches(batch_size=64):
            seen.extend(batch["id"].tolist())
    assert sorted(seen) == [2 * i for i in range(1000)]
    # each shard took SOMETHING (pull-based balancing, 10 blocks over 3)
    # and a second epoch re-covers everything
    seen2 = []
    for sh in shards:
        seen2.extend(r["id"] for r in sh.iter_rows())
    assert sorted(seen2) == [2 * i for i in range(1000)]


def test_streaming_split_feeds_train_workers(ray, tmp_path):
    """DataParallelTrainer ingest: each worker's get_dataset_shard is a
    streaming-split iterator; the union of rows seen across workers is the
    whole dataset with no overlap (reference: stream_split ingest)."""
    import json

    from ray_tpu import train
    from ray_tpu.train import ScalingConfig

    ds = rd.range(256, parallelism=8)
    out_dir = str(tmp_path)

    def loop(config):
        from ray_tpu.data.iterator import StreamSplitDataIterator
        from ray_tpu.train import session

        shard = session.get_dataset_shard("train")
        assert isinstance(shard, StreamSplitDataIterator), type(shard)
        import os
        import time

        ids = []
        rank = session.get_world_rank()
        for batch in shard.iter_batches(batch_size=32):
            ids.extend(int(x) for x in batch["id"])
            # the split is pulled: whoever asks takes.  A worker holds its
            # first batch until the other has one too, so that neither,
            # started late on a loaded host, finds the blocks all taken
            open(f"{config['out']}/first_{rank}", "w").close()
            deadline = time.monotonic() + 120
            while not os.path.exists(f"{config['out']}/first_{1 - rank}") \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
        with open(f"{config['out']}/rank_{rank}.json", "w") as f:
            json.dump(ids, f)
        session.report({"n": len(ids)})

    trainer = train.DataParallelTrainer(
        loop, train_loop_config={"out": out_dir},
        scaling_config=ScalingConfig(num_workers=2),
        datasets={"train": ds})
    trainer.fit()
    union, sizes = [], []
    for rank in range(2):
        with open(f"{out_dir}/rank_{rank}.json") as f:
            ids = json.load(f)
        union.extend(ids)
        sizes.append(len(ids))
    assert sorted(union) == list(range(256))  # disjoint + complete
    assert all(s > 0 for s in sizes)  # both workers actually streamed


# ---------------------------------------------------------------------------
# readers: images + tfrecords


def test_read_images(ray, tmp_path):
    from PIL import Image

    for i in range(4):
        Image.fromarray(
            (np.full((8 + i, 8 + i, 3), i * 10, np.uint8))).save(
            tmp_path / f"img_{i}.png")
    (tmp_path / "notes.txt").write_text("ignored")
    ds = rd.read_images(str(tmp_path), size=(8, 8), include_paths=True)
    rows = ds.take_all()
    assert len(rows) == 4
    shapes = {r["image"].shape for r in rows}
    assert shapes == {(8, 8, 3)}
    assert sorted(r["path"].rsplit("/", 1)[-1] for r in rows) == [
        f"img_{i}.png" for i in range(4)]


def test_tfrecords_roundtrip(ray, tmp_path):
    """write_tfrecords -> read_tfrecords with the built-in Example codec
    (ints, floats, bytes; single- and multi-value features)."""
    ds = rd.from_items([
        {"i": int(i), "f": float(i) / 2, "s": f"row{i}".encode(),
         "vec": [float(i), float(i + 1)]}
        for i in range(20)
    ], parallelism=3)
    out = str(tmp_path / "tfr")
    import os
    os.makedirs(out, exist_ok=True)
    files = ds.write_tfrecords(out)
    assert len(files) == 3
    back = rd.read_tfrecords(out)
    rows = sorted(back.take_all(), key=lambda r: r["i"])
    assert [r["i"] for r in rows] == list(range(20))
    np.testing.assert_allclose([r["f"] for r in rows],
                               [i / 2 for i in range(20)], rtol=1e-6)
    assert rows[3]["s"] == b"row3"
    np.testing.assert_allclose(
        np.asarray(rows[5]["vec"], np.float64), [5.0, 6.0], rtol=1e-6)
