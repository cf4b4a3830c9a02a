"""Streaming training: Trainer.fit over a StreamingDataset consumes
batches lazily with bounded host memory — training over a folder larger
than host RAM (the role sc.binaryFiles streaming plays in the reference,
ImageSet.scala:80; VERDICT r2 #3)."""

import os
import tracemalloc

import numpy as np
import optax
import pytest

from analytics_zoo_tpu.data.dataset import Dataset, StreamingDataset


def _chunks(sizes, dim=4, label=True, log=None):
    rng = np.random.default_rng(0)
    start = 0
    for s in sizes:
        if log is not None:
            log.append(s)
        x = np.arange(start, start + s, dtype=np.float32)[:, None].repeat(
            dim, 1)
        y = rng.integers(0, 3, s).astype(np.int32) if label else None
        start += s
        yield (x, y) if label else x


def test_rebatching_preserves_order_and_sizes():
    ds = Dataset.from_batch_iterable(
        lambda: _chunks([5, 3, 8, 2, 6]), size=24)
    batches = list(ds.batches(6, drop_remainder=False))
    assert [len(b[0]) for b in batches] == [6, 6, 6, 6]
    got = np.concatenate([b[0] for b in batches])
    np.testing.assert_array_equal(got[:, 0], np.arange(24, dtype=np.float32))
    # drop_remainder drops the ragged tail
    ds2 = Dataset.from_batch_iterable(lambda: _chunks([5, 4]), size=9)
    assert [len(b[0]) for b in ds2.batches(4)] == [4, 4]


def test_windowed_shuffle_randomizes_order():
    """VERDICT r3 #10: fit(shuffle=True) on a from_batch_iterable stream
    must actually randomize order (windowed buffer), deterministically
    per (seed, epoch), while preserving the exact sample multiset."""
    ds = Dataset.from_batch_iterable(
        lambda: _chunks([7, 9, 8, 6, 10, 8]), size=48, shuffle_buffer=16)
    ordered = np.concatenate(
        [b[0][:, 0] for b in ds.batches(8, shuffle=False)])
    shuf1 = np.concatenate(
        [b[0][:, 0] for b in ds.batches(8, shuffle=True, seed=1, epoch=0)])
    shuf1b = np.concatenate(
        [b[0][:, 0] for b in ds.batches(8, shuffle=True, seed=1, epoch=0)])
    shuf2 = np.concatenate(
        [b[0][:, 0] for b in ds.batches(8, shuffle=True, seed=1, epoch=1)])
    assert not np.array_equal(shuf1, ordered), "shuffle was a no-op"
    np.testing.assert_array_equal(shuf1, shuf1b)   # deterministic
    assert not np.array_equal(shuf1, shuf2)        # varies per epoch
    # same multiset of samples — nothing lost or duplicated
    np.testing.assert_array_equal(np.sort(shuf1), np.sort(ordered))
    # labels stay paired with their rows: x rows encode their own index,
    # so re-running unshuffled and indexing y by shuffled x matches
    xs, ys = zip(*ds.batches(8, shuffle=True, seed=3, epoch=0))
    x_all = np.concatenate([x[:, 0] for x in xs]).astype(int)
    y_all = np.concatenate(ys)
    _, y_ref = zip(*ds.batches(8, shuffle=False))
    y_ref = np.concatenate(y_ref)
    np.testing.assert_array_equal(y_all, y_ref[x_all])


def test_windowed_shuffle_bounded_window():
    """The shuffle buffer must not materialize the stream: displacement
    from source order is bounded by ~one window."""
    n, window = 4000, 256
    ds = Dataset.from_batch_iterable(
        lambda: _chunks([40] * 100), size=n, shuffle_buffer=window)
    out = np.concatenate(
        [b[0][:, 0] for b in ds.batches(32, shuffle=True, seed=0)])
    displacement = np.abs(out - np.arange(len(out)))
    # a row can ride the carried tail into the next window: displacement
    # is bounded by ~2 windows (+ chunk slack), far below the stream size
    assert displacement.max() <= 2 * window + 80, displacement.max()
    # and it genuinely permutes within windows
    assert (displacement > 0).mean() > 0.9


def test_shuffle_buffer_none_replays_source_order():
    ds = Dataset.from_batch_iterable(
        lambda: _chunks([8, 8, 8]), size=24, shuffle_buffer=None)
    a = np.concatenate([b[0][:, 0] for b in ds.batches(8, shuffle=True)])
    np.testing.assert_array_equal(a, np.arange(24, dtype=np.float32))


def test_stream_is_pulled_lazily():
    """The source generator advances only as far as the consumer pulls —
    the stream is never materialized."""
    log = []
    ds = Dataset.from_batch_iterable(
        lambda: _chunks([8] * 100, log=log), size=800)
    it = ds.batches(16)
    next(it), next(it)
    # 2 batches of 16 need exactly 4 chunks of 8 (plus at most 1 lookahead)
    assert len(log) <= 5, log


def test_streaming_memory_bounded():
    """Iterating a ~47MB stream must not hold more than a few chunks of
    host memory at once."""
    chunk = 64 * 32 * 32 * 3 * 4  # ~786KB

    def make():
        rng = np.random.default_rng(0)
        for _ in range(60):
            yield (rng.normal(size=(64, 32, 32, 3)).astype(np.float32),
                   rng.integers(0, 4, 64).astype(np.int32))

    ds = Dataset.from_batch_iterable(make, size=60 * 64)
    tracemalloc.start()
    tracemalloc.reset_peak()
    n = sum(len(b[0]) for b in ds.batches(128, drop_remainder=False))
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert n == 3840
    if peak < chunk:  # numpy allocations not traced in this build
        pytest.skip("tracemalloc does not see numpy buffers here")
    assert peak < 12 * chunk, f"peak {peak / 1e6:.1f}MB for a streamed pass"


def test_streaming_lazy_map():
    ds = Dataset.from_batch_iterable(lambda: _chunks([4, 4]), size=8)
    doubled = ds.map(lambda b: (b[0] * 2, b[1]), batched=True)
    got = np.concatenate([b[0] for b in doubled.batches(4)])
    np.testing.assert_array_equal(got[:, 0], np.arange(8) * 2.0)
    per_sample = ds.map(lambda s: (s[0] + 1.0, s[1]), batched=False)
    got2 = np.concatenate([b[0] for b in per_sample.batches(4)])
    np.testing.assert_array_equal(got2[:, 0], np.arange(8) + 1.0)


def _write_image_folder(root, n_per_class=12, size=(10, 10)):
    from PIL import Image
    rng = np.random.default_rng(0)
    for cls in ("cat", "dog"):
        d = os.path.join(root, cls)
        os.makedirs(d, exist_ok=True)
        for i in range(n_per_class):
            arr = rng.integers(0, 255, size + (3,)).astype(np.uint8)
            Image.fromarray(arr).save(os.path.join(d, f"{i}.png"))


def test_image_loader_uint8_defers_normalization(tmp_path):
    """out_dtype='uint8' ships raw pixels (4x smaller host→device
    transfer); normalization belongs on-device, in the jitted step."""
    from analytics_zoo_tpu.data.image_loader import ImageLoader
    _write_image_folder(str(tmp_path), n_per_class=4)
    loader = ImageLoader.from_folder(str(tmp_path), batch_size=4,
                                     size=(10, 10), out_dtype="uint8")
    x, y = next(iter(loader))
    assert x.dtype == np.uint8
    assert x.shape == (4, 10, 10, 3)
    assert x.max() > 1  # raw pixel range, not normalized
    f32 = ImageLoader.from_folder(str(tmp_path), batch_size=4,
                                  size=(10, 10), scale=1 / 255.0)
    x2, _ = next(iter(f32))
    np.testing.assert_allclose(x.astype(np.float32) / 255.0, x2,
                               atol=1e-6)
    with pytest.raises(ValueError):
        ImageLoader([], out_dtype="float16")


def test_fit_streams_from_image_folder(tmp_path):
    """End-to-end: ImageLoader folder -> Dataset.from_loader ->
    Trainer.fit, nothing materialized, shuffled per epoch, loss finite."""
    from analytics_zoo_tpu.common.context import init_nncontext
    from analytics_zoo_tpu.data.image_loader import ImageLoader
    from analytics_zoo_tpu.train.trainer import Trainer
    from analytics_zoo_tpu.train import triggers
    from analytics_zoo_tpu.pipeline.api.keras import Sequential, objectives
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Convolution2D, Dense, Flatten)
    from analytics_zoo_tpu.pipeline.api.keras.metrics import Accuracy

    _write_image_folder(str(tmp_path))
    loader = ImageLoader.from_folder(
        str(tmp_path), batch_size=6, size=(10, 10), scale=1 / 255.0)
    ds = Dataset.from_loader(loader)
    assert ds.size == 24
    assert ds.steps_per_epoch(8) == 3

    ctx = init_nncontext(app_name="stream-test")
    m = Sequential()
    m.add(Convolution2D(4, 3, 3, input_shape=(10, 10, 3),
                        activation="relu"))
    m.add(Flatten())
    m.add(Dense(2))
    trainer = Trainer(m.to_graph(),
                      objectives.get("sparse_categorical_crossentropy"),
                      optax.sgd(0.01), metrics=[Accuracy()], mesh=ctx.mesh)
    hist = trainer.fit(ds, batch_size=8,
                       end_trigger=triggers.MaxEpoch(2))
    assert len(hist["loss"]) == 6  # 3 steps x 2 epochs
    assert np.isfinite(hist["loss"]).all()
    res = trainer.evaluate(ds, batch_size=8)
    assert "accuracy" in res and np.isfinite(res["loss"])
    preds = trainer.predict(ds, batch_size=8)
    assert preds.shape == (24, 2)
