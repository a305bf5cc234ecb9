"""Native data-pipeline tests: C++ path vs numpy fallback equivalence —
the CallbackBenchmarkSpec territory (reference:
src/test/scala/apps/CallbackBenchmarkSpec.scala measured the JNA feed path
this module replaces)."""

import io

import numpy as np
import pytest

from sparknet_tpu import native


def test_builds():
    assert native.available(), "native pipeline failed to build"


def test_decode_cifar_matches_numpy(np_rng):
    recs = np_rng.integers(0, 256, size=(5, 3073)).astype(np.uint8)
    images, labels = native.decode_cifar(recs)
    np.testing.assert_array_equal(labels, recs[:, 0].astype(np.int32))
    np.testing.assert_array_equal(
        images, recs[:, 1:].reshape(5, 3, 32, 32).astype(np.float32))


def test_crop_batch_matches_numpy(np_rng):
    batch = np_rng.normal(size=(6, 3, 12, 12)).astype(np.float32)
    ys = np_rng.integers(0, 5, size=6)
    xs = np_rng.integers(0, 5, size=6)
    flips = np_rng.integers(0, 2, size=6)
    mean = np_rng.normal(size=(3, 8, 8)).astype(np.float32)
    out = native.crop_batch(batch, 8, ys, xs, flips, mean)
    for i in range(6):
        ref = batch[i, :, ys[i]:ys[i] + 8, xs[i]:xs[i] + 8]
        if flips[i]:
            ref = ref[:, :, ::-1]
        np.testing.assert_allclose(out[i], ref - mean, rtol=1e-6)


@pytest.mark.parametrize("shape", [(7, 3, 5, 4), (1, 1, 1, 1), (0, 2, 2, 2),
                                   (33, 1, 64, 96)])
def test_crc32_rows_matches_zlib(np_rng, shape):
    """One native call over a run of rows == ``zlib.crc32`` a row, the
    tail's chained over the row's: a flipped bit in either, or in the
    table, flags that row and no other."""
    import zlib
    n = shape[0]
    rows = np_rng.integers(0, 256, size=shape).astype(np.uint8)
    tails = np_rng.integers(-5, 1000, size=n).astype("<i8")
    want = np.asarray(
        [zlib.crc32(tails[i:i + 1], zlib.crc32(rows[i])) & 0xFFFFFFFF
         for i in range(n)], np.uint32)
    assert not native.crc32_rows(rows, tails, want).any()
    if n < 3:
        return
    rows[1, 0, -1, -1] ^= 0x10
    tails[n - 1] += 1
    want[n // 2] ^= 1
    flagged = native.crc32_rows(rows, tails, want)
    assert flagged.dtype == np.bool_
    assert set(np.flatnonzero(flagged)) == {1, n // 2, n - 1}
    # a view into a larger array, as the feed passes its runs
    assert np.array_equal(
        native.crc32_rows(rows[2:n - 1], tails[2:n - 1], want[2:n - 1]),
        flagged[2:n - 1])
    with pytest.raises(ValueError):
        native.crc32_rows(rows, tails[1:], want)


def test_crop_batch_scalar_mean(np_rng):
    batch = np.ones((2, 1, 4, 4), np.float32) * 10
    out = native.crop_batch(batch, 2, np.zeros(2, np.int32),
                            np.zeros(2, np.int32), np.zeros(2, np.int32),
                            mean=3.0)
    np.testing.assert_allclose(out, np.full((2, 1, 2, 2), 7.0))


def test_crop_batch_out_of_bounds(np_rng):
    batch = np.zeros((1, 1, 4, 4), np.float32)
    with pytest.raises(RuntimeError):
        native.crop_batch(batch, 3, np.array([2], np.int32),
                          np.array([0], np.int32), np.array([0], np.int32))


def test_accumulate_mean(np_rng):
    imgs = np_rng.normal(size=(10, 3, 4, 4)).astype(np.float32)
    acc = np.zeros((3, 4, 4), np.float64)
    native.accumulate_mean(imgs, acc)
    np.testing.assert_allclose(acc, imgs.sum(axis=0), rtol=1e-5)


def _jpeg_bytes(arr: np.ndarray) -> bytes:
    from PIL import Image
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", quality=95)
    return buf.getvalue()


def test_decode_jpeg_resize(np_rng):
    src = np.zeros((40, 60, 3), np.uint8)
    src[:, :30] = [255, 0, 0]
    src[:, 30:] = [0, 0, 255]
    out = native.decode_jpeg_resize(_jpeg_bytes(src), 20, 20)
    assert out is not None and out.shape == (3, 20, 20)
    # left half red-ish, right half blue-ish
    assert out[0, :, :8].mean() > 180 and out[2, :, :8].mean() < 80
    assert out[2, :, 12:].mean() > 180 and out[0, :, 12:].mean() < 80


def test_decode_jpeg_garbage_returns_none():
    assert native.decode_jpeg_resize(b"not a jpeg at all", 8, 8) is None
    assert native.decode_jpeg_resize(b"\xff\xd8\xff\xe0truncated", 8, 8) is None


def test_parse_datum_batch_matches_python():
    """Native batched Datum parse == per-record Python decode (u8 and
    float_data payloads), with clean fallback on mismatched shapes."""
    import numpy as np

    from sparknet_tpu import native
    from sparknet_tpu.data.db import array_to_datum, datum_to_array

    if not native.available():
        import pytest
        pytest.skip("no native toolchain")
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, size=(6, 3, 5, 4)).astype(np.uint8)
    labels = rng.integers(0, 9, size=6)
    recs = [array_to_datum(imgs[i], int(labels[i])) for i in range(6)]
    out, labs = native.parse_datum_batch(recs, 3, 5, 4)
    for i, r in enumerate(recs):
        ref_img, ref_lab = datum_to_array(r)
        np.testing.assert_array_equal(out[i], ref_img)
        assert labs[i] == ref_lab

    f = rng.normal(size=(2, 1, 2, 2)).astype(np.float32)
    frecs = [array_to_datum(f[i], i) for i in range(2)]
    fout, _ = native.parse_datum_batch(frecs, 1, 2, 2)
    np.testing.assert_allclose(fout, f, rtol=1e-6)

    assert native.parse_datum_batch(recs, 3, 9, 9) is None  # shape mismatch
