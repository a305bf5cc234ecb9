"""DB-backed host data feeds: Data (LMDB/LevelDB), ImageData, WindowData.

The host-side half of the reference's DB data path: a reader thread pulls
serialized ``Datum`` records from the DB cursor (reference:
caffe/src/caffe/data_reader.cpp:62-109), ``DataTransformer`` applies
scale/crop/mirror/mean (reference: caffe/src/caffe/data_transformer.cpp),
and batches flow to the device via the prefetch pipeline
(sparknet_tpu.data.prefetch).  These feeds produce exactly the batch dict
a ``Data``/``ImageData``/``WindowData`` graph input consumes, making zoo
``train_val.prototxt``s runnable standalone (`caffe train` style) when
the dataset exists — ``replace_data_layers`` remains the SparkNet-style
alternative that swaps these for externally-fed inputs.
"""

from __future__ import annotations

import os
import time
from typing import Any, Iterator

import numpy as np

from ..proto.caffe_pb import Phase
from ..proto.wireformat import WireError, decode
from ..utils import faults
from ..utils.retry import io_retry
from .integrity import DataCorruptionError, Quarantine, QuarantinePolicy


# ---------------------------------------------------------------------------
# DB openers
# ---------------------------------------------------------------------------

def open_db(source: str, backend: str = "LMDB"):
    """db.cpp GetDB analog: backend enum -> reader."""
    backend = str(backend).upper()
    if backend in ("LMDB", "1"):
        from .lmdb_io import LmdbReader
        return LmdbReader(source)
    if backend in ("LEVELDB", "0"):
        from .leveldb_io import LeveldbReader
        return LeveldbReader(source)
    raise ValueError(f"unknown DB backend {backend!r}")


def datum_to_array(datum_bytes: bytes, *, key: Any = None,
                   source: str | None = None) -> tuple[np.ndarray, int]:
    """Serialized Datum -> ((C,H,W) float32, label) (reference:
    data_transformer.cpp Transform(Datum) input handling).

    Every malformed input — truncated protobuf, a payload whose byte
    count contradicts channels×height×width, an undecodable encoded
    image — raises :class:`~sparknet_tpu.data.integrity.
    DataCorruptionError` carrying ``key``/``source`` attribution, never
    an opaque numpy reshape error from three frames down.  ``key`` and
    ``source`` are context-only (the DB key and DB path in the feed
    path)."""
    try:
        m = decode(datum_bytes, "Datum")
    except WireError as e:
        raise DataCorruptionError(
            f"undecodable Datum bytes ({len(datum_bytes)} bytes): {e}",
            source=source, key=key) from e
    c = int(m.get("channels", 1))
    h = int(m.get("height", 1))
    w = int(m.get("width", 1))
    label = int(m.get("label", 0))
    data = m.get("data")
    if m.get("encoded"):
        if h and w:
            from .. import native
            img = native.decode_jpeg_resize(bytes(data), h, w)
            if img is None:
                raise DataCorruptionError(
                    "undecodable encoded Datum", source=source, key=key)
            return img, label
        # natural size: decode without resize
        from io import BytesIO

        from PIL import Image
        try:
            im = Image.open(BytesIO(bytes(data))).convert("RGB")
        except Exception as e:
            raise DataCorruptionError(
                f"undecodable encoded Datum: {e}",
                source=source, key=key) from e
        arr = np.asarray(im, np.float32).transpose(2, 0, 1)
        return np.ascontiguousarray(arr), label
    if c <= 0 or h <= 0 or w <= 0:
        raise DataCorruptionError(
            f"impossible Datum geometry channels={c} height={h} width={w}",
            source=source, key=key)
    if data:
        raw = bytes(data)
        if len(raw) != c * h * w:
            raise DataCorruptionError(
                f"Datum payload is {len(raw)} bytes but "
                f"channels*height*width = {c}*{h}*{w} = {c * h * w}",
                source=source, key=key)
        arr = np.frombuffer(raw, np.uint8).astype(np.float32)
        return arr.reshape(c, h, w), label
    floats = [float(v) for v in m.get_all("float_data")]
    if len(floats) != c * h * w:
        raise DataCorruptionError(
            f"Datum float_data has {len(floats)} values but "
            f"channels*height*width = {c}*{h}*{w} = {c * h * w}",
            source=source, key=key)
    return np.asarray(floats, np.float32).reshape(c, h, w), label


def array_to_datum(img: np.ndarray, label: int = 0,
                   encoded: bytes | None = None) -> bytes:
    """(C,H,W) array (uint8 range) or raw encoded bytes -> serialized Datum
    (reference: util/io.cpp CVMatToDatum / ReadImageToDatum)."""
    from ..proto.textformat import PMessage
    from ..proto.wireformat import encode
    m = PMessage()
    if encoded is not None:
        m.add("channels", 3)
        m.add("height", 0)
        m.add("width", 0)
        m.add("data", encoded)
        m.add("encoded", True)
    else:
        c, h, w = img.shape
        m.add("channels", c)
        m.add("height", h)
        m.add("width", w)
        if img.dtype == np.uint8 or (
                img.min() >= 0 and img.max() <= 255
                and np.allclose(img, np.round(img))):
            m.add("data", np.ascontiguousarray(
                img, np.uint8).tobytes())
        else:
            for v in img.reshape(-1):
                m.add("float_data", float(v))
    m.add("label", int(label))
    return encode(m, "Datum")


# ---------------------------------------------------------------------------
# DataTransformer
# ---------------------------------------------------------------------------

class DataTransformer:
    """scale / mean (file or values) / crop / mirror, matching
    data_transformer.cpp Transform: train = random crop + random mirror,
    test = center crop, mean subtracted at the crop window."""

    def __init__(self, transform_param, phase: Phase, seed: int = 0):
        p = transform_param
        self.scale = float(p.get("scale", 1.0))
        self.crop = int(p.get("crop_size", 0))
        self.mirror = bool(p.get("mirror", False))
        self.phase = phase
        self.rng = np.random.default_rng(seed)
        self.mean: np.ndarray | float | None = None
        mean_file = p.get("mean_file")
        if mean_file is not None:
            from ..proto.caffemodel import load_mean_binaryproto
            self.mean = load_mean_binaryproto(str(mean_file))
        else:
            values = [float(v) for v in p.get_all("mean_value")]
            if values:
                self.mean = np.asarray(values, np.float32).reshape(-1, 1, 1)
        # reusable full-size f32 scratch for the batch mean-subtract
        # intermediate (consumed within batch() — it never escapes).
        # NOT thread-safe: batch() runs on the one feed/consumer thread;
        # the decode POOL parallelizes records, not transforms.
        self._scratch: np.ndarray | None = None

    def __call__(self, img: np.ndarray) -> np.ndarray:
        out = img.astype(np.float32)
        if self.mean is not None:
            out = out - self.mean  # full-size subtract == window subtract
        if self.crop:
            c, h, w = out.shape
            if self.phase == Phase.TRAIN:
                y = int(self.rng.integers(0, h - self.crop + 1))
                x = int(self.rng.integers(0, w - self.crop + 1))
            else:
                y, x = (h - self.crop) // 2, (w - self.crop) // 2
            out = out[:, y:y + self.crop, x:x + self.crop]
        if self.mirror and self.phase == Phase.TRAIN and self.rng.integers(2):
            out = out[:, :, ::-1]
        if self.scale != 1.0:
            out = out * self.scale
        return np.ascontiguousarray(out)

    def _sub_mean(self, x: np.ndarray) -> np.ndarray:
        """``x - mean`` into the reusable scratch buffer (no allocation
        in steady state).  The result aliases internal state — callers
        must consume it within the same ``batch()`` call."""
        if self._scratch is None or self._scratch.shape != x.shape:
            self._scratch = np.empty(x.shape, np.float32)
        np.subtract(x, self.mean, out=self._scratch)
        return self._scratch

    def batch(self, imgs: np.ndarray,
              out: np.ndarray | None = None) -> np.ndarray:
        """Vectorized transform of an [n, c, h, w] batch — one pass
        through the native crop/mirror kernel instead of n Python-level
        transforms (the batched half of the native data path).  This is
        the feed pipeline's PRIMARY transform: per-record paths stack raw
        decodes and come through here too.

        ``out``: optional preallocated result buffer (the caller owns the
        rotation/aliasing contract — see ``pipeline.BufferRing``); the
        mean-subtract intermediate reuses an internal scratch either way,
        so the steady state allocates nothing."""
        from . import transforms
        from .. import native
        x = np.asarray(imgs, np.float32)   # no copy when already f32
        n, _c, h, w = x.shape
        if self.crop:
            if self.mean is not None:
                # full-size subtract == window subtract; scratch is
                # consumed by the crop below, never escapes
                x = self._sub_mean(x)
            if self.phase == Phase.TRAIN:
                ys = self.rng.integers(0, h - self.crop + 1, size=n)
                xs = self.rng.integers(0, w - self.crop + 1, size=n)
            else:
                ys = np.full(n, (h - self.crop) // 2)
                xs = np.full(n, (w - self.crop) // 2)
            flips = (self.rng.integers(0, 2, size=n)
                     if self.mirror and self.phase == Phase.TRAIN
                     else np.zeros(n))
            res = native.crop_batch(x, self.crop, ys.astype(np.int32),
                                    xs.astype(np.int32),
                                    flips.astype(np.int32), out=out)
            if self.scale != 1.0:
                np.multiply(res, self.scale, out=res)
            return res
        owned = False   # does res own its memory (safe to mutate)?
        res = x
        if self.mean is not None:
            res = transforms.subtract_mean(x, self.mean, out=out)
            owned = True
        if self.mirror and self.phase == Phase.TRAIN:
            flips = self.rng.integers(0, 2, size=n).astype(bool)
            if not owned:
                res = transforms._take(out, x.shape)
                res[...] = x
                owned = True
            res[flips] = res[flips, :, :, ::-1]
        if self.scale != 1.0:
            if owned:
                np.multiply(res, self.scale, out=res)
            else:
                res = transforms.scale(x, self.scale, out=out)
                owned = True
        return np.ascontiguousarray(res)


# ---------------------------------------------------------------------------
# Feeds
# ---------------------------------------------------------------------------

def _cycle_items(reader):
    """Endless cursor with rewind-at-end (data_reader.cpp:100-106)."""
    while True:
        n = 0
        for kv in reader.items():
            yield kv
            n += 1
        if n == 0:
            raise ValueError("empty database")


def _is_records(source: str) -> bool:
    """True when ``source`` names pre-decoded record shards (lazy import:
    records.py imports pipeline/objectstore, db.py must stay cheap)."""
    from .records import is_records_source
    return is_records_source(source)


def db_feed(lp, phase: Phase, tops: list[str] | None = None,
            seed: int = 0, quarantine: Quarantine | None = None,
            workers: int | None = None, stats=None, buffers: int = 0,
            ) -> Iterator[dict[str, np.ndarray]]:
    """Batch stream for a ``Data`` layer (LMDB/LevelDB backed).  The fast
    path parses the whole batch's Datums in one native call; otherwise
    decode + integrity checks fan out over a ``pipeline.DecodePool`` of
    ``workers`` threads (default ``SPARKNET_FEED_WORKERS``; 0 = the
    serial reference path).  Either way the batch is transformed in ONE
    vectorized ``DataTransformer.batch`` pass — never per image.

    Determinism: records are PULLED serially on the consumer thread (DB
    cursor order, the fault injector's per-seq corruption coin, and the
    quarantine's epoch accounting are all pull-side), and pool results
    come back in submission order — so for a fixed seed the parallel
    stream is bit-identical to the serial one, including which records
    get quarantined and which replacement records are pulled.

    Every decoded record is validated (decode + geometry against the
    source's first record); a record that fails is routed through
    ``quarantine`` — skipped, counted per source, and replaced by the
    next record, under a bounded per-epoch budget (exceeding it raises
    ``QuarantineExceeded``).  The default quarantine takes its policy
    from the SPARKNET_QUARANTINE_FRACTION / _RECORDS env knobs (default:
    zero tolerance — detected corruption is attributed, not budgeted).
    Pass an explicit :class:`~sparknet_tpu.data.integrity.Quarantine` to
    set the policy in code and read ``quarantine.report()`` afterwards.

    ``stats``: optional ``pipeline.FeedStats`` receiving per-stage
    decode/transform seconds.  ``buffers``: > 0 rotates the batch output
    through that many preallocated buffers (``pipeline.BufferRing``) —
    opt-in, because a consumer that holds more than ``buffers - 1``
    batches concurrently would see them overwritten (a RECORDS source
    reads its next batch while this one is held: one buffer more).  At
    0, the default, a batch that anything still holds is never written
    again (a RECORDS source reads into its earlier arrays only once
    every reference to them is gone).

    A pre-decoded record-shard source (``backend: "RECORDS"``, a
    ``*.rec`` path, or a directory of them — written once by
    ``tools/convert.py``) delegates to ``records.records_feed``: same
    batch/transform/quarantine/determinism contract, no decode stage."""
    from .. import native
    from .pipeline import BufferRing, DecodePool
    p = lp.sub("data_param")
    source = str(p.get("source"))
    batch = int(p.get("batch_size", 1))
    backend = p.get("backend", "LEVELDB")
    if str(backend).upper() == "RECORDS" or _is_records(source):
        from .records import records_feed
        # yield from, not return: db_feed is a generator, and a bare
        # return here would end the stream before the first batch
        yield from records_feed(lp, phase, tops=tops, seed=seed,
                                quarantine=quarantine, workers=workers,
                                stats=stats, buffers=buffers)
        return
    reader = open_db(source, str(backend))
    tf = DataTransformer(lp.sub("transform_param"), phase, seed)
    tops = tops or list(lp.top) or ["data", "label"]
    cursor = _cycle_items(reader)
    epoch_size = len(reader)
    if quarantine is None:
        quarantine = Quarantine(QuarantinePolicy.from_env(),
                                epoch_size=epoch_size, source=source)
    # peek the first record for the batch-parse geometry
    first_img, _ = datum_to_array(reader.first()[1], source=source)
    c, h, w = first_img.shape
    use_native = True  # sticky: one -3/None verdict (e.g. encoded JPEG
    # records) disables the native attempt for this source — no point
    # paying the batch join + output allocation every batch forever
    injector = faults.get_injector()
    state = {"seq": 0}   # feed-lifetime record counter (epoch accounting
    # + the deterministic corrupt_record coin flip)
    ring = BufferRing(buffers) if buffers else None

    def pull() -> tuple[Any, bytes, bool]:
        """(key, value, injected) for the next record; rolls the
        quarantine's epoch budget at each full pass over the source."""
        key, val = next(cursor)
        seq = state["seq"]
        state["seq"] += 1
        if seq and seq % epoch_size == 0:
            quarantine.start_epoch()
        if injector.corrupt_record(seq):
            return key, faults.corrupt_bytes(val, seq), True
        return key, val, False

    def decode_one(kv) -> tuple[np.ndarray, int]:
        """Decode + geometry-validate one record (runs on pool workers);
        corruption raises DataCorruptionError, re-raised by the pool at
        this record's ordinal — quarantine admission happens on the
        consumer side, in pull order."""
        key, val = kv
        img, label = datum_to_array(val, key=key, source=source)
        if img.shape != (c, h, w):
            raise DataCorruptionError(
                f"record shape {img.shape} != source geometry "
                f"({c}, {h}, {w})", source=source, key=key)
        return img, label

    # window >= batch: the feed submits a whole batch before collecting,
    # so a smaller window would deadlock the consumer on its own
    # backpressure (replacement pulls add at most one in-flight record)
    pool = DecodePool(decode_one, workers=workers, name=f"db:{source}",
                      stats=stats, stage="decode", window=batch + 2)

    def transform(imgs) -> np.ndarray:
        t0 = time.perf_counter() if stats is not None else 0.0
        if isinstance(imgs, list):
            imgs = np.stack(imgs)
        n = imgs.shape[0]
        shape = (n, c, tf.crop, tf.crop) if tf.crop else (n, c, h, w)
        data = tf.batch(imgs, out=ring.take(shape) if ring else None)
        if stats is not None:
            stats.note("transform", time.perf_counter() - t0)
            stats.count_batch(n)
        return data

    def collect_one(imgs_l: list, labels_l: list) -> None:
        """Consume the pool's next result in order; a corrupt record is
        admitted to the quarantine (pull order preserved) and simply not
        appended — the caller pulls a replacement."""
        try:
            img, label = pool.result()
        except DataCorruptionError as e:
            quarantine.admit(e)   # raises QuarantineExceeded past budget
            return
        imgs_l.append(img)
        labels_l.append(label)

    try:
        while True:
            records = [pull() for _ in range(batch)]
            # injected-corrupt records take the per-record path so the
            # quarantine sees them; a clean batch keeps the native fast
            # path (one C call: parse + stack, GIL released)
            parsed = None
            if use_native and not any(inj for _, _, inj in records):
                if stats is not None:
                    with stats.timed("decode"):
                        parsed = native.parse_datum_batch(
                            [val for _, val, _ in records], c, h, w)
                else:
                    parsed = native.parse_datum_batch(
                        [val for _, val, _ in records], c, h, w)
                if parsed is None:
                    use_native = False
            if parsed is not None:
                imgs, labels = parsed
                out = {tops[0]: transform(imgs)}
                if len(tops) > 1:
                    out[tops[1]] = labels.astype(np.float32)
                yield out
                continue
            # per-record path: decode fans out over the pool; results and
            # quarantine admissions stay in pull order
            for key, val, _ in records:
                pool.submit((key, val))
            imgs_l: list[np.ndarray] = []
            labels_l: list[int] = []
            for _ in range(batch):
                collect_one(imgs_l, labels_l)
            while len(imgs_l) < batch:   # replace quarantined records
                key, val, _ = pull()
                pool.submit((key, val))
                collect_one(imgs_l, labels_l)
            out = {tops[0]: transform(imgs_l)}
            if len(tops) > 1:
                out[tops[1]] = np.asarray(labels_l, np.float32)
            yield out
    finally:
        pool.close()


def image_data_feed(lp, phase: Phase, seed: int = 0
                    ) -> Iterator[dict[str, np.ndarray]]:
    """Batch stream for an ``ImageData`` layer (reference:
    caffe/src/caffe/layers/image_data_layer.cpp): a ``source`` list file of
    "path label" lines, optional force-resize to new_height×new_width,
    shuffle, then DataTransformer."""
    p = lp.sub("image_data_param")
    entries = read_image_list(str(p.get("source")),
                              str(p.get("root_folder", "")))
    batch = int(p.get("batch_size", 1))
    new_h = int(p.get("new_height", 0))
    new_w = int(p.get("new_width", 0))
    color = bool(p.get("is_color", True))
    shuffle = bool(p.get("shuffle", False))
    tf = DataTransformer(lp.sub("transform_param"), phase, seed)
    rng = np.random.default_rng(seed)
    tops = list(lp.top) or ["data", "label"]
    order = np.arange(len(entries))
    if shuffle:
        rng.shuffle(order)
    pos = 0
    while True:
        imgs, labels = [], []
        for _ in range(batch):
            # wrap mid-batch like lines_id_ in image_data_layer.cpp
            # (re-shuffling at each epoch boundary when shuffle is set)
            if pos >= len(order):
                pos = 0
                if shuffle:
                    rng.shuffle(order)
            path, label = entries[order[pos]]
            pos += 1
            imgs.append(tf(load_image(path, new_h, new_w, color)))
            labels.append(label)
        yield _pack(tops, imgs, labels)


def window_data_feed(lp, phase: Phase, seed: int = 0
                     ) -> Iterator[dict[str, np.ndarray]]:
    """Batch stream for a ``WindowData`` layer (reference:
    caffe/src/caffe/layers/window_data_layer.cpp): foreground/background
    window sampling at fg_fraction, crop + warp each window to crop_size,
    context padding, mean subtraction at the window."""
    p = lp.sub("window_data_param")
    fg_threshold = float(p.get("fg_threshold", 0.5))
    bg_threshold = float(p.get("bg_threshold", 0.5))
    images, fg, bg = read_window_file(str(p.get("source")),
                                      fg_threshold, bg_threshold)
    if not fg and not bg:
        raise ValueError(
            f"WindowData layer {lp.name!r}: no sampleable windows — every "
            f"window overlap falls in [{bg_threshold}, {fg_threshold}) "
            f"(fg_threshold={fg_threshold}, bg_threshold={bg_threshold})")
    batch = int(p.get("batch_size", 1))
    fg_frac = float(p.get("fg_fraction", 0.25))
    context_pad = int(p.get("context_pad", 0))
    tf_param = lp.sub("transform_param")
    crop = int(tf_param.get("crop_size", 0)) or 227
    mirror = bool(tf_param.get("mirror", False))
    scale = float(tf_param.get("scale", 1.0))
    mean_values = [float(v) for v in tf_param.get_all("mean_value")]
    mean = (np.asarray(mean_values, np.float32).reshape(-1, 1, 1)
            if mean_values else None)
    use_square = str(p.get("crop_mode", "warp")) == "square"
    rng = np.random.default_rng(seed)
    tops = list(lp.top) or ["data", "label"]
    n_fg = int(round(batch * fg_frac))
    cache: dict[int, np.ndarray] = {}

    def get_image(img_idx: int) -> np.ndarray:
        if img_idx not in cache:
            if len(cache) > 32:
                cache.clear()
            path = images[img_idx][0]
            cache[img_idx] = load_image(path, 0, 0, True)
        return cache[img_idx]

    def sample(pool):
        return pool[int(rng.integers(0, len(pool)))]

    while True:
        imgs, labels = [], []
        for i in range(batch):
            use_fg = bool(fg) and (i < n_fg or not bg)
            win = sample(fg if use_fg else bg)
            img_idx, label, _ov, x1, y1, x2, y2 = win
            img = get_image(img_idx)
            do_mirror = bool(mirror and phase == Phase.TRAIN
                             and rng.integers(2))
            imgs.append(_crop_warp_window(
                img, x1, y1, x2, y2, crop, context_pad, use_square,
                do_mirror, mean, scale))
            labels.append(0 if not use_fg else label)
        yield _pack(tops, imgs, labels)


def _crop_warp_window(img: np.ndarray, x1: int, y1: int, x2: int, y2: int,
                      crop: int, context_pad: int, use_square: bool,
                      do_mirror: bool, mean: np.ndarray | None,
                      scale: float) -> np.ndarray:
    """The exact window crop of window_data_layer.cpp:300-420: expand the
    region by context_scale = crop/(crop - 2·context_pad) around its center
    (squared first in "square" crop_mode), clip to the image, warp the
    clipped part by the *unclipped* scale factors, and paste it at the pad
    offset into a zeroed crop×crop buffer (the prefetch buffer is zero-
    filled, so out-of-image context stays 0 after mean subtraction)."""
    c, rows, cols = img.shape
    pad_w = pad_h = 0
    crop_w = crop_h = crop
    if context_pad > 0 or use_square:
        if 2 * context_pad >= crop:
            raise ValueError(
                f"context_pad {context_pad} must be less than half the "
                f"net input size {crop} (window_data_layer.cpp context "
                f"scale would invert)")
        context_scale = crop / (crop - 2.0 * context_pad)
        half_h = (y2 - y1 + 1) / 2.0
        half_w = (x2 - x1 + 1) / 2.0
        cx, cy = x1 + half_w, y1 + half_h
        if use_square:
            half_h = half_w = max(half_h, half_w)
        x1 = int(round(cx - half_w * context_scale))
        x2 = int(round(cx + half_w * context_scale))
        y1 = int(round(cy - half_h * context_scale))
        y2 = int(round(cy + half_h * context_scale))
        unclipped_h, unclipped_w = y2 - y1 + 1, x2 - x1 + 1
        pad_x1, pad_y1 = max(0, -x1), max(0, -y1)
        pad_x2 = max(0, x2 - cols + 1)
        pad_y2 = max(0, y2 - rows + 1)
        x1, x2 = x1 + pad_x1, x2 - pad_x2
        y1, y2 = y1 + pad_y1, y2 - pad_y2
        clipped_h, clipped_w = y2 - y1 + 1, x2 - x1 + 1
        scale_x, scale_y = crop / unclipped_w, crop / unclipped_h
        crop_w = int(round(clipped_w * scale_x))
        crop_h = int(round(clipped_h * scale_y))
        pad_x1 = int(round(pad_x1 * scale_x))
        pad_x2 = int(round(pad_x2 * scale_x))
        pad_y1 = int(round(pad_y1 * scale_y))
        pad_h = pad_y1
        pad_w = pad_x2 if do_mirror else pad_x1  # mirrored padding
        crop_h = min(crop_h, crop - pad_h)
        crop_w = min(crop_w, crop - pad_w)
    window = img[:, y1:y2 + 1, x1:x2 + 1]
    warped = _warp(window, crop_h, crop_w)
    if do_mirror:
        warped = warped[:, :, ::-1]
    if mean is not None:
        warped = warped - mean
    out = np.zeros((c, crop, crop), np.float32)
    out[:, pad_h:pad_h + crop_h, pad_w:pad_w + crop_w] = warped * scale
    return out


def feed_for_layer(lp, phase: Phase, seed: int = 0):
    """Dispatch a data-layer LayerParameter to its host feed — the analog
    of LayerRegistry creating the right data layer (layer_factory.hpp)."""
    if lp.type == "Data":
        return db_feed(lp, phase, seed=seed)
    if lp.type == "ImageData":
        return image_data_feed(lp, phase, seed=seed)
    if lp.type == "WindowData":
        return window_data_feed(lp, phase, seed=seed)
    if lp.type == "HDF5Data":
        from .hdf5 import hdf5_feed
        p = lp.sub("hdf5_data_param")
        return hdf5_feed(str(p.get("source")), list(lp.top),
                         int(p.get("batch_size", 1)),
                         shuffle=bool(p.get("shuffle", False)), seed=seed)
    raise ValueError(f"layer {lp.name!r} ({lp.type}) has no host feed")


_FEEDABLE_TYPES = ("Data", "ImageData", "WindowData", "HDF5Data")


def feed_for_net(net_param, phase: Phase, seed: int = 0):
    """Feed for the first self-sourcing data layer active in ``phase``
    (the standalone `caffe train` data path)."""
    from ..proto.caffe_pb import NetState
    for lp in net_param.filtered(NetState(phase)).layer:
        if lp.type in _FEEDABLE_TYPES:
            return feed_for_layer(lp, phase, seed=seed)
    raise ValueError(
        f"net has no DB/file-backed data layer for phase {phase}; feed it "
        "explicitly (set_train_data/set_test_data)")


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def _pack(tops, imgs, labels) -> dict[str, np.ndarray]:
    # asarray, not astype: the stack is already f32 when its inputs are
    # (the common case) — no second whole-batch copy
    out = {tops[0]: np.asarray(np.stack(imgs), np.float32)}
    if len(tops) > 1:
        out[tops[1]] = np.asarray(labels, np.float32)
    return out


def read_image_list(source: str, root: str = "") -> list[tuple[str, int]]:
    entries = []
    with open(source) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            # any whitespace separates path and label (Caffe reads them
            # with istringstream >> extraction)
            path, label = line.rsplit(None, 1)
            entries.append((os.path.join(root, path), int(label)))
    if not entries:
        raise ValueError(f"{source}: empty image list")
    return entries


def load_image(path: str, new_h: int, new_w: int, color: bool) -> np.ndarray:
    """Decode an image file to (C,H,W) float32 0-255; JPEG goes through
    the native libjpeg path (ScaleAndConvert.convertImage force-resize
    semantics), everything else through PIL.  The read retries transient
    I/O errors at record granularity (SPARKNET_IO_RETRIES/_BACKOFF) — one
    NFS blip costs one backoff, not the epoch."""

    def read() -> bytes:
        with open(path, "rb") as f:
            return f.read()

    raw = io_retry(read, describe=f"read {path}")
    if raw[:2] == b"\xff\xd8" and new_h and new_w:
        from .. import native
        img = native.decode_jpeg_resize(raw, new_h, new_w)
        if img is not None:
            return img if color else img.mean(0, keepdims=True)
    from io import BytesIO

    from PIL import Image
    im = Image.open(BytesIO(raw))
    im = im.convert("RGB" if color else "L")
    if new_h and new_w:
        im = im.resize((new_w, new_h), Image.BILINEAR)
    arr = np.asarray(im, np.float32)
    if arr.ndim == 2:
        arr = arr[None]
    else:
        arr = arr.transpose(2, 0, 1)
    return arr


def _warp(window: np.ndarray, out_h: int, out_w: int) -> np.ndarray:
    """Bilinear force-resize (the warp of window_data_layer.cpp)."""
    c, h, w = window.shape
    if h == out_h and w == out_w:
        return window.astype(np.float32)
    ys = np.linspace(0, h - 1, out_h)
    xs = np.linspace(0, w - 1, out_w)
    y0 = np.floor(ys).astype(int)
    x0 = np.floor(xs).astype(int)
    y1 = np.minimum(y0 + 1, h - 1)
    x1 = np.minimum(x0 + 1, w - 1)
    wy = (ys - y0)[None, :, None]
    wx = (xs - x0)[None, None, :]
    p00 = window[:, y0][:, :, x0]
    p01 = window[:, y0][:, :, x1]
    p10 = window[:, y1][:, :, x0]
    p11 = window[:, y1][:, :, x1]
    return ((1 - wy) * ((1 - wx) * p00 + wx * p01)
            + wy * ((1 - wx) * p10 + wx * p11)).astype(np.float32)


def read_window_file(source: str, fg_threshold: float, bg_threshold: float):
    """Parse the R-CNN window file format (window_data_layer.cpp:71-132):
    repeated blocks of:
        # <image_index>
        <image_path>
        <channels> <height> <width>
        <num_windows>
        <label> <overlap> <x1> <y1> <x2> <y2>   (× num_windows)
    Returns (images, fg_windows, bg_windows) with windows as
    (image_idx, label, overlap, x1, y1, x2, y2)."""
    images: list[tuple[str, tuple[int, int, int]]] = []
    fg, bg = [], []
    with open(source) as f:
        lines = [ln.strip() for ln in f if ln.strip()]
    i = 0
    while i < len(lines):
        if not lines[i].startswith("#"):
            raise ValueError(f"{source}: expected '# index' at line {i}")
        path = lines[i + 1]
        c, h, w = (int(v) for v in lines[i + 2].split())
        num = int(lines[i + 3])
        img_idx = len(images)
        images.append((path, (c, h, w)))
        i += 4
        for _ in range(num):
            parts = lines[i].split()
            i += 1
            label, overlap = int(parts[0]), float(parts[1])
            x1, y1, x2, y2 = (int(v) for v in parts[2:6])
            win = (img_idx, label, overlap, x1, y1, x2, y2)
            if overlap >= fg_threshold:
                fg.append(win)
            elif overlap < bg_threshold:
                bg.append(win)
    return images, fg, bg
