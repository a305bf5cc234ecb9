"""ctypes binding + build-on-demand for the native data pipeline.

Where the reference is native, this framework is native too (SURVEY.md §2.3
build rule): the data-plane hot loops live in C++
(``data_pipeline.cpp``), compiled once on demand with the system toolchain
and loaded over ctypes — replacing the reference's JNA + libccaffe FFI
surface (reference: src/main/java/libs/CaffeLibrary.java:8-67,
libccaffe/ccaffe.h:5-69) for the parts that still belong on the host.  The
TPU compute path needs no FFI at all; everything here is batch-granular and
falls back to numpy when no compiler is available.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "data_pipeline.cpp")
_LIB_PATH = os.path.join(_HERE, "_build", "libsparknet_data.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_error: str | None = None


def _build() -> str | None:
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    if (os.path.exists(_LIB_PATH)
            and os.path.getmtime(_LIB_PATH) >= os.path.getmtime(_SRC)):
        return None
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC,
           "-ljpeg", "-lz", "-o", _LIB_PATH]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{type(e).__name__}: {e}"
    if proc.returncode != 0:
        return proc.stderr[-2000:]
    return None


def get_lib() -> ctypes.CDLL | None:
    """The loaded native library, building it on first use; None if the
    toolchain/libjpeg is unavailable (callers fall back to numpy)."""
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        err = _build()
        if err is not None:
            _build_error = err
            print(f"sparknet_tpu.native: build failed, using numpy fallback\n"
                  f"{err}", file=sys.stderr)
            return None
        lib = ctypes.CDLL(_LIB_PATH)
        i64, i32p, f32p, f64p, u8p = (
            ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
        )
        lib.sn_decode_cifar.argtypes = [u8p, i64, f32p, i32p]
        lib.sn_decode_cifar.restype = ctypes.c_int
        lib.sn_crop_batch_f32.argtypes = [
            f32p, i64, ctypes.c_int, ctypes.c_int, ctypes.c_int, f32p,
            ctypes.c_int, i32p, i32p, i32p, ctypes.c_void_p, i64]
        lib.sn_crop_batch_f32.restype = ctypes.c_int
        lib.sn_accumulate_mean.argtypes = [f32p, i64, i64, f64p]
        lib.sn_accumulate_mean.restype = ctypes.c_int
        lib.sn_decode_jpeg_resize.argtypes = [
            u8p, i64, ctypes.c_int, ctypes.c_int, f32p]
        lib.sn_decode_jpeg_resize.restype = ctypes.c_int
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        lib.sn_parse_datum_batch.argtypes = [
            u8p, i64p, i64p, i64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            f32p, i32p]
        lib.sn_parse_datum_batch.restype = ctypes.c_int
        u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
        lib.sn_crc32_rows.argtypes = [u8p, i64, u8p, i64, u32p, i64, u8p]
        lib.sn_crc32_rows.restype = i64
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


# ---------------------------------------------------------------------------
# numpy-signature wrappers (with automatic fallback)
# ---------------------------------------------------------------------------

def decode_cifar(records: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """records: uint8 [N, 3073] -> (images f32 [N,3,32,32], labels i32 [N])."""
    records = np.ascontiguousarray(records, np.uint8)
    n = records.shape[0]
    lib = get_lib()
    if lib is None:
        labels = records[:, 0].astype(np.int32)
        images = records[:, 1:].reshape(n, 3, 32, 32).astype(np.float32)
        return images, labels
    images = np.empty((n, 3, 32, 32), np.float32)
    labels = np.empty((n,), np.int32)
    rc = lib.sn_decode_cifar(records.reshape(-1), n, images.reshape(-1), labels)
    if rc != 0:
        raise RuntimeError(f"sn_decode_cifar failed: {rc}")
    return images, labels


def crop_batch(batch: np.ndarray, crop: int, ys: np.ndarray, xs: np.ndarray,
               flips: np.ndarray, mean: np.ndarray | float | None = None,
               out: np.ndarray | None = None) -> np.ndarray:
    """Crop+mirror+mean-subtract a f32 NCHW batch (ByteImage.cropInto,
    batched).  ``out``: optional preallocated (n, c, crop, crop) f32
    C-contiguous result buffer (e.g. from ``pipeline.BufferRing``) —
    shape/dtype mismatches fall back to a fresh allocation."""
    batch = np.ascontiguousarray(batch, np.float32)
    n, c, h, w = batch.shape
    ys = np.ascontiguousarray(ys, np.int32)
    xs = np.ascontiguousarray(xs, np.int32)
    flips = np.ascontiguousarray(flips, np.int32)
    if (out is None or out.shape != (n, c, crop, crop)
            or out.dtype != np.float32
            or not out.flags["C_CONTIGUOUS"]):
        out = np.empty((n, c, crop, crop), np.float32)
    mean_arr: np.ndarray | None = None
    if mean is not None:
        m = np.asarray(mean, np.float32)
        if m.ndim == 0:
            mean_arr = m.reshape(1)
        else:
            mean_arr = np.ascontiguousarray(
                np.broadcast_to(m, (c, crop, crop)), np.float32)
    lib = get_lib()
    if lib is None:
        for i in range(n):
            img = batch[i, :, ys[i]:ys[i] + crop, xs[i]:xs[i] + crop]
            out[i] = img[:, :, ::-1] if flips[i] else img
        if mean_arr is not None:
            out -= (mean_arr if mean_arr.size > 1 else mean_arr[0])
        return out
    mean_ptr = mean_arr.ctypes.data_as(ctypes.c_void_p) if mean_arr is not None else None
    rc = lib.sn_crop_batch_f32(
        batch.reshape(-1), n, c, h, w, out.reshape(-1), crop, ys, xs, flips,
        mean_ptr, 0 if mean_arr is None else mean_arr.size)
    if rc != 0:
        raise RuntimeError(f"sn_crop_batch_f32 failed: {rc}")
    return out


def accumulate_mean(images: np.ndarray, acc: np.ndarray) -> None:
    """Add per-pixel sums of a f32 [N, ...] batch into a float64 accumulator
    (ComputeMean partition sums)."""
    images = np.ascontiguousarray(images, np.float32)
    n = images.shape[0]
    plane = images.size // max(n, 1)
    if acc.size != plane or acc.dtype != np.float64:
        raise ValueError(
            f"accumulator mismatch: acc {acc.shape}/{acc.dtype}, "
            f"image plane has {plane} elements")
    lib = get_lib()
    if lib is None:
        acc += images.reshape(n, -1).sum(axis=0, dtype=np.float64).reshape(acc.shape)
        return
    rc = lib.sn_accumulate_mean(images.reshape(-1), n, plane, acc.reshape(-1))
    if rc != 0:
        raise RuntimeError(f"sn_accumulate_mean failed: {rc}")


def decode_jpeg_resize(data: bytes, out_h: int, out_w: int) -> np.ndarray | None:
    """JPEG bytes -> f32 [3, out_h, out_w] (force-resize, aspect ignored —
    ScaleAndConvert semantics); None for undecodable input (caller drops)."""
    lib = get_lib()
    if lib is None:
        try:
            from PIL import Image
            import io
            img = Image.open(io.BytesIO(data)).convert("RGB")
            img = img.resize((out_w, out_h), Image.BILINEAR)
            arr = np.asarray(img, np.float32)
            return np.ascontiguousarray(arr.transpose(2, 0, 1))
        except Exception:
            return None
    buf = np.frombuffer(data, np.uint8)
    out = np.empty((3, out_h, out_w), np.float32)
    rc = lib.sn_decode_jpeg_resize(buf, buf.size, out_h, out_w, out.reshape(-1))
    if rc != 0:
        return None
    return out


def parse_datum_batch(records: list[bytes], c: int, h: int, w: int,
                      ) -> tuple[np.ndarray, np.ndarray] | None:
    """Parse serialized Datum protos into (f32 [n,c,h,w], i32 labels) in
    one native pass (the data_reader + C++ protobuf role of the reference;
    reference: caffe/src/caffe/data_reader.cpp, protobuf parse in C++).
    Returns None when unavailable or when the batch has encoded/mismatched
    records — callers fall back to the per-record Python decoder."""
    lib = get_lib()
    if lib is None or not records:
        return None
    sizes = np.asarray([len(r) for r in records], np.int64)
    offsets = np.zeros(len(records), np.int64)
    np.cumsum(sizes[:-1], out=offsets[1:])
    buf = np.frombuffer(b"".join(records), np.uint8)
    out = np.empty((len(records), c, h, w), np.float32)
    labels = np.empty((len(records),), np.int32)
    rc = lib.sn_parse_datum_batch(buf, offsets, sizes, len(records),
                                  c, h, w, out.reshape(-1), labels)
    if rc != 0:
        return None
    return out, labels


def crc32_rows(rows: np.ndarray, tails: np.ndarray,
               want: np.ndarray) -> np.ndarray | None:
    """Which rows fail their checksum: zlib's crc32 of ``rows[i]``'s
    bytes chained over ``tails[i]``'s, against ``want[i]`` (uint32), for
    the ``n`` rows of two C-contiguous arrays — bool ``[n]``, in ONE call
    with the interpreter lock released, so pool threads check their runs
    of records side by side (``zlib.crc32`` a row takes the lock back a
    row).  None when the native library is unavailable: the caller loops
    over ``zlib.crc32``."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(rows)
    if len(tails) != n or len(want) != n:
        raise ValueError(f"crc32_rows: {n} rows, {len(tails)} tails, "
                         f"{len(want)} checksums")
    bad = np.zeros(n, np.uint8)
    if n:
        lib.sn_crc32_rows(rows.view(np.uint8), rows.nbytes // n,
                          tails.view(np.uint8), tails.nbytes // n,
                          want, n, bad)
    return bad.view(np.bool_)
