"""Device-side data augmentation: crop / mirror / mean-subtract / scale
inside the compiled train step.

``DeviceFeed.device_cast`` already proved the transfer half of the feed
win — shipping uint8 over PCIe and casting on device cuts host→HBM bytes
4×.  This module removes the host TRANSFORM stage too: the host ships
raw uint8 record blocks untouched (``records_feed(raw=True)`` /
``db_feed`` without a transform), and Caffe's DataTransformer semantics
(data_transformer.cpp: cast → full-size mean subtract → random/center
crop → random mirror → scale) run as traced XLA ops on the batch already
resident in HBM, vs a host stage that was costing more than the matmuls
it fed.

Exact replay is non-negotiable (the audit plane diffs losses bitwise),
so all randomness draws from the TRACED rng key via ``jax.random``
(threefry is counter-based — the same key yields the same offsets on
CPU, TPU, eager, and jit), and the op order matches the host
``DataTransformer`` exactly.  ``transforms.augment_batch_host`` is the
independent numpy implementation of the same spec used as the bit-parity
oracle: cast, subtract, slice, flip, and multiply are all IEEE-exact in
both f32 implementations, so device-augmented training must reproduce
host-augmented losses bit for bit at the same seed
(``Solver.set_augment(device=True/False)``, tested in
tests/test_records.py).

How crop and mirror lower (PR 31).  The feed ships uint8, and for a batch
of integers of at most 8 bits the crop and the mirror are one SELECTION
pass on the MXU (:func:`crop_mirror`): two batched products with one-hot
matrices, rows ``R[n,r,y] = (y == ys[n]+r)`` and columns ``S[n,x,j] =
(x == xs[n] + (k-1-j if flips[n] else j))`` with the mirror folded into
the column index, in bfloat16 with float32 accumulation.  Each output
element is the sum of exactly one non-zero product ``1 * v``, and every
uint8 value is exact in bfloat16, so the result is bit-identical to a
slice: the same mathematics on another unit, not a lower precision.  The
mean then applies to the cropped float32 result, at each sample's window
(the same IEEE subtraction on the same two values as a full-size subtract
before the crop).  A float batch, whose values bfloat16 does not hold,
keeps the general path: ``lax.dynamic_slice`` under ``vmap`` and a
``where`` over the reversed batch.  On the TPU that path is what the
chip showed it to be (PERF.md, PR 22-31): a gather fusion a sample, a
reverse of the whole batch and a select, a fifth of CaffeNet's step; it
does not "fuse with the first conv".  Which path a trace took is counted
in ``augment_lowering_total{path=select|gather}``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import telemetry


class AugmentSpec(NamedTuple):
    """The transform_param subset that augmentation folds on device.
    ``mean`` is a broadcastable f32 array ((c,1,1) per-channel values or
    a full (c,h,w) mean image, subtracted at each sample's window as
    Caffe's subtract-before-crop does) or None.  ``train`` selects
    random crop+mirror vs deterministic center crop."""

    crop: int = 0
    mirror: bool = False
    mean: np.ndarray | None = None
    scale: float = 1.0
    train: bool = True

    @classmethod
    def from_transform_param(cls, transform_param, phase) -> "AugmentSpec":
        """Build from a LayerParameter ``transform_param`` sub-message —
        the same fields ``db.DataTransformer`` reads, so host and device
        paths are configured from one prototxt source of truth."""
        from ..proto.caffe_pb import Phase
        p = transform_param
        mean = None
        mean_file = p.get("mean_file")
        if mean_file is not None:
            from ..proto.caffemodel import load_mean_binaryproto
            mean = np.asarray(load_mean_binaryproto(str(mean_file)),
                              np.float32)
        else:
            if hasattr(p, "get_all"):      # PMessage sub-message
                mv = p.get_all("mean_value")
            else:                          # plain-dict transform_param
                mv = p.get("mean_value") or []
                if not isinstance(mv, (list, tuple)):
                    mv = [mv]
            values = [float(v) for v in mv]
            if values:
                mean = np.asarray(values, np.float32).reshape(-1, 1, 1)
        return cls(crop=int(p.get("crop_size", 0)),
                   mirror=bool(p.get("mirror", False)),
                   mean=mean, scale=float(p.get("scale", 1.0)),
                   train=(phase == Phase.TRAIN))


def draw_offsets(key, n: int, h: int, w: int, spec: AugmentSpec):
    """(ys, xs, flips) int32 draws for a batch of n images — the ONE
    place augmentation randomness is sampled, shared verbatim by the
    device (:func:`apply`) and host (``transforms.augment_batch_host``)
    paths so their streams cannot diverge.  Test phase: center offsets,
    zero flips, no draws consumed."""
    if spec.crop and spec.train:
        ky, kx, kf = jax.random.split(key, 3)
        ys = jax.random.randint(ky, (n,), 0, h - spec.crop + 1,
                                dtype=jnp.int32)
        xs = jax.random.randint(kx, (n,), 0, w - spec.crop + 1,
                                dtype=jnp.int32)
    elif spec.crop:
        ys = jnp.full((n,), (h - spec.crop) // 2, jnp.int32)
        xs = jnp.full((n,), (w - spec.crop) // 2, jnp.int32)
    else:
        ys = xs = jnp.zeros((n,), jnp.int32)
    if spec.mirror and spec.train:
        kf = jax.random.split(key, 3)[2] if spec.crop else key
        flips = jax.random.randint(kf, (n,), 0, 2, dtype=jnp.int32)
    else:
        flips = jnp.zeros((n,), jnp.int32)
    return ys, xs, flips


def _select(imgs, ys, xs, flips, kh: int, kw: int):
    """Crop and mirror as two one-hot products: exact for values that
    bfloat16 holds (integers of at most 8 bits)."""
    _n, _c, h, w = imgs.shape
    x = imgs.astype(jnp.bfloat16)
    if kw != w or flips is not None:
        j = jnp.arange(kw, dtype=jnp.int32)
        if flips is not None:
            j = jnp.where(flips[:, None], kw - 1 - j, j)
        cols = (xs[:, None] + j)[:, None, :]                    # [n,1,kw]
        sel = jnp.arange(w, dtype=jnp.int32)[None, :, None] == cols
        x = jnp.einsum("ncyx,nxj->ncyj", x, sel.astype(jnp.bfloat16),
                       preferred_element_type=jnp.bfloat16)
    if kh != h:
        rows = ys[:, None, None] + jnp.arange(
            kh, dtype=jnp.int32)[None, :, None]                 # [n,kh,1]
        sel = jnp.arange(h, dtype=jnp.int32)[None, None, :] == rows
        x = jnp.einsum("nry,ncyj->ncrj", sel.astype(jnp.bfloat16), x,
                       preferred_element_type=jnp.float32)
    return x.astype(jnp.float32)


def _gather(imgs, ys, xs, flips, kh: int, kw: int):
    """Crop and mirror of any float batch: a dynamic slice a sample and
    a ``where`` over the reversed result."""
    c = imgs.shape[1]
    x = jax.vmap(lambda img, y, xo: jax.lax.dynamic_slice(
        img, (0, y, xo), (c, kh, kw)))(imgs.astype(jnp.float32), ys, xs)
    if flips is not None:
        x = jnp.where(flips[:, None, None, None], x[..., ::-1], x)
    return x


def crop_mirror(imgs, ys, xs, flips, crop: int):
    """The one crop-and-mirror: ``imgs`` [n,c,h,w] at offsets ``ys``,
    ``xs`` to [n,c,crop,crop] float32, mirrored where ``flips`` (bool or
    0/1; None for no mirror) says.  ``crop`` 0 keeps the size.  Offsets
    lie in ``[0, h - crop]`` (the callers draw them there): outside it
    the slice would clamp and the selection read zeros.  The lowering
    follows what the batch shows: integers of at most 8 bits take the
    selection, anything else the gather (module docstring); the choice
    is made while tracing and counted there."""
    h, w = imgs.shape[-2:]
    kh, kw = (crop, crop) if crop else (h, w)
    if flips is not None:
        flips = flips.astype(bool)
    select = (jnp.issubdtype(imgs.dtype, jnp.integer)
              and imgs.dtype.itemsize == 1)
    telemetry.get_registry().counter(
        "augment_lowering_total",
        "traces of the on-device crop and mirror, by lowering").inc(
            path="select" if select else "gather")
    return (_select if select else _gather)(imgs, ys, xs, flips, kh, kw)


def mean_window(mean, chw: tuple, ys, xs, flips, crop: int):
    """What to subtract from :func:`crop_mirror`'s result so that it is
    bit-identical to Caffe's order (subtract, crop, mirror): ``mean``
    itself where it is the same at every window (a scalar, per-channel
    values, or planes of one value each: per-channel values broadcast to
    an image), else each sample's window of a full-size (c,h,w) mean
    image, or a crop-sized mean mirrored with its sample.  ``mean`` is a
    constant of the program, so its mirror image is one too and a
    mirrored window is a plain window of it: one slice a sample, no
    reverse."""
    mean = np.asarray(mean, np.float32)
    if mean.ndim < 2:
        return jnp.asarray(mean)
    c, h, w = chw
    kh, kw = (crop, crop) if crop else (h, w)
    if mean.shape[-2:] not in ((1, 1), (h, w), (kh, kw)):
        raise ValueError(
            f"device mean shape {mean.shape} matches neither the full "
            f"image ({h}, {w}) nor the crop ({kh}, {kw})")
    if (mean == mean[..., :1, :1]).all():
        return jnp.asarray(mean[..., :1, :1])
    n = ys.shape[0]
    f = (jnp.zeros((n,), jnp.int32) if flips is None
         else flips.astype(jnp.int32))
    if mean.shape[-2:] == (h, w):
        xs = jnp.where(f == 1, w - kw - xs, xs)
    else:
        ys = xs = jnp.zeros((n,), jnp.int32)
    full = np.broadcast_to(mean, (c,) + mean.shape[-2:])
    both = jnp.asarray(np.stack([full, full[..., ::-1]]))
    return jax.vmap(lambda fi, y, xo: jax.lax.dynamic_slice(
        both, (fi, 0, y, xo), (1, c, kh, kw))[0])(f, ys, xs)


def apply(imgs, ys, xs, flips, spec: AugmentSpec):
    """DataTransformer.batch as traced ops over an [n, c, h, w] uint8
    (or f32) batch: per-sample crop and mirror (:func:`crop_mirror`) →
    mean subtract at each sample's window → scale.  Offsets come from
    :func:`draw_offsets`."""
    if not (spec.mirror and spec.train):
        flips = None
    x = crop_mirror(imgs, ys, xs, flips, spec.crop)
    if spec.mean is not None:
        x = x - mean_window(spec.mean, imgs.shape[1:], ys, xs, flips,
                            spec.crop)
    if spec.scale != 1.0:
        x = x * jnp.float32(spec.scale)
    return x


# the scope graph/net.py gives every layer, so that a device trace names
# the augmentation's operations like a layer's
SCOPE = "L[augment]"


@jax.named_scope(SCOPE)
def augment_batch(imgs, key, spec: AugmentSpec):
    """Draw + apply in one call — the train step's entry point."""
    n, _c, h, w = imgs.shape
    ys, xs, flips = draw_offsets(key, n, h, w, spec)
    return apply(imgs, ys, xs, flips, spec)


def out_shape(in_shape: tuple, spec: AugmentSpec) -> tuple:
    """Augmented batch shape for an [n, c, h, w] input."""
    n, c, h, w = in_shape
    return (n, c, spec.crop, spec.crop) if spec.crop else (n, c, h, w)
