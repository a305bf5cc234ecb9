"""Device-side data augmentation: crop / mirror / mean-subtract / scale
inside the compiled train step.

``DeviceFeed.device_cast`` already proved the transfer half of the feed
win — shipping uint8 over PCIe and casting on device cuts host→HBM bytes
4×.  This module removes the host TRANSFORM stage too: the host ships
raw uint8 record blocks untouched (``records_feed(raw=True)`` /
``db_feed`` without a transform), and Caffe's DataTransformer semantics
(data_transformer.cpp: cast → full-size mean subtract → random/center
crop → random mirror → scale) run as traced XLA ops on the batch already
resident in HBM — a handful of elementwise ops and slices that fuse into
the step's first layer, vs a host stage that was costing more than the
matmuls it fed.

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

No custom kernels here by design: crop is ``lax.dynamic_slice`` under
``vmap``, mirror is a reversed gather — both lower to plain XLA slices
that fuse with the first conv's input handling on TPU and CPU alike.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class AugmentSpec(NamedTuple):
    """The transform_param subset that augmentation folds on device.
    ``mean`` is a broadcastable f32 array ((c,1,1) per-channel values or
    a full (c,h,w) mean image — full-size subtract happens BEFORE the
    crop, Caffe's window-indexed mean) or None.  ``train`` selects
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


def apply(imgs, ys, xs, flips, spec: AugmentSpec):
    """DataTransformer.batch as traced ops over an [n, c, h, w] uint8
    (or f32) batch: cast → full-size mean subtract → per-sample dynamic
    crop → per-sample mirror → scale.  Offsets come from
    :func:`draw_offsets`."""
    x = imgs.astype(jnp.float32)
    if spec.mean is not None:
        x = x - jnp.asarray(spec.mean, jnp.float32)
    if spec.crop:
        c = x.shape[1]

        def crop_one(img, y, xo):
            return jax.lax.dynamic_slice(
                img, (0, y, xo), (c, spec.crop, spec.crop))

        x = jax.vmap(crop_one)(x, ys, xs)
    if spec.mirror and spec.train:
        x = jnp.where(flips[:, None, None, None] == 1, x[..., ::-1], x)
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
