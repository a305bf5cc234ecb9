"""User-defined ``Python`` layers (reference: caffe's PythonLayer —
caffe/src/caffe/layer_factory.cpp CreatorRegistry Python branch,
caffe/include/caffe/layers/python_layer.hpp, exercised by
caffe/python/caffe/test/test_python_layer.py).  ``python_param {module,
layer, param_str}`` resolves to a user class imported from ``sys.path``
(pycaffe's $PYTHONPATH contract) or registered programmatically via
:func:`register_python_layer`.

Two user protocols are supported:

**Functional (TPU-native, preferred).**  The class writes its forward in
jnp; it is traced into the surrounding jit and autodiff supplies the
backward::

    class ScaleBy10:
        def setup(self, bottom_shapes, param_str): ...          # optional
        def out_shapes(self, bottom_shapes) -> list[tuple]: ...
        def forward(self, *bottoms) -> array | sequence: ...    # jnp ops
        def init_params(self, rng, bottom_shapes) -> list: ...  # optional

**pycaffe-compatible (host callback).**  Classes written against the
pycaffe interface — ``setup/reshape/forward/backward`` mutating
``bottom[i].data`` / ``top[i].diff`` numpy buffers (e.g. the reference's
examples/pycaffe/layers/pyloss.py) — run unmodified: the adapter detects
the ``reshape`` method, hosts the blobs in numpy shims, and bridges
forward through ``jax.pure_callback`` with a ``jax.custom_vjp`` whose
backward re-runs the user's ``forward`` (to repopulate instance state)
then calls the user's ``backward``.  This matches caffe's execution
reality: Python layers run on the host CPU either way; here they stay
*jittable* — XLA treats the callback as an opaque host node.
``share_in_parallel`` is accepted and ignored (instances are per-layer,
per-net).  Import ``sparknet_tpu.pycaffe_compat`` (or call its
``install()``) to satisfy user modules that do ``import caffe``.

The callback path needs a PJRT runtime with host send/recv callbacks,
which the CPU and standard TPU runtimes have.  The functional protocol
compiles into the XLA program and runs on every
platform; prefer it for anything performance-relevant.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .registry import LayerImpl, Shape, register_layer

_PROGRAMMATIC: dict[str, type] = {}


def register_python_layer(name: str, cls: type) -> None:
    """Register a class under ``python_param.layer == name`` without
    requiring it to be importable from sys.path."""
    _PROGRAMMATIC[name] = cls


def _resolve(module: str, layer: str) -> type:
    # python_param.module wins when importable (the pycaffe contract); the
    # programmatic registry is the fallback for classes with no module,
    # so a registered name can never shadow a real import
    try:
        mod = importlib.import_module(module)
    except ImportError as e:
        if layer in _PROGRAMMATIC:
            return _PROGRAMMATIC[layer]
        raise ImportError(
            f"Python layer module {module!r} not importable (pycaffe "
            f"resolves it from $PYTHONPATH; register_python_layer() is the "
            f"programmatic alternative): {e}") from e
    try:
        return getattr(mod, layer)
    except AttributeError:
        if layer in _PROGRAMMATIC:
            return _PROGRAMMATIC[layer]
        raise AttributeError(
            f"module {module!r} has no class {layer!r}") from None


class PyBlob:
    """numpy stand-in for a caffe Blob as seen by pycaffe layers:
    ``.data`` / ``.diff`` buffers plus the shape accessors pycaffe
    exposes (python_layer.hpp works on ``vector<Blob*>``)."""

    def __init__(self, arr: np.ndarray):
        self.data = np.asarray(arr, np.float32)
        self.diff = np.zeros_like(self.data)

    def reshape(self, *dims: int) -> None:
        self.data = np.zeros(dims, np.float32)
        self.diff = np.zeros(dims, np.float32)

    @property
    def shape(self):
        return self.data.shape

    @property
    def num(self) -> int:
        return self.data.shape[0] if self.data.ndim else 1

    @property
    def channels(self) -> int:
        return self.data.shape[1] if self.data.ndim > 1 else 1

    @property
    def height(self) -> int:
        return self.data.shape[2] if self.data.ndim > 2 else 1

    @property
    def width(self) -> int:
        return self.data.shape[3] if self.data.ndim > 3 else 1

    @property
    def count(self) -> int:
        return int(self.data.size)


class _Binding:
    """One resolved layer instance + its host-side blob shims."""

    def __init__(self, lp, bottom_shapes: Sequence[Shape]):
        p = lp.sub("python_param")
        module = str(p.get("module", ""))
        layer = str(p.get("layer", ""))
        self.param_str = str(p.get("param_str", ""))
        cls = _resolve(module, layer)
        self.caffe_style = hasattr(cls, "reshape")
        # pycaffe never passes __init__ args; bypass only a signature that
        # REQUIRES them (catching TypeError here would mask real bugs
        # inside a user __init__)
        import inspect
        try:
            sig = inspect.signature(cls.__init__)
            needs_args = any(
                p.default is inspect.Parameter.empty
                and p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)
                for name, p in sig.parameters.items() if name != "self")
        except (TypeError, ValueError):
            needs_args = False
        self.inst = cls.__new__(cls) if needs_args else cls()
        # pycaffe sets param_str as an attribute before setup
        try:
            self.inst.param_str = self.param_str
        except AttributeError:
            pass
        self.bottom_shapes = [tuple(s) for s in bottom_shapes]
        if self.caffe_style:
            self.bottoms = [PyBlob(np.zeros(s, np.float32))
                            for s in bottom_shapes]
            self.tops = [PyBlob(np.zeros((0,), np.float32))
                         for _ in (lp.top or [""])]
            self.inst.setup(self.bottoms, self.tops)
            self.inst.reshape(self.bottoms, self.tops)
            self.out_shapes = [tuple(t.data.shape) for t in self.tops]
        else:
            setup = getattr(self.inst, "setup", None)
            if setup is not None:
                setup(self.bottom_shapes, self.param_str)
            self.out_shapes = [tuple(s) for s in
                               self.inst.out_shapes(self.bottom_shapes)]

    # -- host bridges (caffe-style only) ---------------------------------
    def host_forward(self, *bottoms: np.ndarray) -> tuple[np.ndarray, ...]:
        for blob, arr in zip(self.bottoms, bottoms):
            blob.data = np.asarray(arr, np.float32)
        self.inst.forward(self.bottoms, self.tops)
        return tuple(np.asarray(t.data, np.float32) for t in self.tops)

    def host_backward(self, bottoms: tuple[np.ndarray, ...],
                      gtops: tuple[np.ndarray, ...]
                      ) -> tuple[np.ndarray, ...]:
        # re-run forward so instance state (e.g. pyloss's self.diff) is
        # the state this cotangent belongs to, then route top diffs down
        self.host_forward(*bottoms)
        for t, g in zip(self.tops, gtops):
            t.diff = np.asarray(g, np.float32)
        for b in self.bottoms:
            b.diff = np.zeros_like(b.data)
        self.inst.backward(self.tops, [True] * len(self.bottoms),
                           self.bottoms)
        return tuple(np.asarray(b.diff, np.float32) for b in self.bottoms)


def _callback_fn(binding: _Binding) -> Callable:
    """Jittable bridge: pure_callback forward + custom_vjp backward."""
    out_struct = tuple(jax.ShapeDtypeStruct(s, jnp.float32)
                       for s in binding.out_shapes)
    bot_struct = tuple(jax.ShapeDtypeStruct(s, jnp.float32)
                       for s in binding.bottom_shapes)

    @jax.custom_vjp
    def run(*bottoms):
        return jax.pure_callback(binding.host_forward, out_struct, *bottoms)

    def fwd(*bottoms):
        return run(*bottoms), bottoms

    def bwd(bottoms, gtops):
        return jax.pure_callback(binding.host_backward, bot_struct,
                                 bottoms, gtops)

    run.defvjp(fwd, bwd)
    return run


@register_layer("Python")
class PythonLayer(LayerImpl):
    """Adapter resolving ``python_param`` to a user class (see module
    docstring for the two protocols; reference:
    layer_factory.cpp Python registration + python_layer.hpp)."""

    def min_bottoms(self) -> int:
        return 0

    def per_net_copy(self) -> "PythonLayer":
        # one user-layer instance per net node, like caffe's per-net layer
        # objects (net.cpp Init) — stateful pycaffe layers must not share
        # state across nets
        copy = PythonLayer()
        copy.type = self.type
        return copy

    def _binding(self, lp, bottom_shapes) -> _Binding:
        key = (lp.name, tuple(tuple(s) for s in bottom_shapes))
        cache = getattr(self, "_cache", None)
        if cache is None:
            cache = self._cache = {}
        if key not in cache:
            cache[key] = _Binding(lp, bottom_shapes)
        return cache[key]

    def out_shapes(self, lp, bottom_shapes):
        return list(self._binding(lp, bottom_shapes).out_shapes)

    def init(self, rng, lp, bottom_shapes):
        b = self._binding(lp, bottom_shapes)
        init = getattr(b.inst, "init_params", None)
        if init is not None and not b.caffe_style:
            return list(init(rng, b.bottom_shapes))
        return []

    def apply(self, lp, params, bottoms, train, rng):
        b = self._binding(lp, [x.shape for x in bottoms])
        if b.caffe_style:
            outs = _callback_fn(b)(*bottoms)
            return list(outs)
        fwd = b.inst.forward
        out = fwd(*bottoms, *params) if params else fwd(*bottoms)
        if isinstance(out, (list, tuple)):
            return list(out)
        return [out]
