"""Graph compiler: NetParameter -> pure init/apply functions.

The TPU-native replacement for Caffe's ``Net`` (reference:
caffe/src/caffe/net.cpp:40 ``Init`` — phase filtering, topological wiring via
AppendTop/AppendBottom at net.cpp:385/444, per-layer SetUp with shape
inference) and its executor (``ForwardFromTo``/``BackwardFromTo``,
net.cpp:565/635).  Differences by design:

- The graph lowers to one pure function; ``jax.jit`` compiles forward, and
  backward is ``jax.grad`` of it — there are no per-layer Backward
  implementations and no topological scheduler to maintain.
- ``InsertSplits`` (reference: caffe/src/caffe/util/insert_splits.cpp:12) is
  unnecessary: fan-out in a functional graph is just reusing a value; XLA
  accumulates the cotangents.
- Blob memory management (``SyncedMemory`` CPU/GPU state machine, reference:
  caffe/src/caffe/syncedmem.hpp:62) is XLA's problem, not ours.

Parameter storage is a flat ``{key: [blobs...]}`` dict keyed by layer name,
with cross-layer sharing via ``ParamSpec.name`` (reference: net.cpp
AppendParam sharing semantics) resolved to owner keys.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp

from ..ops.registry import LayerImpl, Shape, get_layer_impl
from ..proto.caffe_pb import (
    LayerParameter,
    NetParameter,
    NetState,
    Phase,
)

# WeightCollection — the {layer name -> list of arrays} container the driver
# averages (reference: src/main/scala/libs/Net.scala:14-47).  Here it is just
# a pytree alias; elementwise add / scalarDivide are jax.tree_util one-liners.
WeightCollection = dict[str, list[jax.Array]]

# The sub-scope of a layer's casts to and from the compute dtype, inside the
# layer's own scope: ``L[conv1]/cast`` forward, ``transpose(jvp(L[conv1]))/
# cast`` backward.
CAST_SCOPE = "cast"


def layer_scope(name: str):
    """The scope every operation of layer (or step phase) ``name`` is traced
    under.  Debug information only: it lives in source locations, and a
    profiler trace names device time by its innermost ``L[...]``."""
    return jax.named_scope(f"L[{name}]")


@dataclasses.dataclass
class NetOutputs:
    """Result of one forward pass."""

    blobs: dict[str, jax.Array]      # net-output blobs (unconsumed tops)
    loss: jax.Array                  # Σ loss_weight · top
    params: WeightCollection         # params incl. forward-state updates (BN)


@dataclasses.dataclass
class _LayerNode:
    lp: LayerParameter
    impl: LayerImpl
    bottoms: list[str]
    tops: list[str]
    param_key: str            # this layer's own storage key (== lp.name)
    lr_mults: list[float]
    decay_mults: list[float]
    # per-blob sharing (reference: net.cpp AppendParam — each ParamSpec with
    # a name shares that one blob with the first layer that declared it):
    # blob index -> (owner layer name, owner *stored* position)
    shared_refs: dict[int, tuple[str, int]] = dataclasses.field(
        default_factory=dict)
    # blob index -> position in params[lp.name] for non-shared blobs
    own_map: dict[int, int] = dataclasses.field(default_factory=dict)
    n_blobs: int | None = None     # total blobs (known when probed)

    def owner_keys(self) -> set[str]:
        """Storage keys holding any of this node's blobs."""
        keys = {o for o, _ in self.shared_refs.values()}
        if self.own_map or not self.shared_refs:
            keys.add(self.param_key)
        return keys

    def loss_weights(self) -> list[float]:
        """Per-top loss weights — Layer::SetLossWeights resolution
        (explicit loss_weight, else 1 on a loss layer's first top)."""
        weights = list(self.lp.loss_weight)
        if not weights and self.impl.is_loss():
            weights = [1.0] + [0.0] * (len(self.tops) - 1)
        return weights


class Net:
    """A phase-filtered, shape-inferred, executable network."""

    def __init__(self, net_param: NetParameter, state: NetState | None = None,
                 *, compute_dtype=None, input_overrides=None):
        if state is None:
            state = net_param.state or NetState()
        self.state = state
        self.param = net_param.filtered(state)
        self.name = net_param.name
        self.compute_dtype = compute_dtype
        self.nodes: list[_LayerNode] = []
        self.blob_shapes: dict[str, Shape] = {}
        self.input_blobs: dict[str, Shape] = {}
        # input_overrides: {input blob name: shape} replacing the declared
        # shape of net-level inputs / Input-layer tops — the pycaffe
        # Net::Reshape path (net.cpp:Reshape propagates new bottom shapes;
        # here downstream shapes re-infer from the overridden inputs)
        overrides = {k: tuple(int(d) for d in v)
                     for k, v in (input_overrides or {}).items()}

        # net-level input declarations (legacy `input:` + `input_shape:`)
        for i, name in enumerate(self.param.input):
            shape = overrides.get(name,
                                  tuple(self.param.input_shape[i].dim))
            self.blob_shapes[name] = shape
            self.input_blobs[name] = shape

        shared_owner: dict[str, tuple[str, int]] = {}  # ParamSpec.name -> (layer, idx)
        self._probe_cache: dict[str, list] = {}
        self._node_by_name: dict[str, _LayerNode] = {}
        # blobs whose batch dim is data-dependent (downstream of Filter):
        # their declared shapes are placeholders — building params from them
        # would silently mis-size blobs (reference: filter_layer.cpp Reshape
        # runs per batch; our shapes are static)
        tainted: set[str] = set()

        for lp in self.param.layer:
            # per_net_copy: layers with per-net host state (Python layers)
            # get a fresh impl per Net — caffe instantiates layer objects
            # per net (net.cpp Init); stateless impls stay singletons
            impl = get_layer_impl(lp.type).per_net_copy()
            tops = list(lp.top)
            bottoms = list(lp.bottom)
            for b in bottoms:
                if b not in self.blob_shapes:
                    raise ValueError(
                        f"layer {lp.name!r} bottom {b!r} unknown "
                        f"(known: {sorted(self.blob_shapes)})")
            bshapes = [self.blob_shapes[b] for b in bottoms]
            if any(b in tainted for b in bottoms):
                self._check_batch_insensitive(lp, impl, bottoms, bshapes,
                                              tainted)
            oshapes = impl.out_shapes(lp, bshapes)
            taints = (getattr(impl, "dynamic_batch", False)
                      or any(b in tainted for b in bottoms))
            if not tops:
                tops = [lp.name] if oshapes else []
            while len(tops) < len(oshapes):
                tops.append(f"{lp.name}_top{len(tops)}")
            for t, s in zip(tops, oshapes):
                self.blob_shapes[t] = tuple(int(d) for d in s)
            if taints:
                tainted.update(tops)
            if getattr(impl, "is_input", lambda: False)():
                if overrides:
                    oshapes = [overrides.get(t, tuple(int(d) for d in s))
                               for t, s in zip(tops, oshapes)]
                    for t, s in zip(tops, oshapes):
                        self.blob_shapes[t] = tuple(int(d) for d in s)
                for t, s in zip(tops, oshapes):
                    self.input_blobs[t] = tuple(int(d) for d in s)

            # param sharing resolution — per ParamSpec entry, as in
            # net.cpp AppendParam (each named spec shares exactly one blob
            # with the first declarer of that name)
            specs = lp.param
            lr_mults = [ps.lr_mult for ps in specs]
            decay_mults = [ps.decay_mult for ps in specs]
            raw_refs: dict[int, tuple[str, int]] = {}
            for i, ps in enumerate(specs):
                if not ps.name:
                    continue
                owner = shared_owner.get(ps.name)
                if owner is None:
                    shared_owner[ps.name] = (lp.name, i)
                else:
                    raw_refs[i] = owner
            if lp.type == "BatchNorm":
                lr_mults = [0.0, 0.0, 0.0]
                decay_mults = [0.0, 0.0, 0.0]
            node = _LayerNode(
                lp=lp, impl=impl, bottoms=bottoms, tops=tops,
                param_key=lp.name, lr_mults=lr_mults, decay_mults=decay_mults,
            )
            if raw_refs:
                self._resolve_sharing(node, raw_refs)
            self.nodes.append(node)
            self._node_by_name[lp.name] = node

        # net outputs via Caffe's available-blob walk (net.cpp AppendTop/
        # AppendBottom: a bottom is erased from the available set, a top
        # re-inserted — so a trailing IN-PLACE layer's blob remains an
        # output, unlike a naive produced-minus-consumed difference).
        # Survivors are listed in FIRST-production order (stable for
        # consumers indexing output_blobs, e.g. classify.py), not Caffe's
        # reinsertion order.
        available: dict[str, None] = {}
        order: dict[str, None] = {}
        for n in self.nodes:
            for b in n.bottoms:
                available.pop(b, None)
            for t in n.tops:
                available[t] = None
                order[t] = None
        self.output_blobs = [t for t in order
                             if t in available and t not in self.input_blobs]
        unknown = set(overrides) - set(self.input_blobs)
        if unknown:
            raise ValueError(
                f"input_overrides for non-input blobs: {sorted(unknown)}")
        self._detect_hfuse_groups()
        self._detect_vfuse_chains()
        self._fuse_skip_noted: set[str] = set()

    def _detect_hfuse_groups(self) -> None:
        """Horizontal fusion of sibling 1x1 convolutions (default ON,
        SPARKNET_NO_HFUSE=1 disables): inception blocks run 3 pointwise
        convs over the SAME input (bvlc_googlenet: 1x1 / 3x3_reduce /
        5x5_reduce per block), each too narrow to fill the MXU's 128-lane
        tiles.
        conv(x,W1) || conv(x,W2) == split(conv(x, concat(W1,W2))) exactly
        (per-output-channel reductions are untouched), so the executor
        can run ONE wider conv and slice — a TPU-shape optimization with
        no reference analog (the GPU reference gains nothing from it).
        Members must read the same VERSION of the bottom (in-place chains
        reassign names), hence the producer-version group key.

        The env toggle is read ONCE here (at Net construction): flipping
        SPARKNET_NO_HFUSE after the first jitted step can never retrace
        the cached executable, so a per-trace read would silently ignore
        the flip.  Per-Net-instance it is at least deterministic."""
        from ..ops.vision import conv_geometry
        from ..utils import knobs
        self._hfuse_enabled = knobs.raw("SPARKNET_NO_HFUSE") != "1"
        ver: dict[str, int] = {}
        groups: dict[tuple, list[_LayerNode]] = {}
        for node in self.nodes:
            if (node.lp.type == "Convolution" and len(node.bottoms) == 1
                    and len(node.tops) == 1):
                kh, kw, sh, sw, ph, pw, dh, dw, _, group, bias = \
                    conv_geometry(node.lp)
                if (kh, kw, sh, sw, ph, pw, dh, dw, group) == (
                        1, 1, 1, 1, 0, 0, 1, 1, 1):
                    b = node.bottoms[0]
                    groups.setdefault((b, ver.get(b, 0), bias),
                                      []).append(node)
            for t in node.tops:
                ver[t] = ver.get(t, 0) + 1
        # first member name -> all member nodes; later members -> stash
        self._hfuse_first: dict[str, list[_LayerNode]] = {}
        self._hfuse_member: set[str] = set()
        for members in groups.values():
            if len(members) >= 2:
                self._hfuse_first[members[0].lp.name] = members
                self._hfuse_member.update(m.lp.name for m in members[1:])

    def _detect_vfuse_chains(self) -> None:
        """Vertical chain fusion: the graph's legal conv..LRN chains
        (``graph/fusion.py``), or none under SPARKNET_FUSE=off — latched
        at Net construction like the hfuse toggle.  Runs AFTER hfuse
        detection: horizontal groups keep their members, vertical
        chains take what's left."""
        from . import fusion
        self._fuse_plan = fusion.resolve_plan(self)
        chains = self._fuse_plan.chains
        self._vfuse_head = {ch.members[0]: ch for ch in chains}
        self._vfuse_member = {m for ch in chains for m in ch.members[1:]}

    def fuse_plan_id(self) -> str:
        """Short id of the active vertical-fusion plan (``off`` when
        none): what a run prints to say which chains it fused."""
        return self._fuse_plan.plan_id()

    def tune_plan_id(self) -> str:
        """Always ``off``: there is no tuning table.  Kept because the
        benchmark's drivers print it (ROADMAP D12)."""
        return "off"

    def _note_unfused_run(self, reason: str) -> None:
        """A fusable net executing unfused (ranged run, eps injection,
        blob introspection) used to be silent — a profile captured from
        such a run would pool into the fused baseline band.  One
        instant() per (net, reason) plus an always-on counter make the
        mislabel visible; trace-time cost only."""
        from ..utils import telemetry
        telemetry.get_registry().counter(
            "fusion_unfused_runs_total",
            "runs of a fusable net that skipped fusion").inc(
                reason=reason)
        if reason not in self._fuse_skip_noted:
            self._fuse_skip_noted.add(reason)
            telemetry.instant(
                "fusion.unfused_run", cat="graph", reason=reason,
                net=self.name or "?",
                hfuse_groups=len(getattr(self, "_hfuse_first", {})),
                vfuse_chains=len(getattr(self, "_vfuse_head", {})))

    @staticmethod
    def _check_batch_insensitive(lp, impl, bottoms, bshapes, tainted) -> None:
        """A consumer of Filter output sees a placeholder batch dim (the
        real one is data-dependent, filter_layer.cpp Reshape).  Reject only
        layers whose *parameter* shapes would change with that dim —
        standard layers (InnerProduct axis=1, Convolution, ...) size params
        off non-batch dims and stay valid eager."""
        def probe(shapes):
            return jax.eval_shape(lambda r: impl.init(r, lp, shapes),
                                  jax.ShapeDtypeStruct((2,), jnp.uint32))
        bumped = [tuple([s[0] + 1] + list(s[1:])) if b in tainted and s
                  else s for b, s in zip(bottoms, bshapes)]
        try:
            a, c = probe(bshapes), probe(bumped)
            sensitive = [x.shape for x in a] != [x.shape for x in c]
        except Exception:
            sensitive = bool(probe(bshapes))  # bump broke init: be strict
        if sensitive:
            raise ValueError(
                f"layer {lp.name!r} ({lp.type}) builds parameters from "
                f"blobs with a data-dependent batch dim (downstream of a "
                f"Filter layer) — its declared shapes are unreliable")

    def _probe_blob_shapes(self, node: _LayerNode) -> list[tuple[Shape, Any]]:
        """(shape, dtype) of each learnable blob without allocating them.
        Cached per layer — sharing-heavy graphs probe owners repeatedly."""
        cached = self._probe_cache.get(node.lp.name)
        if cached is not None:
            return cached
        bshapes = [self.blob_shapes[b] for b in node.bottoms]
        structs = jax.eval_shape(
            lambda r: node.impl.init(r, node.lp, bshapes),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        out = [(tuple(s.shape), s.dtype) for s in structs]
        self._probe_cache[node.lp.name] = out
        return out

    @staticmethod
    def _merge_shared_mult(node: _LayerNode, owner: _LayerNode,
                           i: int, oidx: int, attr: str, label: str) -> None:
        """net.cpp AppendParam lr_mult/decay_mult semantics for a shared
        blob: the sharer's explicit value propagates to the owner when the
        owner left it unset; both explicit and different is an error."""
        raw = f"raw_{label}"
        specs, ospecs = node.lp.param, owner.lp.param
        mine = getattr(specs[i], raw, None) if i < len(specs) else None
        if mine is None:
            return
        owners = getattr(ospecs[oidx], raw, None) if oidx < len(ospecs) else None
        if owners is None:
            mults = getattr(owner, attr)
            while len(mults) <= oidx:
                mults.append(1.0)
            mults[oidx] = mine
        elif owners != mine:
            raise ValueError(
                f"shared param {label} mismatch: layer {node.lp.name!r} "
                f"blob {i} sets {mine}, owner {owner.lp.name!r} blob {oidx} "
                f"sets {owners} (reference: net.cpp AppendParam CHECK)")

    def _resolve_sharing(self, node: _LayerNode,
                         raw_refs: dict[int, tuple[str, int]]) -> None:
        """Map each shared blob index to (owner key, owner stored position),
        validating shapes against the owner (net.cpp AppendParam CHECKs)."""
        mine = self._probe_blob_shapes(node)
        node.n_blobs = len(mine)
        for i, (oname, oidx) in raw_refs.items():
            if i >= len(mine):
                continue  # named spec beyond the layer's blob count
            owner = self._node_by_name.get(oname)
            if owner is None:
                raise ValueError(
                    f"layer {node.lp.name!r} shares param {i} with unknown "
                    f"layer {oname!r}")
            oshapes = self._probe_blob_shapes(owner)
            if oidx >= len(oshapes):
                raise ValueError(
                    f"layer {node.lp.name!r} param {i} shares blob {oidx} of "
                    f"{oname!r}, which has only {len(oshapes)} blobs")
            if oshapes[oidx][0] != mine[i][0]:
                raise ValueError(
                    f"shared param shape mismatch: {node.lp.name!r} blob {i} "
                    f"{mine[i][0]} vs owner {oname!r} blob {oidx} "
                    f"{oshapes[oidx][0]} (reference: net.cpp AppendParam)")
            self._merge_shared_mult(node, owner, i, oidx, "lr_mults", "lr_mult")
            self._merge_shared_mult(node, owner, i, oidx,
                                    "decay_mults", "decay_mult")
            # owner stored position: identity unless the owner itself shares
            opos = owner.own_map.get(oidx, oidx) if owner.shared_refs else oidx
            node.shared_refs[i] = (oname, opos)
        node.own_map = {
            i: pos for pos, i in enumerate(
                j for j in range(len(mine)) if j not in node.shared_refs)
        }

    # -- construction -----------------------------------------------------
    def init(self, rng: jax.Array) -> WeightCollection:
        """Create all learnable blobs with Caffe-filler init (the SetUp pass
        of reference net.cpp:73-133).  Shared blobs are created only by
        their owner layer."""
        params: WeightCollection = {}
        for node in self.nodes:
            rng, sub = jax.random.split(rng)
            bshapes = [self.blob_shapes[b] for b in node.bottoms]
            blobs = node.impl.init(sub, node.lp, bshapes)
            if not blobs:
                continue
            if node.shared_refs:
                own = [b for i, b in enumerate(blobs)
                       if i not in node.shared_refs]
                if own:
                    params[node.lp.name] = own
            else:
                params[node.lp.name] = list(blobs)
        return params

    def node_params(self, params: WeightCollection,
                    node: _LayerNode) -> list[jax.Array]:
        """Assemble the blob list a node sees, following shared refs."""
        if not node.shared_refs:
            return params.get(node.param_key, [])
        out = []
        for i in range(node.n_blobs or 0):
            ref = node.shared_refs.get(i)
            if ref is None:
                out.append(params[node.param_key][node.own_map[i]])
            else:
                out.append(params[ref[0]][ref[1]])
        return out

    def _scatter_node_params(self, params: dict, node: _LayerNode,
                             updated: Sequence[jax.Array]) -> None:
        """Write a node's (possibly shared) updated blobs back to owners."""
        if not node.shared_refs:
            params[node.param_key] = list(updated)
            return
        own = list(params.get(node.param_key, []))
        for i, b in enumerate(updated):
            ref = node.shared_refs.get(i)
            if ref is None:
                own[node.own_map[i]] = b
            else:
                oname, opos = ref
                oblobs = list(params[oname])
                oblobs[opos] = b
                params[oname] = oblobs
        if own:
            params[node.param_key] = own

    def lr_mult_tree(self, params: WeightCollection) -> WeightCollection:
        """Per-blob lr multipliers, same pytree structure as params
        (ParamSpec.lr_mult, reference: caffe.proto ParamSpec)."""
        return self._mult_tree(params, "lr_mults", 1.0)

    def decay_mult_tree(self, params: WeightCollection) -> WeightCollection:
        return self._mult_tree(params, "decay_mults", 1.0)

    def _mult_tree(self, params, attr, default):
        out: WeightCollection = {}
        by_name = {n.lp.name: n for n in self.nodes}
        for key, blobs in params.items():
            node = by_name.get(key)
            mults = getattr(node, attr, []) if node is not None else []
            if node is not None and node.shared_refs:
                # stored position -> original blob index (storage compacts
                # away shared blobs)
                orig = {pos: i for i, pos in node.own_map.items()}
                idxs = [orig.get(p, p) for p in range(len(blobs))]
            else:
                idxs = list(range(len(blobs)))
            out[key] = [
                jnp.asarray(mults[i] if i < len(mults) else default)
                for i in idxs
            ]
        return out

    # -- execution --------------------------------------------------------
    def apply(self, params: WeightCollection, inputs: Mapping[str, jax.Array],
              *, train: bool | None = None, rng: jax.Array | None = None,
              ) -> NetOutputs:
        """One forward pass.  ``inputs`` binds every input blob (data-layer
        top).  Returns net outputs, the weighted loss sum, and params with
        any forward-state updates (BatchNorm running stats) applied."""
        blobs, loss, new_params = self._run(params, inputs, train, rng)
        out = {t: blobs[t] for t in self.output_blobs}
        return NetOutputs(blobs=out, loss=loss, params=new_params)

    def apply_all(self, params, inputs, *, train=None, rng=None,
                  upto: str | None = None,
                  eps: Mapping[str, jax.Array] | None = None,
                  start: str | None = None,
                  ) -> dict[str, jax.Array]:
        """Forward returning every intermediate blob (debug; the analog of
        reading arbitrary blobs over the reference's FFI introspection,
        libccaffe/ccaffe.cpp:86-139).  ``upto`` stops execution after the
        named layer (pycaffe's ``forward(end=...)`` truncation).  ``start``
        begins execution AT the named layer (pycaffe ``forward(start=...)``,
        pycaffe.py:105): layers before it are skipped and every bottom they
        would have produced must be supplied in ``inputs``.  ``eps`` maps
        blob names to zero-valued perturbations added at each blob's final
        assignment — differentiating w.r.t. them yields d(out)/d(blob) for
        INTERMEDIATE blobs (pycaffe ``backward(diffs=[...])``)."""
        for nm, which in ((upto, "upto"), (start, "start")):
            if nm is not None and nm not in self._node_by_name:
                raise ValueError(
                    f"unknown layer {nm!r} for {which}= "
                    f"(layers: {self.layer_names()})")
        blobs, _, _ = self._run(params, inputs, train, rng, upto=upto,
                                eps=eps, start=start, introspect=True)
        return blobs

    def _cast(self, arrs, dtype):
        """Cast floating arrays for mixed-precision compute; ints (labels,
        indices) pass through.  Called inside the layer's ``L[...]`` scope:
        a fusion rooted in a cast reads ``L[<layer>]/cast``."""
        with jax.named_scope(CAST_SCOPE):
            return [a.astype(dtype)
                    if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                    else a for a in arrs]

    def _run(self, params, inputs, train, rng, upto: str | None = None,
             eps: Mapping[str, jax.Array] | None = None,
             start: str | None = None, introspect: bool = False):
        """The layer-by-layer forward shared by apply/apply_all.

        With ``compute_dtype`` set (bf16 on TPU), params and activations
        are cast per layer for MXU-rate matmuls while master params, BN
        state updates, loss layers, and the loss sum stay float32 — the
        standard mixed-precision recipe (params stay f32; casts are
        differentiable, so grads flow back in f32)."""
        if train is None:
            train = self.state.phase == Phase.TRAIN
        start_i = 0
        if start is not None:
            start_i = next(i for i, n in enumerate(self.nodes)
                           if n.lp.name == start)
        stop_i = len(self.nodes) - 1
        if upto is not None:
            ui = next((i for i, n in enumerate(self.nodes)
                       if n.lp.name == upto), None)
            if ui is not None:
                if ui < start_i:
                    raise ValueError(
                        f"start={start!r} comes after upto={upto!r}")
                stop_i = ui
        # the nodes this run actually executes — rng validation and eps
        # placement must see the RANGE, not the whole net
        active = self.nodes[start_i:stop_i + 1]
        if rng is None and any(n.impl.needs_rng(n.lp, train) for n in active):
            raise ValueError(
                f"net {self.name!r} needs an rng in this mode "
                f"(stochastic layer present)")
        if start is None:
            for name in self.input_blobs:
                if name not in inputs:
                    raise ValueError(f"missing input blob {name!r}")
        blobs: dict[str, jax.Array] = dict(inputs)
        new_params = dict(params)
        cd = self.compute_dtype
        loss = jnp.zeros((), jnp.float32)
        # eps injection point: a blob's FINAL assignment WITHIN the
        # executed range (in-place chains reassign; Caffe's per-blob diff
        # is the diff at the final value the run actually produced — a
        # producer outside [start, upto] never runs and must not claim
        # the injection)
        last_producer: dict[str, str] = {}
        if eps:
            for n in active:
                for t in n.tops:
                    if t in eps:
                        last_producer[t] = n.lp.name
        started = start is None
        # fusion runs on full-net, non-introspected runs only (ranged
        # runs and eps injection keep the plain per-layer path, and
        # apply_all must surface REAL intermediate blobs).  Horizontal
        # 1x1-sibling fusion: on by default (exact transform, measured
        # -5.6% GoogLeNet step), SPARKNET_NO_HFUSE=1 restores per-layer
        # execution.  Vertical chains: the graph's conv..LRN chains
        # (graph/fusion.py), SPARKNET_FUSE=off for none.  Both latched
        # at Net construction.
        full_run = start is None and upto is None and not eps \
            and not introspect
        hfuse_on = (bool(self._hfuse_first) and full_run
                    and self._hfuse_enabled)
        vfuse_on = bool(self._vfuse_head) and full_run
        if not full_run and (
                (self._hfuse_first and self._hfuse_enabled)
                or self._vfuse_head):
            # a fusable net running unfused must not be silent — a
            # profile captured from this run is NOT the fused baseline
            self._note_unfused_run(
                "ranged" if (start is not None or upto is not None)
                else "eps" if eps else "introspect")
        hstash: dict[str, jax.Array] = {}
        for ni, node in enumerate(self.nodes):
            if not started:
                if node.lp.name != start:
                    continue
                started = True
            if getattr(node.impl, "is_input", lambda: False)():
                # Input-type layers still honor upto= (their tops are the
                # bound inputs; nothing to execute)
                if upto is not None and node.lp.name == upto:
                    break
                continue
            if vfuse_on and node.lp.name in self._vfuse_member:
                # executed inside its chain head's fused block; its
                # intermediate blob is single-consumer by legality
                # (graph/fusion.py), so nothing downstream misses it
                continue
            missing = [b for b in node.bottoms if b not in blobs]
            if missing:
                raise ValueError(
                    f"layer {node.lp.name!r} needs blobs {missing}; with "
                    f"start={start!r} every bottom produced before the "
                    f"start layer must be fed in inputs")
            stateful = getattr(node.impl, "has_state", False)
            if vfuse_on and node.lp.name in self._vfuse_head:
                ch = self._vfuse_head[node.lp.name]
                members = [self._node_by_name[m] for m in ch.members]
                assert not any(
                    getattr(m.impl, "has_state", False)
                    or m.impl.needs_rng(m.lp, train)
                    or any(w for w in m.loss_weights())
                    for m in members), (
                    f"vfuse chain {ch.scope()!r} admitted a stateful/"
                    f"rng/loss member; fix graph/fusion.py legality")
                final = self._apply_fused_chain(ch, members, new_params,
                                                blobs, cd, train)
                blobs[members[-1].tops[0]] = final
                continue
            if hfuse_on and node.lp.name in self._hfuse_member:
                # sibling 1x1 conv: its slice of the fused conv was
                # stashed when the group's first member ran
                tops = [hstash.pop(node.lp.name)]
            elif hfuse_on and node.lp.name in self._hfuse_first:
                members = self._hfuse_first[node.lp.name]
                # the fused path passes rng=None and skips stateful/
                # is_loss handling for EVERY member (non-first members
                # are served from hstash) — sound only while detection
                # admits nothing but stateless, rng-free Convolutions
                assert not any(
                    getattr(m.impl, "has_state", False)
                    or m.impl.needs_rng(m.lp, train)
                    for m in members), (
                    f"hfuse group of {node.lp.name!r} admitted a "
                    f"stateful/rng layer; fix _detect_hfuse_groups")
                mp = [self.node_params(new_params, m) for m in members]
                sizes = [p0[0].shape[0] for p0 in mp]
                cuts, acc = [], 0
                for s in sizes[:-1]:
                    acc += s
                    cuts.append(acc)
                bots = [blobs[node.bottoms[0]]]
                with layer_scope("+".join(m.lp.name for m in members)):
                    fused = [jnp.concatenate([p0[0] for p0 in mp], axis=0)]
                    if len(mp[0]) > 1:  # bias_term (uniform within a group)
                        fused.append(jnp.concatenate([p0[1] for p0 in mp],
                                                     axis=0))
                    if cd is not None:
                        bots = self._cast(bots, cd)
                        fused = self._cast(fused, cd)
                    (y,) = node.impl.apply(node.lp, fused, bots, train,
                                           None)
                    parts = jnp.split(y, cuts, axis=1)
                for m, part in zip(members[1:], parts[1:]):
                    hstash[m.lp.name] = part
                tops = [parts[0]]
            else:
                p = self.node_params(new_params, node)
                bots = [blobs[b] for b in node.bottoms]
                # named scope: XLA op metadata carries "L[<layer>]"
                # through fwd AND the AD transpose, so a profiler trace
                # attributes device time per layer (the `caffe time`
                # per-layer view, reference: caffe/tools/caffe.cpp:290-376,
                # but post-fusion on-device; benchmark/lib/trace.py and
                # utils/xplane.py read it).  The casts sit inside it: they
                # are the layer's cost
                with layer_scope(node.lp.name):
                    layer_rng = None
                    if rng is not None and node.impl.needs_rng(node.lp,
                                                               train):
                        # per-node identity fold, NOT sequential splits: a
                        # ranged run (start=/upto=) must give each layer
                        # the same stream the full forward gave it, so
                        # ranged backward replays the masks its forward
                        # actually used
                        layer_rng = jax.random.fold_in(rng, ni)
                    if cd is not None:
                        if (node.impl.is_loss()
                                or node.lp.type == "Accuracy" or stateful):
                            # numerics-critical: losses, accuracy, BN
                            # batch stats
                            bots = self._cast(bots, jnp.float32)
                        else:
                            bots = self._cast(bots, cd)
                            p = self._cast(p, cd)
                    result = node.impl.apply(node.lp, p, bots, train,
                                             layer_rng)
                if stateful:
                    tops, updated = result
                    self._scatter_node_params(new_params, node, updated)
                else:
                    tops = result
            # what the run does to a layer's tops is the layer's too
            with layer_scope(node.lp.name):
                if eps:
                    tops = [v + eps[t]
                            if last_producer.get(t) == node.lp.name else v
                            for t, v in zip(node.tops, tops)]
                for t, v in zip(node.tops, tops):
                    blobs[t] = v
                # loss accumulation (reference: Layer::SetLossWeights +
                # Net::Forward summing weighted tops)
                for w, v in zip(node.loss_weights(), tops):
                    if w:
                        # f32 accumulation even when the top was computed
                        # in a reduced compute_dtype (loss_weight on
                        # non-loss layers)
                        loss = loss + w * jnp.sum(v.astype(jnp.float32))
            if upto is not None and node.lp.name == upto:
                break
        return blobs, loss, new_params

    def _apply_fused_chain(self, ch, members, params, blobs, cd, train):
        """Execute one planned vertical chain as a single block.

        The head conv runs through its own impl (XLA's MXU tiling is
        already optimal; on eligible stems that includes the
        space-to-depth rewrite), and so do a pool and a ReLU that does
        not fold.  The tail, [ReLU+]LRN, is
        ``ops.vision.lrn_chain_epilogue`` — the Pallas one-VMEM-trip
        kernel on TPU, the scale-residual custom-VJP reference
        elsewhere.  All of it sits in the shared ``L[a+b+...]`` scope,
        so the chain profiles as ONE row."""
        from ..ops.vision import lrn_chain_epilogue, lrn_geometry
        folds = ch.epilogue == "relu+lrn"
        head = members[0]
        x = blobs[head.bottoms[0]]
        p = self.node_params(params, head)
        with layer_scope(ch.scope()):
            if cd is not None:
                x = self._cast([x], cd)[0]
                p = self._cast(p, cd)
            (y,) = head.impl.apply(head.lp, p, [x], train, None)
            # between head and tail: a ReLU, a pool; neither has blobs
            for m in members[1:-2 if folds else -1]:
                (y,) = m.impl.apply(m.lp, [], [y], train, None)
            size, alpha, beta, k, _ = lrn_geometry(members[-1].lp)
            y = lrn_chain_epilogue(y, size, alpha, beta, k, relu=folds)
        return y

    # -- introspection (FFI-parity helpers; reference: ccaffe.cpp:86-139,
    #    Net.scala:64-66) --------------------------------------------------
    @property
    def num_layers(self) -> int:
        return len(self.nodes)

    def layer_names(self) -> list[str]:
        return [n.lp.name for n in self.nodes]

    def layer_num_weights(self, params: WeightCollection) -> dict[str, int]:
        return {k: len(v) for k, v in params.items()}


# -- WeightCollection math (reference: Net.scala:17-46) ---------------------

def weights_add(a: WeightCollection, b: WeightCollection) -> WeightCollection:
    """Elementwise sum — WeightCollection.add (reference: Net.scala:27-46)."""
    return jax.tree_util.tree_map(jnp.add, a, b)


def weights_scalar_divide(w: WeightCollection, v: float) -> WeightCollection:
    """In the reference this is in-place (Net.scala:17-23); pure here."""
    return jax.tree_util.tree_map(lambda x: x / v, w)
