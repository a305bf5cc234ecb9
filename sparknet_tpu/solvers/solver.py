"""Host-level Solver — the ``Solver::Step``/``Solve`` analog.

Mirrors the training loop of the reference (caffe/src/caffe/solver.cpp:193-283
``Step``: clear diffs → iter_size fwd/bwd accumulation → smoothed loss →
ApplyUpdate → optional snapshot) and the fork's JVM-driven test pass
(``Solver::TestAndStoreResult``, reference: caffe/src/caffe/solver.cpp:413-445
— runs the share-weights test net N times accumulating every output scalar).

Differences by design: one call into a jit-compiled train step does
forward+backward+update on device; the host loop only feeds data and reads
the smoothed loss.  ``iter_size`` micro-batching runs as a ``lax.scan``
inside the same compiled step, so gradient accumulation never leaves HBM.
"""

from __future__ import annotations

import collections
from typing import Any, Callable, Iterator, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.net import Net, WeightCollection
from ..proto.caffe_pb import NetParameter, NetState, Phase, SolverParameter
from ..utils import telemetry
from ..utils.glog import log_line
from .lr_policies import learning_rate
from .update_rules import make_update_rule


def load_weights_into(net, params, path: str):
    """Weights-only load into an existing (net, params) pair — the
    Net::CopyTrainedLayersFrom path without constructing a full Solver
    (used by Classifier/Detector, `caffe test`, extract_features)."""
    loader = Solver.__new__(Solver)
    loader.params = params
    loader.train_net = net
    loader.load_weights(path)
    return loader.params


class Solver:
    """Owns params + optimizer state and a compiled train step.

    The factory path matches ``CaffeNet.apply`` → ``load_solver_from_protobuf``
    (reference: src/main/scala/libs/Net.scala:209-219, libccaffe/ccaffe.cpp:72)
    except the solver type is honored rather than hardcoded to SGD (the
    reference wrapper instantiates ``SGDSolver`` unconditionally — a known
    wart we do not reproduce).
    """

    def __init__(self, sp: SolverParameter, *, seed: int | None = None,
                 jit: bool = True, compute_dtype=None, remat: bool = False):
        self.sp = sp
        net_param = sp.net_param or sp.train_net_param
        if net_param is None:
            raise ValueError("SolverParameter carries no net definition")
        if seed is None:
            seed = sp.random_seed if sp.random_seed >= 0 else 0
        self.train_net = Net(net_param, NetState(Phase.TRAIN),
                             compute_dtype=compute_dtype)
        # dedicated test net definitions win (Solver::InitTestNets
        # precedence, solver.cpp:104-172: test_net_param > test_net file >
        # shared net); `test_net:` file paths must be resolved into
        # test_net_param by the caller (proto.caffe_pb.resolve_solver_nets).
        # EVERY test_net entry is instantiated and evaluated, like the
        # reference's test_nets_ vector (Solver::TestAll loops them all).
        test_params = list(sp.test_net_param) or [net_param]
        self.test_nets: list[Net] = [
            Net(tp, NetState(Phase.TEST), compute_dtype=compute_dtype)
            for tp in test_params]
        self.test_net = self.test_nets[0]
        self._dedicated_test_net = bool(sp.test_net_param)
        self.rule = make_update_rule(sp)
        self._rng = jax.random.PRNGKey(seed)
        self._rng, init_rng = jax.random.split(self._rng)
        self.params: WeightCollection = self.train_net.init(init_rng)
        # a dedicated test net may own layers the train net lacks; those
        # keep their filler init while matching layers share trained
        # params (Net::ShareTrainedLayersWith, net.cpp:737).  Probe key
        # sets shape-only first — the full filler init runs only when the
        # test net actually has extra layers.  One extra-collection per
        # test net.
        self._test_extras: list[WeightCollection] = []
        for i, tn in enumerate(self.test_nets):
            extra: WeightCollection = {}
            if self._dedicated_test_net:
                probe = jax.eval_shape(
                    lambda r, tn=tn: tn.init(r),
                    jax.ShapeDtypeStruct((2,), jnp.uint32))
                if any(k not in self.params for k in probe):
                    full = tn.init(jax.random.fold_in(init_rng, i + 1))
                    extra = {k: v for k, v in full.items()
                             if k not in self.params}
            self._test_extras.append(extra)
        self.state = self.rule.init(self.params)
        self.iter = 0
        self._lr_mults = self.train_net.lr_mult_tree(self.params)
        self._decay_mults = self.train_net.decay_mult_tree(self.params)
        self._remat = remat
        self._smoothed = collections.deque(maxlen=max(sp.average_loss, 1))
        self._signal_guard = None       # installed by solve(); polled per
        self._stop_requested = False    # iteration inside step()
        self._train_iter: Iterator[Mapping[str, Any]] | None = None
        self._test_iter_factories: list[
            Callable[[], Iterator[Mapping[str, Any]]] | None] = \
            [None] * len(self.test_nets)

        self._jit = jit                 # set_augment rebuilds self._step
        self._augment_spec = None       # ops.augment.AugmentSpec when set
        self._augment_device = False
        step = self.make_train_step()
        self._step = jax.jit(step, donate_argnums=(0, 1)) if jit else step
        self._test_fwds = [
            (jax.jit(f) if jit else f)
            for f in (self._make_test_forward(tn) for tn in self.test_nets)]
        self._test_fwd = self._test_fwds[0]

    # -- pure step construction ------------------------------------------
    def make_train_step(self):
        """Build the pure (params, state, it, batches, rng) -> (params,
        state, loss) function.  ``batches`` has a leading iter_size axis.
        The body — iter_size accumulation → preprocess → rule update — is
        the shared ``local_update`` of ``step.make_step_fns``."""
        from .step import make_step_fns
        _, local_update, _ = make_step_fns(
            self.sp, self.train_net, self.rule, self._lr_mults,
            self._decay_mults, remat=self._remat)
        return local_update

    # -- data feeding (CaffeNet.setTrainData/setTestData analog;
    #    reference: src/main/scala/libs/Net.scala:79-92) ------------------
    def set_train_data(self, it: Iterator[Mapping[str, Any]]) -> None:
        self._train_iter = it

    def set_augment(self, spec, device: bool | None = None,
                    blob: str = "data") -> None:
        """Fold crop/mirror/mean-subtract/scale into the train step so
        the feed ships raw uint8 (``records_feed(raw=True)``) and the
        host transform stage disappears.

        ``device=True`` (default: the ``SPARKNET_AUG_DEVICE`` knob)
        recompiles ``self._step`` with ``ops.augment.augment_batch``
        traced in front of the update — the augmentation RNG splits off
        the step's traced key, so replay stays exact.  ``device=False``
        runs the SAME spec through the numpy reference
        (``transforms.augment_batch_host``) on the host, consuming the
        identical key split — both paths produce bit-identical train
        losses at the same seed (the exactness-audit contract; every op
        involved is IEEE-exact in numpy and XLA).  Call with
        ``spec=None`` to remove augmentation again."""
        from ..ops.augment import augment_batch
        from ..utils import knobs
        from .step import INPUT_SCOPE
        if device is None:
            device = knobs.get_bool("SPARKNET_AUG_DEVICE", True)
        self._augment_spec = spec
        self._augment_device = bool(device) and spec is not None
        self._augment_blob = blob
        base = self.make_train_step()
        if self._augment_device:
            spec_ = spec

            def step(params, state, it, batches, rng):
                # the glue round ``augment_batch``'s own ``L[augment]``
                with jax.named_scope(INPUT_SCOPE):
                    aug_rng, rng = jax.random.split(rng)
                    data = batches[blob]
                    i, n = data.shape[0], data.shape[1]
                    flat = data.reshape((i * n,) + data.shape[2:])
                    out = augment_batch(flat, aug_rng, spec_)
                    batches = dict(batches)
                    batches[blob] = out.reshape((i, n) + out.shape[1:])
                return base(params, state, it, batches, rng)
        else:
            step = base
        self._step = (jax.jit(step, donate_argnums=(0, 1))
                      if self._jit else step)

    def _host_augment(self, stacked, rng):
        """The ``device=False`` half of :meth:`set_augment`: numpy
        augmentation on the already-stacked [iter, n, ...] feed, drawing
        from the same key split the device path traces.  Returns
        (stacked, remaining_rng)."""
        from ..data.transforms import augment_batch_host
        aug_rng, rng = jax.random.split(rng)
        data = np.asarray(stacked[self._augment_blob])
        i, n = data.shape[0], data.shape[1]
        flat = data.reshape((i * n,) + data.shape[2:])
        out = augment_batch_host(flat, aug_rng, self._augment_spec)
        stacked = dict(stacked)
        stacked[self._augment_blob] = jnp.asarray(
            out.reshape((i, n) + out.shape[1:]))
        return stacked, rng

    def set_test_data(self, factory: Callable[[], Iterator[Mapping[str, Any]]],
                      net_id: int = 0) -> None:
        self._test_iter_factories[net_id] = factory

    @property
    def _test_iter_factory(self):
        return self._test_iter_factories[0]

    @property
    def _test_extra(self) -> WeightCollection:
        """Test-only params of test net 0 (back-compat alias; per-net
        collections live in ``_test_extras``)."""
        return self._test_extras[0]

    def _ensure_test_factory(self, net_id: int = 0) -> None:
        """Self-sourcing test nets (DummyData etc.) evaluate without an
        explicit feed; nets with input blobs still require one."""
        if self._test_iter_factories[net_id] is None:
            if self.test_nets[net_id].input_blobs:
                raise RuntimeError(
                    "no test data set; call set_test_data first")
            import itertools
            self._test_iter_factories[net_id] = lambda: itertools.repeat({})

    # -- Solver::Step (reference: solver.cpp:193-283) ---------------------
    def step(self, n: int) -> float:
        """Run n iterations pulling minibatches from the train iterator;
        returns the smoothed loss (solver.cpp:226-235 average_loss)."""
        if self._train_iter is None:
            if self.train_net.input_blobs:
                raise RuntimeError(
                    "no train data set; call set_train_data first")
            # self-sourcing net (DummyData/Data layers generate their own
            # batches on device — dummy_data_layer.cpp etc.): empty feed
            import itertools
            self._train_iter = itertools.repeat({})
        loss = 0.0
        for _ in range(n):
            stacked = self._next_batches()
            self._rng, rng = jax.random.split(self._rng)
            if self._augment_spec is not None and not self._augment_device:
                # host-side half of the augment parity contract: consume
                # the same key split the device path traces
                stacked, rng = self._host_augment(stacked, rng)
            debug = self.sp.debug_info and (
                not self.sp.display or (self.iter + 1) % self.sp.display == 0)
            # copy: the jitted step donates param buffers
            params_before = jax.tree_util.tree_map(
                jnp.copy, self.params) if debug else None
            with telemetry.span("step.dispatch", cat="step",
                                iter=self.iter):
                self.params, self.state, loss_dev = self._step(
                    self.params, self.state, self.iter, stacked, rng)
            # the loss stays a DEVICE scalar here — fetching it every
            # iteration would serialize the host loop on each compiled
            # step (the reference pattern carried over from per-iter
            # logging).  ``smoothed_loss()`` converts lazily, so the host
            # only synchronizes at display boundaries and chunk ends —
            # the per-step analog of the trainer's harvest_lag.
            loss = loss_dev
            self._smoothed.append(loss_dev)
            self.iter += 1
            if debug:
                self._log_debug_info(stacked, params_before, rng)
            if self.sp.display and self.iter % self.sp.display == 0:
                log_line(f"Iteration {self.iter}, "
                         f"loss = {self.smoothed_loss():.6f}")
                # the reference logs the rate each display interval
                # (SGDSolver::ApplyUpdate, sgd_solver.cpp:104-106) — the
                # rate the NEXT step will apply, which is what caffe's
                # ApplyUpdate(iter_) prints at the same boundary
                log_line(f"Iteration {self.iter}, "
                         f"lr = {float(learning_rate(self.sp, self.iter)):g}")
            # snapshot-on-schedule (reference: solver.cpp:270-277)
            if (self.sp.snapshot and self.sp.snapshot_prefix
                    and self.iter % self.sp.snapshot == 0):
                self.snapshot_caffe()
            # per-iteration signal poll (solver.cpp:270-281 GetRequestedAction
            # inside Step — keeps huge chunks interruptible)
            if self._signal_guard is not None:
                from ..utils.signals import SolverAction
                action = self._signal_guard.check()
                if action == SolverAction.SNAPSHOT and self.sp.snapshot_prefix:
                    print(f"Snapshotting (signal) at iter {self.iter}")
                    self.snapshot_caffe()
                elif action in (SolverAction.STOP,
                                SolverAction.SNAPSHOT_STOP):
                    # SNAPSHOT_STOP (preemption notice): the stop path in
                    # solve() snapshots before returning, so both map to
                    # a clean, resumable stop at the chunk boundary
                    self._stop_requested = True
                    break
        return self.smoothed_loss() if self._smoothed else float(loss)

    def solve(self, max_iter: int | None = None) -> float:
        """Drive training to ``max_iter`` with the Solver::Solve schedule
        (reference: solver.cpp:285-330): optional test at start
        (test_initialization / resume on an interval boundary), periodic
        test passes every ``test_interval``, a final test pass, the
        step-level display/snapshot handled by ``step``, and the
        SignalHandler contract — SIGHUP snapshots, SIGINT snapshots then
        stops at the next chunk boundary (solver.cpp:270-281).  Returns
        the final smoothed loss."""
        from ..utils.signals import SignalGuard
        sp = self.sp
        max_iter = max_iter or sp.max_iter or 100
        if sp.test_interval:
            for i, tn in enumerate(self.test_nets):
                if not tn.input_blobs:
                    self._ensure_test_factory(i)  # self-sourcing test net
        interval = sp.test_interval \
            if (sp.test_interval and any(self._test_iter_factories)) else 0
        test_iter = sp.test_iter[0] if sp.test_iter else 50
        can_snapshot = bool(sp.snapshot_prefix)
        if interval and self.iter % interval == 0 and (
                self.iter > 0 or sp.test_initialization):
            self._print_test_scores(test_iter)
        loss = 0.0
        self._stop_requested = False
        with SignalGuard() as guard:
            self._signal_guard = guard
            try:
                while self.iter < max_iter:
                    n = (min(interval - self.iter % interval,
                             max_iter - self.iter)
                         if interval else max_iter - self.iter)
                    loss = self.step(n)
                    if self._stop_requested:
                        print(f"Optimization stopped early (signal) at "
                              f"iter {self.iter}")
                        if can_snapshot:
                            self.snapshot_caffe()
                        return loss
                    log_line(f"Iteration {self.iter}, loss = {loss:.6f}")
                    if interval:
                        self._print_test_scores(test_iter)
            finally:
                self._signal_guard = None
        print("Optimization Done.")
        return loss

    def _print_test_scores(self, default_iter: int) -> None:
        """Evaluate every testable net in turn (Solver::TestAll,
        solver.cpp:407-411) with its own test_iter."""
        multi = len(self.test_nets) > 1
        for n in range(len(self.test_nets)):
            if (self._test_iter_factories[n] is None
                    and self.test_nets[n].input_blobs):
                continue  # this net has no feed; skip rather than raise
            ti = (self._test_iter_for(n) if self.sp.test_iter
                  else default_iter)
            # the reference's marker line (solver.cpp Test: "Iteration
            # %d, Testing net (#%d)") — log parsers key test scores to
            # the iteration by it, incl. the pre-training pass on resume
            log_line(f"Iteration {self.iter}, Testing net (#{n})")
            tag = f" #{n}" if multi else ""
            for k, v in self.test(ti, net_id=n).items():
                arr = np.asarray(v, np.float64) / ti
                if arr.ndim == 0:
                    log_line(
                        f"    Test net{tag} output: {k} = {float(arr):.6f}")
                else:  # per-element, like Caffe's indexed test outputs
                    for i, x in enumerate(arr.reshape(-1)):
                        log_line(f"    Test net{tag} output: "
                                 f"{k}[{i}] = {float(x):.6f}")

    def _log_debug_info(self, stacked, params_before, rng) -> None:
        """Per-blob/param mean-|x| dumps behind ``sp.debug_info`` — the
        ForwardDebugInfo / UpdateDebugInfo logging of the reference
        (net.cpp:711-735, sgd_solver.cpp via Solver::Step).  The forward
        re-runs eagerly on the first micro-batch with the PRE-update
        params — net.cpp ForwardDebugInfo reflects the step's actual
        forward; update magnitudes come from the params delta (the jitted
        step exposes no grads)."""
        def asum(v) -> float:
            return float(jnp.mean(jnp.abs(v)))

        first = jax.tree_util.tree_map(lambda x: x[0], stacked)
        blobs = self.train_net.apply_all(params_before, first, train=True,
                                         rng=rng)
        for node in self.train_net.nodes:
            for t in node.tops:
                if t in blobs:
                    print(f"    [Forward] Layer {node.lp.name}, "
                          f"top blob {t} data: {asum(blobs[t]):.6g}")
        for key, before in params_before.items():
            for i, (b, a) in enumerate(zip(before, self.params[key])):
                print(f"    [Update] Layer {key}, param {i} "
                      f"data: {asum(a):.6g}; diff: {asum(a - b):.6g}")

    def _next_batches(self):
        with telemetry.span("step.next_batch", cat="step", iter=self.iter):
            batches = [dict(next(self._train_iter))
                       for _ in range(self.sp.iter_size)]
            return jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *batches)

    def smoothed_loss(self) -> float:
        """Average of the trailing ``average_loss`` window
        (solver.cpp:226-235).  The window holds device scalars; this is
        the one place they are fetched, so calling it IS the host sync
        point — step() only does so at display boundaries and chunk
        ends."""
        if not self._smoothed:
            return 0.0
        with telemetry.span("step.loss_fetch", cat="step", iter=self.iter):
            return float(sum(float(v) for v in self._smoothed)
                         / len(self._smoothed))

    # -- test pass (Solver::TestAndStoreResult; reference:
    #    solver.cpp:413-445 + ccaffe.cpp:179-187) -------------------------
    @staticmethod
    def _make_test_forward(tn: Net):
        # outputs pass through element-wise (Accuracy's per-class second
        # top stays a vector) — Solver::TestAndStoreResult accumulates
        # every element of every output blob (solver.cpp:413-445)
        def fwd(params, batch, rng=None):
            out = tn.apply(params, batch, train=False, rng=rng)
            return dict(out.blobs)
        return fwd

    def test(self, num_steps: int | None = None,
             net_id: int = 0) -> dict[str, Any]:
        """Run weight-sharing test net ``net_id`` ``num_steps`` times,
        accumulating each output-blob element (the JVM then averages
        across workers — reference: ImageNetApp.scala:138-140).  Scalar
        outputs come back as floats; vector outputs (per-class accuracy)
        as numpy arrays.  Solver::Test(test_net_id), solver.cpp:413-445."""
        self._ensure_test_factory(net_id)
        if num_steps is None:
            num_steps = self._test_iter_for(net_id)
        it = self._test_iter_factories[net_id]()
        tn = self.test_nets[net_id]
        needs_rng = any(n.impl.needs_rng(n.lp, False) for n in tn.nodes)
        # test-net-only layers keep filler init; merged as jit ARGUMENTS
        # (not trace constants) so surgery on them is honored per call
        extra = self._test_extras[net_id]
        params = {**extra, **self.params} if extra else self.params
        totals: dict[str, Any] = {}
        for _ in range(num_steps):
            rng = None
            if needs_rng:  # stochastic data layers (gaussian DummyData)
                self._rng, rng = jax.random.split(self._rng)
            scores = self._test_fwds[net_id](params, dict(next(it)), rng)
            for k, v in scores.items():
                val = float(v) if np.ndim(v) == 0 else np.asarray(v)
                totals[k] = val if k not in totals else totals[k] + val
        return totals

    def _test_iter_for(self, net_id: int) -> int:
        """Per-net test_iter (repeated field, one per test net like the
        reference's check at solver.cpp:36-44); last value repeats."""
        ti = self.sp.test_iter
        if not ti:
            return 1
        return ti[net_id] if net_id < len(ti) else ti[-1]

    # -- checkpointing (Solver::Snapshot/Restore; reference:
    #    solver.cpp:447-530, sgd_solver.cpp:242-296; FFI surface
    #    ccaffe.cpp:205-211) ----------------------------------------------
    def snapshot(self, path: str) -> None:
        from ..utils.checkpoint import save_checkpoint
        save_checkpoint(path, {
            "params": self.params,
            "state": self.state,
            "iter": self.iter,
        })

    def restore(self, path: str) -> None:
        from ..utils.checkpoint import load_checkpoint
        blob = load_checkpoint(path)
        self.params = jax.tree_util.tree_map(jnp.asarray, blob["params"])
        self.state = jax.tree_util.tree_map(jnp.asarray, blob["state"])
        self.iter = int(blob["iter"])

    def load_weights(self, path: str) -> None:
        """Weights-only load (Net::CopyTrainedLayersFrom; reference:
        net.cpp:843-848, Net.scala:195-197): copy blobs for layers whose
        names match, leave the rest initialized.  Accepts the repo's npz
        checkpoints, Caffe ``.caffemodel``/binaryproto files (sniffed by
        magic; net.cpp:805-848) including V1-format zoo models, AND
        ``.caffemodel.h5`` HDF5 models (net.cpp:889-924)."""
        with open(path, "rb") as f:
            magic = f.read(4)
        if magic[:2] == b"PK":  # npz (zip) — framework-native checkpoint
            from ..utils.checkpoint import load_checkpoint
            blob = load_checkpoint(path)
            saved = blob["params"] if "params" in blob else blob
            for k, v in saved.items():
                if k in self.params:
                    self.params[k] = [jnp.asarray(b) for b in v]
            return
        if magic == b"\x89HDF":  # .caffemodel.h5 (CopyTrainedLayersFromHDF5,
            # net.cpp:889-924)
            from ..data.hdf5 import load_model_hdf5
            self.copy_trained_layers_from(load_model_hdf5(path))
            return
        from ..proto.caffemodel import load_caffemodel
        self.copy_trained_layers_from(load_caffemodel(path))

    @staticmethod
    def _shape_adapt(src, dst_shape, where: str):
        """Legacy-shape tolerance, no broader: a saved blob may be reshaped
        only when it is the same dims modulo size-1 axes (the legacy 4-d
        spellings like (1,1,N,K) for an (N,K) fc blob — Blob::ShapeEquals,
        reference: blob.cpp).  Any other mismatch raises, as Caffe's shape
        CHECKs do (a same-size layout difference, e.g. a transposed ip
        weight, must not be silently reshaped)."""
        src = np.asarray(src)
        if src.shape == tuple(dst_shape):
            return src
        squeeze = lambda s: tuple(d for d in s if d != 1)
        if squeeze(src.shape) != squeeze(dst_shape):
            raise ValueError(
                f"{where}: checkpoint shape {src.shape} incompatible with "
                f"net shape {tuple(dst_shape)}")
        return src.reshape(dst_shape)

    def copy_trained_layers_from(self, saved: Mapping[str, list]) -> None:
        """Copy blobs by layer name (Net::CopyTrainedLayersFrom semantics;
        reference: net.cpp:805-842 — matching names copied with shape
        CHECKs, everything else left initialized).  Caffe serializes every
        layer with its FULL blob list (sharer layers carry shared blobs in
        Net::ToProto), so copies route through the sharing map — writing a
        shared blob via a sharer updates the owner's copy, last write wins,
        exactly as Caffe copies through the shared pointer."""
        by_name = {n.lp.name: n for n in self.train_net.nodes}
        # staged[(storage key, position)] = new array
        staged: dict[tuple[str, int], jnp.ndarray] = {}
        for name, blobs in saved.items():
            node = by_name.get(name)
            if node is None:
                continue
            target = self.train_net.node_params(self.params, node)
            if not target and not blobs:
                continue
            if len(blobs) != len(target):
                raise ValueError(
                    f"layer {name!r}: checkpoint has {len(blobs)} blobs, "
                    f"net expects {len(target)}")
            for i, (src, dst) in enumerate(zip(blobs, target)):
                arr = jnp.asarray(
                    self._shape_adapt(src, dst.shape,
                                      f"layer {name!r} blob {i}"), dst.dtype)
                ref = node.shared_refs.get(i) if node.shared_refs else None
                if ref is None:
                    pos = node.own_map[i] if node.shared_refs else i
                    staged[(name, pos)] = arr
                else:
                    staged[ref] = arr
        # commit only after every layer validated — a partial copy must not
        # leave the solver with half-replaced weights
        for (key, pos), arr in staged.items():
            blobs = list(self.params[key])
            blobs[pos] = arr
            self.params[key] = blobs

    # -- Caffe-format snapshots (Solver::Snapshot/Restore, both
    #    snapshot_format values: BINARYPROTO and HDF5; reference:
    #    solver.cpp:447-530, sgd_solver.cpp:242-338) -----------------------
    _HISTORY_SLOTS = {
        "SGD": ("history",), "NESTEROV": ("history",),
        "ADAGRAD": ("history",), "RMSPROP": ("history",),
        "ADADELTA": ("sq_grad", "sq_update"), "ADAM": ("m", "v"),
    }

    def _history_flat(self) -> list:
        """Flatten optimizer state into Caffe's history-blob order: one run
        of learnable-param-order blobs per slot (AdaDelta/Adam push a second
        run onto ``history_``; reference: adadelta_solver.cpp ctor,
        adam_solver.cpp AdamPreSolve)."""
        flat = []
        for slot in self._HISTORY_SLOTS[self.rule.name]:
            tree = self.state[slot]
            for key in self.params:
                flat.extend(np.asarray(b) for b in tree[key])
        return flat

    def snapshot_caffe(self, prefix: str | None = None) -> tuple[str, str]:
        """Write ``<prefix>_iter_N.caffemodel`` + ``.solverstate`` exactly as
        Solver::Snapshot names them (reference: solver.cpp:461-476), or the
        ``.caffemodel.h5`` + ``.solverstate.h5`` pair when
        ``snapshot_format: HDF5`` (solver.cpp:449-459 SnapshotToHDF5,
        sgd_solver.cpp:275-298)."""
        from ..proto.caffemodel import save_caffemodel, save_solverstate
        prefix = prefix if prefix is not None else self.sp.snapshot_prefix
        base = f"{prefix}_iter_{self.iter}"
        hdf5 = self.sp.snapshot_format == "HDF5"
        model_path = base + (".caffemodel.h5" if hdf5 else ".caffemodel")
        state_path = base + (".solverstate.h5" if hdf5 else ".solverstate")
        net_param = self.sp.net_param or self.sp.train_net_param
        # Net::ToProto writes every layer with its FULL blob list (sharer
        # layers repeat shared blobs), so Caffe's CopyTrainedLayersFrom
        # CHECK_EQ(blobs_size) accepts the file — assemble through the
        # sharing map rather than dumping compacted storage
        full = {}
        for node in self.train_net.nodes:
            blobs = self.train_net.node_params(self.params, node)
            if blobs:
                full[node.lp.name] = blobs
        if hdf5:
            from ..data.hdf5 import save_model_hdf5, save_state_hdf5
            save_model_hdf5(model_path, full)
            save_state_hdf5(state_path, self.iter, self._history_flat(),
                            learned_net=model_path)
        else:
            save_caffemodel(model_path, full, net_param)
            save_solverstate(state_path, self.iter, self._history_flat(),
                             learned_net=model_path)
        return model_path, state_path

    def restore_caffe(self, state_path: str) -> None:
        """Restore from a ``.solverstate`` / ``.solverstate.h5`` (+ its
        learned_net model if present; reference: solver.cpp:510-530,
        sgd_solver.cpp:280-296 binaryproto, :321-338 HDF5 — dispatched on
        the HDF5 magic like caffe dispatches on the .h5 suffix)."""
        import os

        from ..data.hdf5 import is_hdf5_file, load_state_hdf5
        from ..proto.caffemodel import load_solverstate
        st = (load_state_hdf5(state_path) if is_hdf5_file(state_path)
              else load_solverstate(state_path))
        history = st["history"]
        slots = self._HISTORY_SLOTS[self.rule.name]
        n_blobs = sum(len(v) for v in self.params.values())
        if len(history) != n_blobs * len(slots):
            raise ValueError(
                f"solverstate has {len(history)} history blobs, expected "
                f"{n_blobs * len(slots)} ({len(slots)} slot(s) × {n_blobs})")
        # validate + stage everything before mutating any solver state
        idx = 0
        new_state = dict(self.state)
        for slot in slots:
            tree = {}
            for key in self.params:
                blobs = []
                for i, dst in enumerate(self.params[key]):
                    src = self._shape_adapt(
                        history[idx], dst.shape,
                        f"history[{idx}] (layer {key!r} blob {i}, "
                        f"slot {slot!r})")
                    idx += 1
                    blobs.append(jnp.asarray(src, dst.dtype))
                tree[key] = blobs
            new_state[slot] = tree
        if st["learned_net"]:
            # Caffe dies if the referenced model file is unreadable
            # (ReadNetParamsFromBinaryFileOrDie); resuming optimizer history
            # over fresh random weights would silently diverge.
            if not os.path.exists(st["learned_net"]):
                raise FileNotFoundError(
                    f"solverstate references learned_net "
                    f"{st['learned_net']!r}, which does not exist")
            self.load_weights(st["learned_net"])
        self.state = new_state
        self.iter = st["iter"]
