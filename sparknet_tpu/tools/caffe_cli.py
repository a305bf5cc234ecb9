"""The `caffe` command-line tool analog: train / test / time / device_query
(reference: caffe/tools/caffe.cpp — brew-function registry at :55, train at
:153, test at :222, time at :290, device_query at :110).

Usage:
  python -m sparknet_tpu.tools.caffe_cli train --solver S.prototxt \
      [--snapshot X.solverstate | --weights W.caffemodel] \
      [--devices N|all [--strategy sync|local_sgd|hierarchical] \
       [--tau T] [--hosts H]]
  python -m sparknet_tpu.tools.caffe_cli test --model M.prototxt \
      --weights W.caffemodel [--iterations 50]
  python -m sparknet_tpu.tools.caffe_cli time --model M.prototxt \
      [--iterations 50]
  python -m sparknet_tpu.tools.caffe_cli device_query

Self-sourcing data layers (Data/ImageData/WindowData/HDF5Data) feed
themselves from their configured sources — zoo train_val.prototxts run
standalone once their DBs exist.
"""

from __future__ import annotations

import argparse
import sys


def _train(args) -> int:
    from ..data.db import feed_for_net
    from ..data.prefetch import device_feed
    from ..proto import Phase, load_solver_prototxt
    from ..solvers import Solver

    sp = load_solver_prototxt(args.solver)
    _resolve_solver_net(sp, args.solver)
    if _device_count(args) > 1:
        return _train_multi(args, sp)
    if args.strategy != "sync" or args.tau != 1 or args.hosts is not None:
        # distributed flags without --devices must not silently run the
        # single-device path as if the strategy had been honored
        raise SystemExit(
            "--strategy/--tau/--hosts require --devices N|all (>1)")
    solver = Solver(sp, seed=0)
    if args.weights:
        solver.load_weights(args.weights)
        print(f"Finetuning from {args.weights}")
    if args.snapshot:
        solver.restore_caffe(args.snapshot)
        print(f"Resuming from {args.snapshot} (iter {solver.iter})")

    net_param = sp.net_param or sp.train_net_param
    solver.set_train_data(device_feed(feed_for_net(net_param, Phase.TRAIN)))
    # test feeds come from the nets the Solver actually evaluates: every
    # dedicated test_net definition when present, else the shared net
    test_sources = list(sp.test_net_param) or [net_param]
    for i, ts in enumerate(test_sources):
        try:
            factory = lambda ts=ts: feed_for_net(ts, Phase.TEST)
            factory()  # probe
            solver.set_test_data(factory, net_id=i)
        except ValueError as e:
            # the reference fails loudly when a test DB is unreadable
            # (DataLayer::DataLayerSetUp); we keep training but must not
            # drop the eval silently — a mis-pathed LMDB otherwise looks
            # like a clean run with no test scores
            print(f"WARNING: test net #{i} feed unavailable, skipping "
                  f"eval for it: {e}", file=sys.stderr)

    solver.solve()
    if sp.snapshot_prefix:
        model, _state = solver.snapshot_caffe()
        print(f"Snapshotting to {model}")
    return 0


def _device_count(args) -> int:
    """--devices N | --devices all (the `caffe train --gpu 0,1,.../all`
    device-set selection, reference: caffe/tools/caffe.cpp:81-103)."""
    spec = getattr(args, "devices", None)
    if spec is None:
        return 1
    if spec == "all":
        import jax
        return len(jax.devices())
    try:
        n = int(spec)
    except ValueError:
        raise SystemExit(f"--devices must be an integer or 'all', "
                         f"got {spec!r}")
    if n < 1:
        raise SystemExit(f"--devices must be >= 1, got {n}")
    return n


def _train_multi(args, sp) -> int:
    """Multi-device training — the P2PSync path `caffe train --gpu
    0,1,...` spins up (reference: caffe/tools/caffe.cpp:208-211 →
    parallel.cpp P2PSync::Run).  Strategy "sync" is that per-step
    gradient-averaging semantics; "local_sgd" is SparkNet's τ-step
    weight averaging (ImageNetApp.scala:100-182).  Like the reference's
    multi-GPU mode, the prototxt batch size stays PER DEVICE: each step
    consumes one feed minibatch per device (parallel.cpp:390-415 — every
    solver owns its data layer and pulls distinct batches)."""
    import math

    import numpy as np

    from ..data.db import feed_for_net
    from ..parallel import DistributedTrainer, TrainerConfig, make_mesh
    from ..parallel.mesh import put_global_tree, replicated
    from ..proto import Phase
    from ..utils.glog import log_line

    n = _device_count(args)
    if args.strategy == "hierarchical":
        from ..parallel import make_pod_mesh
        hosts = args.hosts if args.hosts is not None else max(1, n // 4)
        if hosts < 1:
            raise SystemExit(f"--hosts must be >= 1, got {hosts}")
        if n % hosts:
            raise SystemExit(
                f"--devices {n} not divisible by --hosts {hosts}")
        mesh = make_pod_mesh(hosts, n // hosts)
        topo = f"{hosts}x{n // hosts} pod"
    else:
        if args.hosts is not None:
            raise SystemExit(
                "--hosts only applies to --strategy hierarchical")
        mesh = make_mesh(n)
        topo = f"{n} devices"
    trainer = DistributedTrainer(
        sp, mesh, TrainerConfig(strategy=args.strategy, tau=args.tau),
        seed=0)
    print(f"Multi-device training: {topo}, strategy={args.strategy}, "
          f"tau={args.tau}")
    if args.weights:
        from ..solvers import Solver
        loader = Solver(sp, seed=0, jit=False)
        loader.load_weights(args.weights)
        trainer.params = put_global_tree(
            {k: [np.asarray(b) for b in v]
             for k, v in loader.params.items()}, replicated(mesh))
        print(f"Finetuning from {args.weights}")
    if args.snapshot:
        with open(args.snapshot, "rb") as f:
            if f.read(2) != b"PK":  # npz (zip) — the trainer's format
                raise SystemExit(
                    f"{args.snapshot}: --devices resume needs the npz "
                    f"snapshot a --devices run writes; .solverstate "
                    f"files are single-device (per-worker optimizer "
                    f"state is not convertible)")
        trainer.restore(args.snapshot)
        print(f"Resuming from {args.snapshot} (iter {trainer.iter})")

    net_param = sp.net_param or sp.train_net_param
    feed = feed_for_net(net_param, Phase.TRAIN)
    bpr = trainer.batches_per_round

    def host_rounds():
        while True:
            steps = []
            for _ in range(bpr):
                bs = [dict(next(feed)) for _ in range(n)]
                steps.append(
                    {k: np.concatenate([np.asarray(b[k]) for b in bs])
                     for k in bs[0]})
            yield {k: np.stack([s[k] for s in steps]) for k in steps[0]}

    # prefetch + async device_put with the trainer's round sharding, so
    # host DB reads for round R+1 overlap round R's device compute (the
    # same device_feed path the single-device _train uses); closed after
    # the loop — the producer thread over the endless generator must not
    # outlive training holding staged rounds in HBM
    rounds = trainer.input_feed(host_rounds())

    # eval runs on the trainer's shared-definition test net; dedicated
    # test_net definitions have no distributed analog here (the reference
    # tests on the root solver only in multi-GPU mode, solver.cpp Solve)
    test_feed_src = None
    if sp.test_interval:
        if sp.test_net_param:
            print("WARNING: dedicated test_net definitions are evaluated "
                  "on the shared net's definition in --devices mode",
                  file=sys.stderr)
        try:
            feed_for_net(net_param, Phase.TEST)  # probe
            test_feed_src = lambda: feed_for_net(net_param, Phase.TEST)
        except ValueError as e:
            print(f"WARNING: test feed unavailable, skipping eval: {e}",
                  file=sys.stderr)

    def eval_pass():
        ti = sp.test_iter[0] if sp.test_iter else 50
        steps = math.ceil(ti / n)  # each step scores n reference batches
        tfeed = test_feed_src()

        def gen():
            while True:
                bs = [dict(next(tfeed)) for _ in range(n)]
                yield {k: np.concatenate([np.asarray(b[k]) for b in bs])
                       for k in bs[0]}
        totals = trainer.test(gen(), steps)
        denom = totals.pop("__test_batches__", steps * n) or 1
        log_line(f"Iteration {trainer.iter}, Testing net (#0)")
        for k, v in totals.items():
            arr = np.asarray(v, np.float64) / denom
            for i, x in enumerate(arr.reshape(-1)):
                idx = f"[{i}]" if arr.ndim else ""
                log_line(f"    Test net output: {k}{idx} = {float(x):.6f}")

    max_iter = sp.max_iter or 100
    if (max_iter - trainer.iter) % args.tau:
        # a compiled round cannot stop mid-scan (same boundary semantics
        # as the trainer's snapshot-on-schedule); be loud about it
        print(f"WARNING: max_iter {max_iter} is not a multiple of "
              f"tau={args.tau} from iter {trainer.iter}; training runs "
              f"to the next round boundary "
              f"({math.ceil((max_iter - trainer.iter) / args.tau) * args.tau + trainer.iter})",
              file=sys.stderr)
    with rounds:
        while trainer.iter < max_iter:
            prev = trainer.iter
            loss = trainer.train_round(next(rounds))
            if (sp.display
                    and prev // sp.display != trainer.iter // sp.display):
                log_line(f"Iteration {trainer.iter}, loss = {loss:.6f}")
            if (test_feed_src is not None and sp.test_interval
                    and prev // sp.test_interval
                    != trainer.iter // sp.test_interval):
                eval_pass()
    if sp.snapshot_prefix:
        path = f"{sp.snapshot_prefix}_iter_{trainer.iter}.npz"
        trainer.snapshot(path)
        print(f"Snapshotting to {path}")
    print("Optimization Done.")
    return 0


def _test(args) -> int:
    import collections

    import jax
    import numpy as np

    from ..data.db import feed_for_net
    from ..graph import Net
    from ..proto import NetState, Phase, load_net_prototxt
    from ..solvers.solver import load_weights_into

    net_param = load_net_prototxt(args.model)
    net = Net(net_param, NetState(Phase.TEST))
    params = net.init(jax.random.PRNGKey(0))
    if args.weights:
        params = load_weights_into(net, params, args.weights)
    feed = feed_for_net(net_param, Phase.TEST)
    fwd = jax.jit(lambda p, b: net.apply(p, b, train=False).blobs)
    totals: dict[str, float] = collections.defaultdict(float)
    for i in range(args.iterations):
        batch = {k: np.asarray(v) for k, v in next(feed).items()}
        out = fwd(params, batch)
        parts = []
        for k, v in out.items():
            val = float(np.mean(np.asarray(v)))
            totals[k] += val
            parts.append(f"{k} = {val:.4f}")
        print(f"Batch {i}, " + ", ".join(parts))
    for k, v in totals.items():
        print(f"{k} = {v / args.iterations:.6f}")
    return 0


def _time(args) -> int:
    from .time_net import main as time_main
    argv = ["--model", args.model, "--iterations", str(args.iterations)]
    if args.per_layer:
        argv.append("--per-layer")
    return time_main(argv) or 0


def _device_query(args) -> int:
    from ..utils.profiling import device_memory_summary
    for row in device_memory_summary():
        print(f"Device:                        {row['device']}")
        print(f"Device kind:                   {row['kind']}")
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if row.get(key) is not None:
                print(f"{key + ':':<30} {row[key]}")
    return 0


def _resolve_solver_net(sp, solver_path: str) -> None:
    """Load the solver's net:/train_net:/test_net: file references into
    *_net_param (Solver::InitTrainNet/InitTestNets path resolution)."""
    from ..proto.caffe_pb import resolve_solver_nets
    try:
        resolve_solver_nets(sp, solver_path)
    except FileNotFoundError as e:
        raise SystemExit(str(e))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="caffe",
                                 description="caffe.cpp CLI analog")
    sub = ap.add_subparsers(dest="action", required=True)
    p = sub.add_parser("train")
    p.add_argument("--solver", required=True)
    p.add_argument("--snapshot", default=None)
    p.add_argument("--weights", default=None)
    p.add_argument("--devices", default=None, metavar="N|all",
                   help="train data-parallel over N devices (or 'all') — "
                        "the `caffe train --gpu 0,1,.../all` analog "
                        "(caffe.cpp:81-103); prototxt batch is per device")
    p.add_argument("--strategy",
                   choices=["sync", "local_sgd", "hierarchical"],
                   default="sync",
                   help="sync: per-step gradient averaging (P2PSync "
                        "semantics); local_sgd: tau-step weight averaging "
                        "(SparkNet rounds); hierarchical: both composed "
                        "on a (host, chip) pod mesh")
    p.add_argument("--tau", type=int, default=1,
                   help="steps per round for local_sgd / hierarchical")
    p.add_argument("--hosts", type=int, default=None,
                   help="host-axis size for --strategy hierarchical "
                        "(default: devices//4)")
    p.set_defaults(fn=_train)
    p = sub.add_parser("test")
    p.add_argument("--model", required=True)
    p.add_argument("--weights", default=None)
    p.add_argument("--iterations", type=int, default=50)
    p.set_defaults(fn=_test)
    p = sub.add_parser("time")
    p.add_argument("--model", required=True)
    p.add_argument("--iterations", type=int, default=50)
    p.add_argument("--per-layer", action="store_true")
    p.set_defaults(fn=_time)
    p = sub.add_parser("device_query")
    p.set_defaults(fn=_device_query)
    args = ap.parse_args(argv)
    from ..utils.compile_cache import use_compile_cache
    use_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
