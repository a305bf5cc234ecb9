"""Distributed-training tests on the virtual 8-device CPU mesh — the
multi-node coverage the reference never had (SURVEY.md §4.1: "there are no
distributed tests"; the CPU_ONLY analog per §4.3)."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.data import make_minibatches
from sparknet_tpu.models import lenet
from sparknet_tpu.parallel import DistributedTrainer, TrainerConfig, make_mesh
from sparknet_tpu.proto import load_solver_prototxt_with_net
from sparknet_tpu.solvers import Solver

SOLVER_TXT = 'base_lr: 0.05\nmomentum: 0.9\nlr_policy: "fixed"\n'


def synth(np_rng, n, shape=(1, 28, 28), num_classes=10):
    labels = np_rng.integers(0, num_classes, size=n)
    x = np_rng.normal(scale=0.3, size=(n, *shape)).astype(np.float32)
    for k in range(num_classes):
        x[labels == k, :, k % shape[1], :] += 2.0
    return x, labels.astype(np.float32)


def round_batches(np_rng, tau, global_batch):
    x, y = synth(np_rng, tau * global_batch)
    return {"data": x.reshape(tau, global_batch, 1, 28, 28),
            "label": y.reshape(tau, global_batch)}


def test_mesh_shapes():
    mesh = make_mesh(8)
    assert mesh.shape == {"data": 8, "model": 1}
    mesh2 = make_mesh(8, model_parallel=2)
    assert mesh2.shape == {"data": 4, "model": 2}
    with pytest.raises(ValueError):
        make_mesh(6, model_parallel=4)


def test_mesh_of_more_devices_than_exist_is_an_error():
    """Never a silently narrower mesh: `--workers 8` on four chips must
    not train four workers."""
    import jax
    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"have {n}"):
        make_mesh(n + 1)


@pytest.mark.parametrize("strategy", ["sync", "local_sgd"])
def test_distributed_loss_decreases(strategy, np_rng):
    # lr 0.01: local_sgd workers see batch 4 — 0.05 genuinely diverges there
    sp = load_solver_prototxt_with_net(
        'base_lr: 0.01\nmomentum: 0.9\nlr_policy: "fixed"\n', lenet(32, 32))
    mesh = make_mesh(8)
    tr = DistributedTrainer(sp, mesh, TrainerConfig(strategy=strategy, tau=5),
                            seed=0)
    assert tr.n_workers == 8
    losses = [tr.train_round(round_batches(np_rng, 5, 32)) for _ in range(6)]
    assert losses[0] == pytest.approx(np.log(10), rel=0.3)
    assert losses[-1] < 0.5 * losses[0]
    assert tr.iter == 30


def test_sync_matches_single_process_bigbatch(np_rng):
    """Gradient-pmean over 4 shards of batch 32 == single-device batch 32
    (the correctness invariant P2PSync relies on)."""
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(32, 32))
    x, y = synth(np_rng, 64)

    single = Solver(sp, seed=0)
    mesh = make_mesh(4)
    tr = DistributedTrainer(sp, mesh, TrainerConfig(strategy="sync", tau=1),
                            seed=0)
    # same seed -> identical initial params
    np.testing.assert_allclose(np.asarray(single.params["conv1"][0]),
                               np.asarray(tr.params["conv1"][0]))
    single.set_train_data(itertools.cycle(
        [{"data": x[i:i + 32], "label": y[i:i + 32]} for i in range(0, 64, 32)]))
    single.step(2)
    for i in range(0, 64, 32):
        tr.train_round({"data": x[i:i + 32][None], "label": y[i:i + 32][None]})

    for k in single.params:
        for a, b in zip(single.params[k], tr.params[k]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


def test_local_sgd_weight_averaging_semantics(np_rng):
    """After one round of τ=3, params must equal the mean of what each
    worker would have computed alone on its shard (SparkNet's
    WeightCollection.add / scalarDivide invariant)."""
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(8, 8))
    mesh = make_mesh(2)
    tr = DistributedTrainer(sp, mesh, TrainerConfig(strategy="local_sgd",
                                                    tau=3), seed=0)
    init_params = jax.tree_util.tree_map(np.asarray, tr.params)
    batches = round_batches(np_rng, 3, 16)
    tr.train_round(batches)

    # replay each worker locally with a plain Solver starting from the same
    # params and its own data shard + the same per-worker rng stream
    rng0 = jax.random.PRNGKey(0)
    _, run_rng = jax.random.split(rng0)          # trainer's self._rng
    round_rng, _ = jax.random.split(run_rng)     # rng passed into round 1
    worker_params = []
    for w in range(2):
        s = Solver(sp, seed=0)
        s.params = jax.tree_util.tree_map(jnp.asarray, init_params)
        shard = {k: v[:, 8 * w:8 * (w + 1)] for k, v in batches.items()}
        feed = iter([{k: v[t] for k, v in shard.items()} for t in range(3)])
        s.set_train_data(feed)
        # mirror the trainer's rng chain for this worker
        wrng = jax.random.fold_in(round_rng, w)
        for _ in range(3):
            wrng, sub = jax.random.split(wrng)
            batch = next(s._train_iter)
            stacked = {k: jnp.asarray(v)[None] for k, v in batch.items()}
            s.params, s.state, _ = s._step(s.params, s.state, s.iter, stacked, sub)
            s.iter += 1
        worker_params.append(s.params)

    for k in worker_params[0]:
        for i, blob in enumerate(worker_params[0][k]):
            avg = (np.asarray(blob) + np.asarray(worker_params[1][k][i])) / 2
            np.testing.assert_allclose(np.asarray(tr.params[k][i]), avg,
                                       rtol=2e-4, atol=2e-5)


def test_distributed_test_aggregation(np_rng):
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(32, 32))
    mesh = make_mesh(8)
    tr = DistributedTrainer(sp, mesh, TrainerConfig(strategy="sync"), seed=0)
    x, y = synth(np_rng, 64)
    feed = itertools.cycle([{"data": x[i:i + 32], "label": y[i:i + 32]}
                            for i in range(0, 64, 32)])
    scores = tr.test(feed, num_steps=2)
    assert "accuracy" in scores
    # raw worker-batch sums + count (ImageNetApp.scala:139-140 contract)
    assert scores["__test_batches__"] == 16  # 8 workers × 2 steps
    assert 0.0 <= scores["accuracy"] / scores["__test_batches__"] <= 1.0


def test_trainer_snapshot_restore(tmp_path, np_rng):
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(16, 16))
    mesh = make_mesh(4)
    cfg = TrainerConfig(strategy="local_sgd", tau=2)
    tr = DistributedTrainer(sp, mesh, cfg, seed=0)
    tr.train_round(round_batches(np_rng, 2, 16))
    p = str(tmp_path / "dist.npz")
    tr.snapshot(p)
    tr2 = DistributedTrainer(sp, mesh, cfg, seed=5)
    tr2.restore(p)
    assert tr2.iter == 2
    np.testing.assert_allclose(np.asarray(tr2.params["conv1"][0]),
                               np.asarray(tr.params["conv1"][0]))
    # momentum state restored per-worker
    chex_tree = jax.tree_util.tree_leaves(tr2.state)
    assert all(l.shape[0] == 4 for l in chex_tree)


def test_restore_rejects_mismatched_strategy_or_workers(tmp_path, np_rng):
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(16, 16))
    tr = DistributedTrainer(sp, make_mesh(4),
                            TrainerConfig(strategy="sync"), seed=0)
    p = str(tmp_path / "sync.npz")
    tr.snapshot(p)
    wrong_strategy = DistributedTrainer(
        sp, make_mesh(4), TrainerConfig(strategy="local_sgd"), seed=0)
    with pytest.raises(ValueError, match="strategy"):
        wrong_strategy.restore(p)
    wrong_mesh = DistributedTrainer(
        sp, make_mesh(8), TrainerConfig(strategy="sync"), seed=0)
    with pytest.raises(ValueError, match="workers"):
        wrong_mesh.restore(p)


def test_eval_batch_divisibility(np_rng):
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(8, 8))
    tr = DistributedTrainer(sp, make_mesh(8), TrainerConfig(), seed=0)
    feed = iter([{"data": np.zeros((60, 1, 28, 28), np.float32),
                  "label": np.zeros(60, np.float32)}])
    with pytest.raises(ValueError, match="not divisible"):
        tr.test(feed, 1)


def test_batch_divisibility_validation(np_rng):
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(8, 8))
    tr = DistributedTrainer(sp, make_mesh(8), TrainerConfig(tau=1), seed=0)
    with pytest.raises(ValueError, match="not divisible"):
        tr.train_round({"data": np.zeros((1, 12, 1, 28, 28), np.float32),
                        "label": np.zeros((1, 12), np.float32)})
    with pytest.raises(ValueError, match="!= tau"):
        tr.train_round({"data": np.zeros((2, 16, 1, 28, 28), np.float32),
                        "label": np.zeros((2, 16), np.float32)})


def test_iter_size_matches_bigbatch(np_rng):
    """iter_size accumulation inside the compiled round: 2 micro-batches of
    B accumulated then normalized == one batch of 2B (solver.cpp:221-224
    semantics; fixes ADVICE r1 #1)."""
    x, y = synth(np_rng, 32)
    mesh = make_mesh(4)

    sp2 = load_solver_prototxt_with_net(
        SOLVER_TXT + "iter_size: 2\n", lenet(16, 16))
    tr2 = DistributedTrainer(sp2, mesh, TrainerConfig(strategy="sync", tau=1),
                             seed=0)
    assert tr2.batches_per_round == 2
    tr2.train_round({"data": x.reshape(2, 16, 1, 28, 28),
                     "label": y.reshape(2, 16)})

    sp1 = load_solver_prototxt_with_net(SOLVER_TXT, lenet(32, 32))
    tr1 = DistributedTrainer(sp1, mesh, TrainerConfig(strategy="sync", tau=1),
                             seed=0)
    tr1.train_round({"data": x.reshape(1, 32, 1, 28, 28),
                     "label": y.reshape(1, 32)})

    for k in tr1.params:
        for a, b in zip(tr1.params[k], tr2.params[k]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def test_iter_size_local_sgd_runs(np_rng):
    sp = load_solver_prototxt_with_net(
        SOLVER_TXT + "iter_size: 2\n", lenet(16, 16))
    tr = DistributedTrainer(sp, make_mesh(4),
                            TrainerConfig(strategy="local_sgd", tau=2), seed=0)
    assert tr.batches_per_round == 4
    x, y = synth(np_rng, 4 * 16)
    loss = tr.train_round({"data": x.reshape(4, 16, 1, 28, 28),
                           "label": y.reshape(4, 16)})
    assert np.isfinite(loss)
    assert tr.iter == 2  # iter counts steps, not micro-batches


def test_trainer_snapshot_on_schedule(tmp_path, np_rng):
    """sp.snapshot fires at round boundaries when an iter multiple is
    crossed (reference: solver.cpp:270-277)."""
    import os

    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(8, 8))
    sp.snapshot = 4
    sp.snapshot_prefix = str(tmp_path / "sched")
    tr = DistributedTrainer(sp, make_mesh(4),
                            TrainerConfig(strategy="sync", tau=2), seed=0)
    for _ in range(2):
        tr.train_round(round_batches(np_rng, 2, 8))
    assert os.path.exists(str(tmp_path / "sched") + "_iter_4.npz")


def test_sync_state_only_pmean_preserves_replication(np_rng):
    """BN-bearing net under sync DP: running stats stay replicated while
    only state blobs ride the per-step collective (VERDICT r1 weak #7)."""
    from sparknet_tpu.models.dsl import java_data_layer, layer, net_param

    net = net_param("bn_net", [
        java_data_layer("input", ["data", "label"], None, (16, 1, 8, 8),
                        (16,)),
        layer("conv1", "Convolution", ["data"], ["conv1"],
              convolution_param={"num_output": 4, "kernel_size": 3,
                                 "weight_filler": {"type": "xavier"}}),
        layer("bn1", "BatchNorm", ["conv1"], ["bn1"]),
        layer("relu1", "ReLU", ["bn1"], ["bn1r"]),
        layer("ip", "InnerProduct", ["bn1r"], ["ip"],
              inner_product_param={"num_output": 10,
                                   "weight_filler": {"type": "xavier"}}),
        layer("loss", "SoftmaxWithLoss", ["ip", "label"], ["loss"]),
    ])
    sp = load_solver_prototxt_with_net(SOLVER_TXT, net)
    tr = DistributedTrainer(sp, make_mesh(4),
                            TrainerConfig(strategy="sync", tau=2), seed=0)
    x, y = synth(np_rng, 32, shape=(1, 8, 8))
    loss = tr.train_round({"data": x.reshape(2, 16, 1, 8, 8),
                           "label": y.reshape(2, 16)})
    assert np.isfinite(loss)
    # replicated out_spec holds: all per-device copies of the BN stats agree
    bn_key = next(k for k in tr.params if "bn" in k)
    for blob in tr.params[bn_key]:
        shards = [np.asarray(s.data) for s in blob.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_allclose(shards[0], s, rtol=1e-6)


def test_device_preprocess_round(np_rng):
    """TrainerConfig.device_preprocess crops/mirrors/mean-subtracts inside
    the compiled round: the net sees crop-sized inputs while the feed
    ships raw full-size images (the TPU-native feed-bottleneck fix)."""
    from sparknet_tpu.models.dsl import java_data_layer, layer, net_param
    from sparknet_tpu.parallel import device_crop_mirror_mean

    crop, full = 6, 8
    net = net_param("devpre", [
        java_data_layer("input", ["data", "label"], None,
                        (8, 1, crop, crop), (8,)),
        layer("ip", "InnerProduct", ["data"], ["ip"],
              inner_product_param={"num_output": 4,
                                   "weight_filler": {"type": "xavier"}}),
        layer("loss", "SoftmaxWithLoss", ["ip", "label"], ["loss"]),
    ])
    sp = load_solver_prototxt_with_net(SOLVER_TXT, net)
    mean = np_rng.normal(size=(1, full, full)).astype(np.float32)
    for strategy in ("local_sgd", "sync"):
        tr = DistributedTrainer(
            sp, make_mesh(2),
            TrainerConfig(strategy=strategy, tau=2,
                          device_preprocess=device_crop_mirror_mean(
                              crop, mirror=True, mean=mean)), seed=0)
        x = np_rng.normal(size=(2, 8, 1, full, full)).astype(np.float32)
        y = np_rng.integers(0, 4, size=(2, 8)).astype(np.float32)
        loss = tr.train_round({"data": x, "label": y})
        assert np.isfinite(loss), strategy


def test_device_preprocess_deterministic_semantics(np_rng):
    """With crop == input size and mirror off, the on-device path reduces
    to exactly the host path's mean subtraction — same round result."""
    from sparknet_tpu.models.dsl import java_data_layer, layer, net_param
    from sparknet_tpu.parallel import device_crop_mirror_mean

    size = 6
    net = net_param("devpre_eq", [
        java_data_layer("input", ["data", "label"], None,
                        (8, 1, size, size), (8,)),
        layer("ip", "InnerProduct", ["data"], ["ip"],
              inner_product_param={"num_output": 3,
                                   "weight_filler": {"type": "xavier"}}),
        layer("loss", "SoftmaxWithLoss", ["ip", "label"], ["loss"]),
    ])
    sp = load_solver_prototxt_with_net(SOLVER_TXT, net)
    mean = np_rng.normal(size=(1, size, size)).astype(np.float32)
    x = np_rng.normal(size=(2, 8, 1, size, size)).astype(np.float32)
    y = np_rng.integers(0, 3, size=(2, 8)).astype(np.float32)

    tr_host = DistributedTrainer(
        sp, make_mesh(2), TrainerConfig(strategy="sync", tau=2), seed=0)
    loss_host = tr_host.train_round({"data": x - mean, "label": y})

    tr_dev = DistributedTrainer(
        sp, make_mesh(2),
        TrainerConfig(strategy="sync", tau=2,
                      device_preprocess=device_crop_mirror_mean(
                          size, mirror=False, mean=mean)), seed=0)
    loss_dev = tr_dev.train_round({"data": x, "label": y})
    np.testing.assert_allclose(float(loss_host), float(loss_dev), rtol=1e-5)
    for k in tr_host.params:
        for a, b in zip(tr_host.params[k], tr_dev.params[k]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


def test_device_preprocess_crop_sized_mean(np_rng):
    """A crop-sized (pycaffe mean-file style) mean works on the device
    path, subtracted after cropping; a nonsense shape fails clearly."""
    import pytest

    from sparknet_tpu.parallel import device_crop_mirror_mean

    crop, full = 4, 6
    mean_c = np_rng.normal(size=(1, crop, crop)).astype(np.float32)
    pre = device_crop_mirror_mean(crop, mirror=False, mean=mean_c)
    x = np_rng.normal(size=(2, 3, 1, full, full)).astype(np.float32)
    import jax
    out = pre({"data": x}, jax.random.PRNGKey(0))["data"]
    assert out.shape == (2, 3, 1, crop, crop)

    bad = device_crop_mirror_mean(crop, mean=np.zeros((1, 5, 5), np.float32))
    with pytest.raises(ValueError, match="matches neither"):
        bad({"data": x}, jax.random.PRNGKey(0))


@pytest.mark.parametrize("mirror", [True, False])
@pytest.mark.parametrize("mean", ["none", "channel", "planes", "image",
                                  "crop"])
@pytest.mark.parametrize("dtype,path", [("uint8", "select"),
                                        ("float32", "gather")])
def test_device_preprocess_is_the_numpy_oracle_bit_for_bit(
        np_rng, monkeypatch, dtype, path, mean, mirror):
    """``device_crop_mirror_mean`` against numpy on the trainer's own
    draws: per-channel values, the same broadcast to an image, a
    full-size mean image (subtracted at each sample's window) and a
    crop-sized one (mirrored with its sample), for a raw uint8 feed (the
    selection) and a float32 one (the gather); the choice is counted."""
    from sparknet_tpu.parallel import device_crop_mirror_mean
    from sparknet_tpu.utils import telemetry

    crop, full, c = 28, 32, 3
    chan = np.asarray([104.0, 117.0, 123.0], np.float32).reshape(c, 1, 1)
    mean_arr = {
        "none": None, "channel": chan,
        "planes": np.broadcast_to(chan, (c, full, full)),
        "image": np_rng.normal(110, 30, (c, full, full)).astype(np.float32),
        "crop": np_rng.normal(110, 30, (c, crop, crop)).astype(np.float32),
    }[mean]
    x = np_rng.integers(0, 256, size=(2, 4, c, full, full)).astype(dtype)
    rng = jax.random.PRNGKey(5)
    for k in ("SPARKNET_TELEMETRY", "SPARKNET_TRACE_DIR",
              "SPARKNET_METRICS_SNAP"):
        monkeypatch.delenv(k, raising=False)
    telemetry.reset()
    try:
        pre = device_crop_mirror_mean(crop, mirror=mirror, mean=mean_arr)
        out = np.asarray(jax.jit(pre)({"data": x}, rng)["data"])
        fam = telemetry.get_registry().snapshot()["augment_lowering_total"]
        assert {s["labels"]["path"]: s["value"]
                for s in fam["samples"]} == {path: 1.0}
    finally:
        telemetry.reset()

    ky, kx, kf = jax.random.split(rng, 3)
    ys = np.asarray(jax.random.randint(ky, (8,), 0, full - crop + 1))
    xs = np.asarray(jax.random.randint(kx, (8,), 0, full - crop + 1))
    flips = (np.asarray(jax.random.bernoulli(kf, 0.5, (8,))) if mirror
             else np.zeros(8, bool))
    flat = x.reshape(8, c, full, full).astype(np.float32)
    if mean in ("channel", "planes", "image"):
        flat = flat - mean_arr
    want = np.empty((8, c, crop, crop), np.float32)
    for i in range(8):
        win = flat[i, :, ys[i]:ys[i] + crop, xs[i]:xs[i] + crop]
        if mean == "crop":
            win = win - mean_arr
        want[i] = win[:, :, ::-1] if flips[i] else win
    assert out.dtype == np.float32 and out.shape == (2, 4, c, crop, crop)
    assert np.array_equal(out.reshape(want.shape), want)
    assert not mirror or 0 < flips.sum() < 8


def test_uneven_partition_eval_matches_per_worker_truth(np_rng):
    """Reference semantics for unequal partitions (each zipPartitions
    worker tests its OWN `len` batches — ImageNetApp.scala:108-141): the
    masked SPMD eval must equal per-worker truth computed one partition
    at a time on a 1-device mesh."""
    from sparknet_tpu.apps.common import eval_feed
    from sparknet_tpu.data.partition import PartitionedDataset

    def mk_items(n, seed):
        r = np.random.default_rng(seed)
        return [(r.normal(size=(1, 28, 28)).astype(np.float32),
                 float(r.integers(0, 10))) for _ in range(n)]

    # sizes 6,4,4,2 with batch 2 -> per-worker steps 3,2,2,1; lockstep 3
    parts = [mk_items(6, 0), mk_items(4, 1), mk_items(4, 2), mk_items(2, 3)]
    ds = PartitionedDataset(parts)
    factory, steps = eval_feed(ds, per_worker_batch=2)
    assert steps == 3

    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(8, 8))
    tr = DistributedTrainer(sp, make_mesh(4), TrainerConfig(), seed=0)
    totals = tr.test(factory(), steps)
    assert totals["__test_batches__"] == 8.0  # 3+2+2+1

    # ground truth: a single-worker mesh scores each partition's batches
    sp1 = load_solver_prototxt_with_net(SOLVER_TXT, lenet(2, 2))
    tr1 = DistributedTrainer(sp1, make_mesh(1), TrainerConfig(), seed=0)
    for k in tr.params:  # identical weights
        for a, b in zip(tr.params[k], tr1.params[k]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    truth: dict = {}
    for p in parts:
        for t in range(len(p) // 2):
            recs = p[t * 2:(t + 1) * 2]
            feed1 = iter([{
                "data": np.stack([r[0] for r in recs]),
                "label": np.asarray([r[1] for r in recs], np.float32)}])
            s = tr1.test(feed1, 1)
            for k, v in s.items():
                truth[k] = truth.get(k, 0.0) + v
    assert truth.pop("__test_batches__") == 8.0
    for k, v in truth.items():
        np.testing.assert_allclose(totals[k], v, rtol=1e-5, atol=1e-6,
                                   err_msg=k)


BN_DP_NET = """
name: "bn_dp"
layer { name: "data" type: "Input" top: "data"
  input_param { shape { dim: 8 dim: 1 dim: 12 dim: 12 } } }
layer { name: "label" type: "Input" top: "label"
  input_param { shape { dim: 8 } } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 4 kernel_size: 3
    weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "bn1" type: "BatchNorm" bottom: "conv1" top: "bn1" }
layer { name: "sc1" type: "Scale" bottom: "bn1" top: "sc1"
  scale_param { bias_term: true } }
layer { name: "relu1" type: "ReLU" bottom: "sc1" top: "sc1" }
layer { name: "ip" type: "InnerProduct" bottom: "sc1" top: "ip"
  inner_product_param { num_output: 5 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" }
"""


def test_local_sgd_averages_bn_running_stats(np_rng):
    """SparkNet's weight averaging iterates EVERY blob — BatchNorm
    running stats included (WeightCollection.add, Net.scala:27-46 sums
    all weights of all layers; the driver then scalarDivides).  The
    local_sgd round must do the same: after one round the BN blobs equal
    the mean of the per-worker stats, which genuinely differ across data
    shards."""
    from sparknet_tpu.proto import load_net_prototxt

    sp = load_solver_prototxt_with_net(SOLVER_TXT,
                                       load_net_prototxt(BN_DP_NET))
    mesh = make_mesh(2)
    tau = 2
    tr = DistributedTrainer(sp, mesh, TrainerConfig(strategy="local_sgd",
                                                    tau=tau), seed=0)
    init_params = jax.tree_util.tree_map(np.asarray, tr.params)
    batches = {
        "data": np_rng.normal(size=(tau, 16, 1, 12, 12)).astype(np.float32),
        "label": np_rng.integers(0, 5, size=(tau, 16)).astype(np.float32),
    }
    tr.train_round(batches)

    # replay each worker locally with a plain Solver from the same params
    # and its own shard + the trainer's per-worker rng stream
    rng0 = jax.random.PRNGKey(0)
    _, run_rng = jax.random.split(rng0)          # trainer's self._rng
    round_rng, _ = jax.random.split(run_rng)     # rng passed into round 1
    worker_params = []
    for w in range(2):
        s = Solver(sp, seed=0)
        s.params = jax.tree_util.tree_map(jnp.asarray, init_params)
        shard = {k: v[:, 8 * w:8 * (w + 1)] for k, v in batches.items()}
        feed = iter([{k: v[t] for k, v in shard.items()}
                     for t in range(tau)])
        s.set_train_data(feed)
        wrng = jax.random.fold_in(round_rng, w)
        for _ in range(tau):
            wrng, sub = jax.random.split(wrng)
            batch = next(s._train_iter)
            stacked = {k: jnp.asarray(v)[None] for k, v in batch.items()}
            s.params, s.state, _ = s._step(s.params, s.state, s.iter,
                                           stacked, sub)
            s.iter += 1
        worker_params.append(s.params)

    # the running mean/var genuinely diverged across shards (averaging is
    # non-trivial), while the scale factor advanced identically
    for i in (0, 1):
        assert not np.allclose(np.asarray(worker_params[0]["bn1"][i]),
                               np.asarray(worker_params[1]["bn1"][i]))
    # every blob of every layer — BN stats and scale factor included —
    # equals the per-worker mean
    for k in worker_params[0]:
        for i, blob in enumerate(worker_params[0][k]):
            avg = (np.asarray(blob)
                   + np.asarray(worker_params[1][k][i])) / 2
            np.testing.assert_allclose(np.asarray(tr.params[k][i]), avg,
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=f"{k}[{i}]")


# ---------------------------------------------------------------------------
# Hierarchical two-level DP: (host, chip) mesh — per-step grad psum over
# chips (P2PSync tier, parallel.cpp:271-360) x tau-step weight averaging
# over hosts (Spark round tier, ImageNetApp.scala:100-182), composed.
# ---------------------------------------------------------------------------

from sparknet_tpu.parallel import make_pod_mesh


def _tree_allclose(a, b, rtol=2e-4, atol=2e-5):
    for k in a:
        for i, blob in enumerate(a[k]):
            np.testing.assert_allclose(
                np.asarray(blob), np.asarray(b[k][i]), rtol=rtol, atol=atol,
                err_msg=f"{k}[{i}]")


def test_pod_mesh_shapes():
    mesh = make_pod_mesh(2, 4)
    assert mesh.shape == {"host": 2, "chip": 4}
    with pytest.raises(ValueError):
        make_pod_mesh(3, 4)  # 12 > 8 devices
    with pytest.raises(ValueError, match="hierarchical"):
        DistributedTrainer(
            load_solver_prototxt_with_net(SOLVER_TXT, lenet(8, 8)),
            make_mesh(8), TrainerConfig(strategy="hierarchical"))


def test_hierarchical_loss_decreases(np_rng):
    sp = load_solver_prototxt_with_net(
        'base_lr: 0.01\nmomentum: 0.9\nlr_policy: "fixed"\n', lenet(32, 32))
    tr = DistributedTrainer(sp, make_pod_mesh(2, 4),
                            TrainerConfig(strategy="hierarchical", tau=5),
                            seed=0)
    assert tr.n_workers == 8 and tr.n_hosts == 2 and tr.n_chips == 4
    losses = [tr.train_round(round_batches(np_rng, 5, 32)) for _ in range(6)]
    assert losses[0] == pytest.approx(np.log(10), rel=0.3)
    assert losses[-1] < 0.5 * losses[0]
    assert tr.iter == 30


def test_hierarchical_one_host_collapses_to_sync(np_rng):
    """A 1xN pod has no host tier to average over: every round must match
    the flat per-step-gradient strategy exactly (momentum included — the
    single host owns the one optimizer state, like sync's)."""
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(16, 16))
    hier = DistributedTrainer(sp, make_pod_mesh(1, 4),
                              TrainerConfig(strategy="hierarchical", tau=2),
                              seed=0)
    flat = DistributedTrainer(sp, make_mesh(4),
                              TrainerConfig(strategy="sync", tau=2), seed=0)
    for _ in range(3):
        batches = round_batches(np_rng, 2, 16)
        lh = hier.train_round(batches)
        lf = flat.train_round(batches)
        assert lh == pytest.approx(lf, rel=1e-5)
    _tree_allclose(hier.params, flat.params)


def test_hierarchical_one_chip_collapses_to_local_sgd(np_rng):
    """An Nx1 pod has no chip tier to psum over: every round must match
    flat tau-step weight averaging exactly (per-worker == per-host
    optimizer states)."""
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(8, 8))
    hier = DistributedTrainer(sp, make_pod_mesh(4, 1),
                              TrainerConfig(strategy="hierarchical", tau=3),
                              seed=0)
    flat = DistributedTrainer(sp, make_mesh(4),
                              TrainerConfig(strategy="local_sgd", tau=3),
                              seed=0)
    for _ in range(2):
        batches = round_batches(np_rng, 3, 16)
        lh = hier.train_round(batches)
        lf = flat.train_round(batches)
        assert lh == pytest.approx(lf, rel=1e-5)
    _tree_allclose(hier.params, flat.params)


def test_hierarchical_tau1_plain_sgd_collapses_to_flat_sync(np_rng):
    """With tau=1 and a stateless rule (momentum 0), averaging per-host
    UPDATES equals updating with the all-device mean gradient, so a 2x4
    pod matches flat 8-way sync across rounds (the update is linear in
    the gradient)."""
    sp = load_solver_prototxt_with_net(
        'base_lr: 0.05\nlr_policy: "fixed"\nweight_decay: 0.001\n',
        lenet(16, 16))
    hier = DistributedTrainer(sp, make_pod_mesh(2, 4),
                              TrainerConfig(strategy="hierarchical", tau=1),
                              seed=0)
    flat = DistributedTrainer(sp, make_mesh(8),
                              TrainerConfig(strategy="sync", tau=1), seed=0)
    for _ in range(3):
        batches = round_batches(np_rng, 1, 16)
        hier.train_round(batches)
        flat.train_round(batches)
    _tree_allclose(hier.params, flat.params)


def test_hierarchical_composition_replay(np_rng):
    """The definitional test: a 2x2 tau=2 hierarchical round equals, per
    host, a flat 2-chip sync trainer run on that host's rows for tau
    rounds, with the two hosts' results then averaged by hand."""
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(8, 8))
    tau = 2
    hier = DistributedTrainer(sp, make_pod_mesh(2, 2),
                              TrainerConfig(strategy="hierarchical",
                                            tau=tau), seed=0)
    init = jax.tree_util.tree_map(np.asarray, hier.params)
    batches = round_batches(np_rng, tau, 16)  # [tau, 16, ...]
    hier.train_round(batches)

    host_params = []
    for h in range(2):
        sub = DistributedTrainer(sp, make_mesh(2),
                                 TrainerConfig(strategy="sync", tau=1),
                                 seed=0)
        sub.params = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x), init)
        rows = {k: v[:, 8 * h:8 * (h + 1)] for k, v in batches.items()}
        for t in range(tau):
            sub.train_round({k: v[t][None] for k, v in rows.items()})
        host_params.append(jax.tree_util.tree_map(np.asarray, sub.params))

    avg = jax.tree_util.tree_map(
        lambda a, b: (a + b) / 2, host_params[0], host_params[1])
    _tree_allclose(hier.params, avg)


def test_hierarchical_bn_one_host_matches_sync(np_rng):
    """BatchNorm running stats under the chip tier follow sync's
    per-step re-averaging (state_keys pmean over chips)."""
    from sparknet_tpu.proto import load_net_prototxt
    sp = load_solver_prototxt_with_net(SOLVER_TXT,
                                       load_net_prototxt(BN_DP_NET))
    hier = DistributedTrainer(sp, make_pod_mesh(1, 2),
                              TrainerConfig(strategy="hierarchical", tau=2),
                              seed=0)
    flat = DistributedTrainer(sp, make_mesh(2),
                              TrainerConfig(strategy="sync", tau=2), seed=0)
    batches = {
        "data": np_rng.normal(size=(2, 16, 1, 12, 12)).astype(np.float32),
        "label": np_rng.integers(0, 5, size=(2, 16)).astype(np.float32),
    }
    hier.train_round(batches)
    flat.train_round(batches)
    _tree_allclose(hier.params, flat.params)


def test_hierarchical_snapshot_restore(tmp_path, np_rng):
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(8, 8))
    cfg = TrainerConfig(strategy="hierarchical", tau=2)
    tr = DistributedTrainer(sp, make_pod_mesh(2, 2), cfg, seed=0)
    tr.train_round(round_batches(np_rng, 2, 16))
    path = str(tmp_path / "hier.npz")
    tr.snapshot(path)

    tr2 = DistributedTrainer(sp, make_pod_mesh(2, 2), cfg, seed=1)
    tr2.restore(path)
    assert tr2.iter == tr.iter
    _tree_allclose(tr2.params, tr.params, rtol=0, atol=0)
    # deterministic net: the next round from restored state matches
    batches = round_batches(np_rng, 2, 16)
    assert tr.train_round(batches) == pytest.approx(
        tr2.train_round(batches), rel=1e-6)

    # a different host tiling must be refused (per-host optimizer state)
    tr41 = DistributedTrainer(sp, make_pod_mesh(4, 1), cfg, seed=0)
    with pytest.raises(ValueError, match="hosts"):
        tr41.restore(path)


def test_vmap_local_sgd_matches_mesh_trainer(np_rng):
    """tools/learning_proxy.py runs 8-way local SGD on ONE chip by
    vmapping the per-worker update over a stacked param/state axis and
    averaging at the tau boundary; this pins that form against the mesh
    trainer's local_sgd round (deterministic net, identical data
    assignment), so the proxy's 8-way numbers speak for the mesh
    implementation."""
    from sparknet_tpu.graph.net import Net
    from sparknet_tpu.proto import NetState, Phase
    from sparknet_tpu.solvers.step import make_step_fns
    from sparknet_tpu.solvers.update_rules import make_update_rule

    W, tau, b = 2, 3, 8
    sp = load_solver_prototxt_with_net(SOLVER_TXT, lenet(W * b, W * b))
    tr = DistributedTrainer(sp, make_mesh(W),
                            TrainerConfig(strategy="local_sgd", tau=tau),
                            seed=0)
    batches = round_batches(np_rng, tau, W * b)
    tr.train_round(batches)

    # the vmap form, exactly as the proxy builds it
    net = Net(sp.net_param or sp.train_net_param, NetState(Phase.TRAIN))
    rule = make_update_rule(sp)
    rng0 = jax.random.PRNGKey(0)
    _, init_rng = jax.random.split(rng0)     # the trainer's init chain
    params0 = net.init(init_rng)
    state0 = rule.init(params0)
    _, local_update, _ = make_step_fns(
        sp, net, rule, net.lr_mult_tree(params0),
        net.decay_mult_tree(params0), in_scan=True)
    vm = jax.vmap(local_update, in_axes=(0, 0, None, 0, 0))

    stack = lambda t: jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (W,) + x.shape), t)
    wparams, wstate = stack(params0), stack(state0)
    for t in range(tau):
        # worker w sees rows [w*b:(w+1)*b] — the shard_map row split
        micro = {k: jnp.asarray(v[t]).reshape((W, 1, b) + v[t].shape[1:])
                 for k, v in batches.items()}
        wparams, wstate, _ = vm(wparams, wstate, t,
                                micro, jax.random.split(rng0, W))
    avg = jax.tree_util.tree_map(lambda x: x.mean(0), wparams)
    _tree_allclose(tr.params, avg)


def test_vmap_hierarchical_matches_mesh_trainer(np_rng):
    """make_host_step (tools/learning_proxy.py) — the single-chip vmap
    restatement of the hierarchical strategy's per-step chip-mean update
    — pinned against the mesh trainer's (host, chip) round, so the
    proxy's hierarchical curve speaks for the mesh implementation."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "learning_proxy",
        os.path.join(os.path.dirname(__file__), os.pardir,
                     "tools", "learning_proxy.py"))
    lp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lp)

    from sparknet_tpu.graph.net import Net
    from sparknet_tpu.proto import NetState, Phase
    from sparknet_tpu.solvers.step import make_step_fns
    from sparknet_tpu.solvers.update_rules import make_update_rule

    H, C, tau, b = 2, 2, 2, 4
    sp = load_solver_prototxt_with_net(SOLVER_TXT,
                                       lenet(H * C * b, H * C * b))
    tr = DistributedTrainer(sp, make_pod_mesh(H, C),
                            TrainerConfig(strategy="hierarchical",
                                          tau=tau), seed=0)
    batches = round_batches(np_rng, tau, H * C * b)
    tr.train_round(batches)

    net = Net(sp.net_param or sp.train_net_param, NetState(Phase.TRAIN))
    rule = make_update_rule(sp)
    rng0 = jax.random.PRNGKey(0)
    _, init_rng = jax.random.split(rng0)     # the trainer's init chain
    params0 = net.init(init_rng)
    state0 = rule.init(params0)
    lr_m = net.lr_mult_tree(params0)
    dc_m = net.decay_mult_tree(params0)
    _, _, accum = make_step_fns(sp, net, rule, lr_m, dc_m, in_scan=True)
    host_step = lp.make_host_step(sp, rule, lr_m, dc_m, accum)
    vm_host = jax.vmap(host_step, in_axes=(0, 0, None, 0, 0))

    stack = lambda t: jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (H,) + x.shape), t)
    hparams, hstate = stack(params0), stack(state0)
    for t in range(tau):
        # mesh batch rows shard host-major over (host, chip)
        micro = {k: jnp.asarray(v[t]).reshape((H, C, 1, b)
                                              + v[t].shape[1:])
                 for k, v in batches.items()}
        rngs = jax.random.split(rng0, H * C).reshape(H, C, 2)
        hparams, hstate, _ = vm_host(hparams, hstate, t, micro, rngs)
    avg = jax.tree_util.tree_map(lambda x: x.mean(0), hparams)
    _tree_allclose(tr.params, avg)


def test_hierarchical_bn_composition_replay(np_rng):
    """BN running stats under the COMPOSED topology (2 hosts x 2 chips,
    tau=2): each host behaves as a flat 2-chip sync trainer on its rows
    (per-step chip re-averaging of the stats), and the tau boundary
    averages them across hosts with the weights — pinned against that
    exact replay."""
    from sparknet_tpu.proto import load_net_prototxt

    sp = load_solver_prototxt_with_net(SOLVER_TXT,
                                       load_net_prototxt(BN_DP_NET))
    tau = 2
    hier = DistributedTrainer(sp, make_pod_mesh(2, 2),
                              TrainerConfig(strategy="hierarchical",
                                            tau=tau), seed=0)
    init = jax.tree_util.tree_map(np.asarray, hier.params)
    batches = {
        "data": np_rng.normal(size=(tau, 16, 1, 12, 12)).astype(np.float32),
        "label": np_rng.integers(0, 5, size=(tau, 16)).astype(np.float32),
    }
    hier.train_round(batches)

    host_params = []
    for h in range(2):
        sub = DistributedTrainer(sp, make_mesh(2),
                                 TrainerConfig(strategy="sync", tau=1),
                                 seed=0)
        sub.params = jax.tree_util.tree_map(jnp.asarray, init)
        rows = {k: v[:, 8 * h:8 * (h + 1)] for k, v in batches.items()}
        for t in range(tau):
            sub.train_round({k: v[t][None] for k, v in rows.items()})
        host_params.append(jax.tree_util.tree_map(np.asarray, sub.params))

    # the BN running stats genuinely diverged across the two hosts
    # (the host average is non-trivial)
    for i in (0, 1):
        assert not np.allclose(host_params[0]["bn1"][i],
                               host_params[1]["bn1"][i])
    avg = jax.tree_util.tree_map(
        lambda a, b: (a + b) / 2, host_params[0], host_params[1])
    _tree_allclose(hier.params, avg)
