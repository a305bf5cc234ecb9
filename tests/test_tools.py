"""Dataset/CLI tool tests: convert_imageset -> compute_image_mean ->
caffe_cli train/test -> extract_features over a tiny generated dataset —
the analog of exercising caffe/tools/*.cpp end to end."""

import json
import os

import numpy as np
import pytest

from sparknet_tpu.data.db import datum_to_array, open_db
from sparknet_tpu.proto.caffemodel import load_mean_binaryproto
from sparknet_tpu.tools import (
    caffe_cli,
    compute_image_mean,
    convert_imageset,
    extract_features,
)


@pytest.fixture(scope="module")
def image_dataset(tmp_path_factory):
    root = tmp_path_factory.mktemp("imgs")
    from PIL import Image
    rng = np.random.default_rng(0)
    lines = []
    for i in range(12):
        arr = rng.integers(0, 256, size=(10, 10, 3)).astype(np.uint8)
        name = f"im{i}.png"
        Image.fromarray(arr).save(str(root / name))
        lines.append(f"{name} {i % 3}")
    listfile = root / "list.txt"
    listfile.write_text("".join(l + "\n" for l in lines))
    return root, listfile


def test_convert_imageset_and_mean(image_dataset, tmp_path):
    root, listfile = image_dataset
    db = str(tmp_path / "db_lmdb")
    rc = convert_imageset.main([str(root), str(listfile), db,
                                "--resize_height", "8",
                                "--resize_width", "8"])
    assert rc == 0
    with open_db(db, "LMDB") as r:
        assert len(r) == 12
        _k, v = r.first()
        img, label = datum_to_array(v)
        assert img.shape == (3, 8, 8)
        assert label == 0

    mean_file = str(tmp_path / "mean.binaryproto")
    assert compute_image_mean.main([db, mean_file]) == 0
    mean = load_mean_binaryproto(mean_file)
    assert mean.shape == (3, 8, 8)
    assert 64 < mean.mean() < 192  # uniform-random pixels


def test_convert_imageset_leveldb(image_dataset, tmp_path):
    root, listfile = image_dataset
    db = str(tmp_path / "db_ldb")
    rc = convert_imageset.main([str(root), str(listfile), db,
                                "--backend", "leveldb",
                                "--resize_height", "8",
                                "--resize_width", "8", "--gray"])
    assert rc == 0
    with open_db(db, "LEVELDB") as r:
        assert len(r) == 12
        img, _ = datum_to_array(r.first()[1])
        assert img.shape == (1, 8, 8)


@pytest.fixture()
def db_net(image_dataset, tmp_path):
    root, listfile = image_dataset
    db = str(tmp_path / "train_lmdb")
    convert_imageset.main([str(root), str(listfile), db,
                           "--resize_height", "8", "--resize_width", "8"])
    model = tmp_path / "net.prototxt"
    model.write_text(f"""
name: "toolnet"
layer {{ name: "data" type: "Data" top: "data" top: "label"
        data_param {{ source: "{db}" batch_size: 4 backend: LMDB }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param {{ num_output: 3
                              weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label"
        top: "loss" include {{ phase: TRAIN }} }}
layer {{ name: "acc" type: "Accuracy" bottom: "ip" bottom: "label"
        top: "acc" include {{ phase: TEST }} }}
""")
    return tmp_path, model


def test_caffe_cli_train_and_test(db_net, capsys):
    tmp_path, model = db_net
    solver = tmp_path / "solver.prototxt"
    solver.write_text(f"""
net: "{model}"
base_lr: 0.01
momentum: 0.9
lr_policy: "fixed"
max_iter: 6
test_iter: 2
test_interval: 3
snapshot_prefix: "{tmp_path / 'snap'}"
snapshot: 1
""")
    assert caffe_cli.main(["train", "--solver", str(solver)]) == 0
    out = capsys.readouterr().out
    assert "Iteration 6" in out and "Optimization Done." in out
    model_file = str(tmp_path / "snap_iter_6.caffemodel")
    assert os.path.exists(model_file)

    assert caffe_cli.main(["test", "--model", str(model),
                           "--weights", model_file,
                           "--iterations", "2"]) == 0
    out = capsys.readouterr().out
    assert "acc =" in out


@pytest.mark.parametrize("strategy,tau,devices,extra,topo", [
    ("sync", 1, 2, [], "2 devices"),
    ("local_sgd", 2, 2, [], "2 devices"),
    ("hierarchical", 2, 4, ["--hosts", "2"], "2x2 pod"),
])
def test_caffe_cli_train_multi_device(db_net, capsys, strategy, tau,
                                      devices, extra, topo):
    """`caffe train --devices N` routes to DistributedTrainer (the
    `caffe train --gpu 0,1` P2PSync path, caffe/tools/caffe.cpp:81-103,
    208-211), end to end from the CLI on the virtual CPU mesh: DB-backed
    feed fanned out one minibatch per device, loss/test logging, npz
    snapshot.  The hierarchical case drives the composed (host, chip)
    pod from the same flag surface."""
    tmp_path, model = db_net
    solver = tmp_path / f"solver_{strategy}.prototxt"
    solver.write_text(f"""
net: "{model}"
base_lr: 0.01
momentum: 0.9
lr_policy: "fixed"
max_iter: 4
display: 2
test_iter: 2
test_interval: 2
snapshot_prefix: "{tmp_path / ('multi_' + strategy)}"
""")
    args = ["train", "--solver", str(solver),
            "--devices", str(devices), "--strategy", strategy,
            "--tau", str(tau)] + extra
    rc = caffe_cli.main(args)
    assert rc == 0
    out = capsys.readouterr().out
    assert f"Multi-device training: {topo}" in out
    assert f"strategy={strategy}" in out
    assert "loss = " in out and "Optimization Done." in out
    assert "Testing net (#0)" in out and "acc = " in out
    snap = tmp_path / f"multi_{strategy}_iter_4.npz"
    assert snap.exists()

    # resume from the snapshot picks up at iter 4 and finishes cleanly
    solver.write_text(solver.read_text().replace("max_iter: 4",
                                                 "max_iter: 6"))
    rc = caffe_cli.main(args + ["--snapshot", str(snap)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Resuming from" in out and "(iter 4)" in out


def test_extract_features(db_net, tmp_path, capsys):
    tpath, model = db_net
    solver = tpath / "solver.prototxt"
    solver.write_text(f"""
net: "{model}"
base_lr: 0.01
lr_policy: "fixed"
max_iter: 2
snapshot_prefix: "{tpath / 'ef'}"
snapshot: 1
""")
    caffe_cli.main(["train", "--solver", str(solver)])
    weights = str(tpath / "ef_iter_2.caffemodel")
    feat_db = str(tmp_path / "feat_lmdb")
    rc = extract_features.main([weights, str(model), "ip", feat_db, "2"])
    assert rc == 0
    with open_db(feat_db, "LMDB") as r:
        assert len(r) == 8  # 2 batches x 4
        img, _ = datum_to_array(r.first()[1])
        assert img.shape == (3, 1, 1)


def test_device_query(capsys):
    assert caffe_cli.main(["device_query"]) == 0
    assert "Device kind" in capsys.readouterr().out


def test_upgrade_net_proto_text(tmp_path):
    """V0 prototxt -> upgraded V2 prototxt that parses as new-style and
    builds (upgrade_net_proto_text.cpp analog)."""
    from sparknet_tpu.tools import upgrade_net_proto

    src = tmp_path / "v0.prototxt"
    src.write_text("""
name: "v0"
input: "data"
input_dim: 1 input_dim: 1 input_dim: 8 input_dim: 8
layers { layer { name: "pad" type: "padding" pad: 1 }
         bottom: "data" top: "p" }
layers { layer { name: "c" type: "conv" num_output: 2 kernelsize: 3
                 weight_filler { type: "xavier" } } bottom: "p" top: "c" }
layers { layer { name: "r" type: "relu" } bottom: "c" top: "c" }
""")
    out = tmp_path / "v2.prototxt"
    assert upgrade_net_proto.main([str(src), str(out)]) == 0
    text = out.read_text()
    assert "layers" not in text.replace("layer {", "")  # new-style only
    assert 'type: "Convolution"' in text

    import jax

    from sparknet_tpu.graph import Net
    from sparknet_tpu.proto import load_net_prototxt
    net = Net(load_net_prototxt(str(out)))
    params = net.init(jax.random.PRNGKey(0))
    assert params["c"][0].shape == (2, 1, 3, 3)
    assert net.blob_shapes["c"] == (1, 2, 8, 8)  # pad survived the upgrade


def test_upgrade_net_proto_binary(tmp_path):
    """Binary round-trip preserves weight blobs (upgrade_net_proto_binary)."""
    from sparknet_tpu.proto.caffemodel import (
        load_net_binaryproto,
        save_caffemodel,
    )
    from sparknet_tpu.tools import upgrade_net_proto

    src = str(tmp_path / "w.caffemodel")
    w = np.arange(12, dtype=np.float32).reshape(3, 4)
    save_caffemodel(src, {"ip": [w]})
    out = str(tmp_path / "upgraded.caffemodel")
    assert upgrade_net_proto.main([src, out, "--binary"]) == 0
    net = load_net_binaryproto(out)
    by_name = {l.name: l for l in net.layer}
    np.testing.assert_array_equal(by_name["ip"].blobs[0], w)


def test_upgrade_sniffs_named_caffemodel(tmp_path):
    """A binary NetParameter whose first bytes are the name field
    (b'\\n...' — printable ASCII) must still be detected as binary."""
    from sparknet_tpu.proto.caffemodel import (
        load_net_binaryproto,
        save_caffemodel,
    )
    from sparknet_tpu.tools import upgrade_net_proto

    src = str(tmp_path / "named.caffemodel")
    w = np.ones((2, 2), np.float32)
    save_caffemodel(src, {"ip": [w]}, name="CaffeNet")
    with open(src, "rb") as f:
        assert f.read(1) == b"\n"  # the sniffing trap: looks like text
    out = str(tmp_path / "out.caffemodel")
    assert upgrade_net_proto.main([src, out, "--binary"]) == 0
    net = load_net_binaryproto(out)
    assert net.name == "CaffeNet"


def test_upgrade_preserves_net_state(tmp_path):
    from sparknet_tpu.proto import load_net_prototxt
    from sparknet_tpu.tools import upgrade_net_proto

    src = tmp_path / "s.prototxt"
    src.write_text("""
name: "staged"
state { phase: TEST stage: "deploy" }
layer { name: "d" type: "Input" top: "x"
        input_param { shape { dim: 1 dim: 2 } } }
""")
    out = tmp_path / "out.prototxt"
    assert upgrade_net_proto.main([str(src), str(out)]) == 0
    net = load_net_prototxt(str(out))
    assert net.state.stage == ["deploy"]


def test_classifier_predict(tmp_path):
    """pycaffe Classifier analog: deploy prototxt + caffemodel ->
    center-crop and 10-crop-averaged predictions."""
    from sparknet_tpu.classify import Classifier, oversample

    deploy = tmp_path / "deploy.prototxt"
    deploy.write_text("""
name: "tinydeploy"
layer { name: "data" type: "Input" top: "data"
        input_param { shape { dim: 1 dim: 3 dim: 8 dim: 8 } } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 4
                              weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "ip" top: "prob" }
""")
    clf = Classifier(str(deploy), image_dims=(10, 10))
    imgs = [np.random.default_rng(i).normal(size=(3, 10, 10)) for i in range(2)]
    probs = clf.predict(imgs, oversample_crops=True)
    assert probs.shape == (2, 4)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-4)
    probs_c = clf.predict(imgs, oversample_crops=False)
    assert probs_c.shape == (2, 4)

    crops = oversample(np.stack([np.asarray(i, np.float32) for i in imgs]), 8)
    assert crops.shape == (20, 3, 8, 8)
    # crop 4 is the center crop; crop 9 is its mirror
    np.testing.assert_allclose(crops[4 * 2], crops[9 * 2][:, :, ::-1])


def test_draw_net(tmp_path):
    from sparknet_tpu.tools import draw_net

    net = tmp_path / "net.prototxt"
    net.write_text("""
name: "toy"
layer { name: "data" type: "Input" top: "data"
        input_param { shape { dim: 1 dim: 3 dim: 8 dim: 8 } } }
layer { name: "conv" type: "Convolution" bottom: "data" top: "conv"
        convolution_param { num_output: 2 kernel_size: 3
                            weight_filler { type: "xavier" } } }
layer { name: "relu" type: "ReLU" bottom: "conv" top: "conv" }
""")
    out = tmp_path / "net.dot"
    assert draw_net.main([str(net), str(out)]) == 0
    dot = out.read_text()
    assert dot.startswith('digraph "toy"')
    assert '"L_conv"' in dot and '"B_data" -> "L_conv"' in dot
    assert "kernel 3" in dot
    assert dot.count("{") == dot.count("}")


def test_detector_windows(tmp_path):
    from sparknet_tpu.classify import Detector

    deploy = tmp_path / "det.prototxt"
    deploy.write_text("""
layer { name: "data" type: "Input" top: "data"
        input_param { shape { dim: 1 dim: 3 dim: 8 dim: 8 } } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 3
                              weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "ip" top: "prob" }
""")
    det = Detector(str(deploy), context_pad=2)
    img = np.random.default_rng(0).normal(size=(3, 32, 32)).astype(np.float32)
    out = det.detect_windows([(img, [(0, 0, 15, 15), (8, 8, 31, 31)])])
    assert len(out) == 2
    assert out[0]["window"] == (0, 0, 15, 15)
    assert out[0]["prediction"].shape == (3,)
    np.testing.assert_allclose(out[0]["prediction"].sum(), 1.0, rtol=1e-4)


def test_classifier_crop_sized_mean(tmp_path):
    """pycaffe-style mean arrays are net-input (crop) sized; subtraction
    must happen per-crop, not at image_dims (Transformer.set_mean)."""
    from sparknet_tpu.classify import Classifier, Detector

    deploy = tmp_path / "m.prototxt"
    deploy.write_text("""
layer { name: "data" type: "Input" top: "data"
        input_param { shape { dim: 1 dim: 3 dim: 8 dim: 8 } } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 2
                              weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "ip" top: "prob" }
""")
    mean = np.ones((3, 8, 8), np.float32) * 7  # crop-sized, pycaffe-style
    clf = Classifier(str(deploy), image_dims=(12, 12), mean=mean)
    img = np.random.default_rng(0).normal(size=(3, 12, 12))
    probs = clf.predict([img], oversample_crops=True)
    assert probs.shape == (1, 2)

    # detector: crop-sized mean + border-clipped window + grayscale->RGB-ish
    det = Detector(str(deploy), mean=mean, context_pad=2)
    gray = np.random.default_rng(1).normal(size=(20, 20))  # 2-D image
    out = det.detect_windows([(np.tile(gray[None], (3, 1, 1)),
                               [(0, 0, 10, 10)])])
    assert out[0]["prediction"].shape == (2,)


def test_bench_cpu_smoke(tmp_path):
    """bench.py must emit exactly one valid JSON line on stdout with the
    documented schema — the contract the benchmark driver consumes."""
    import subprocess
    import sys
    env = dict(os.environ,
               BENCH_PLATFORM="cpu", BENCH_MODEL="lenet", BENCH_BATCH="4",
               BENCH_ITERS="1", BENCH_REPS="1", BENCH_WINDOWS="1",
               BENCH_DTYPE="f32", BENCH_FEED_ITERS="2",
               BENCH_FEED_BATCH="8",
               BENCH_ATTEMPTS="1", BENCH_TIMEOUT_S="280",
               BENCH_ROUND="0",  # the round leg has its own gate (roundbench)
               BENCH_SERVING="0",  # as does serving (servesmoke)
               BENCH_FUSE="off")  # and vertical fusion
    env.pop("XLA_FLAGS", None)  # conftest's 8-device flag slows the child
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, os.path.join(root, "bench.py")],
                          capture_output=True, timeout=300, cwd=root, env=env)
    assert proc.returncode == 0, proc.stderr.decode()[-800:]
    lines = proc.stdout.decode().strip().splitlines()
    assert len(lines) == 1, lines
    result = json.loads(lines[0])
    assert result["metric"] == "lenet_train_images_per_sec"
    assert result["value"] > 0
    assert result["dtype"] == "f32"
    assert result["by_dtype"]["f32"]["images_per_sec"] == result["value"]
    feed = result["feed_in_loop"]
    assert feed["images_per_sec"] > 0 and "overlap_pct" in feed
    # the three legs are measured at the same (overridden) batch and are
    # mutually consistent: 0 <= overlap <= 100 and the in-loop step can't
    # beat a perfect pipeline by more than timer noise
    assert feed["batch"] == 8
    assert feed["feed_alone_s_per_batch"] > 0
    assert feed["compute_s_per_step"] > 0
    assert 0.0 <= feed["overlap_pct"] <= 100.0
    assert feed["bound"] in ("feed", "compute")
    assert feed["feed_compute_ratio"] > 0
    assert feed["step_s"] > 0.25 * max(feed["feed_alone_s_per_batch"],
                                       feed["compute_s_per_step"])


def test_bench_feed_overlap_nondegenerate(tmp_path):
    """The prefetch pipeline must MEASURABLY overlap feed and compute in
    the non-degenerate regime (round-3 verdict: 'measured, not
    asserted').  BENCH_FEED_DELAY_S injects a deterministic per-batch
    host cost (decode stand-in) that dominates this platform's compute,
    so the verdict is pinned: in-loop total must land near
    max(feed, compute), well under serial feed+compute — i.e. the
    producer thread genuinely hides its work behind the step."""
    import subprocess
    import sys
    delay = 0.15
    env = dict(os.environ,
               BENCH_PLATFORM="cpu", BENCH_MODEL="lenet", BENCH_BATCH="4",
               BENCH_ITERS="1", BENCH_REPS="1", BENCH_WINDOWS="1",
               BENCH_DTYPE="f32", BENCH_FEED_ITERS="6",
               BENCH_FEED_BATCH="16", BENCH_FEED_DELAY_S=str(delay),
               BENCH_ATTEMPTS="1", BENCH_TIMEOUT_S="280",
               BENCH_ROUND="0",  # the round leg has its own gate (roundbench)
               BENCH_SERVING="0",  # as does serving (servesmoke)
               BENCH_FUSE="off")  # and vertical fusion
    env.pop("XLA_FLAGS", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, os.path.join(root, "bench.py")],
                          capture_output=True, timeout=300, cwd=root, env=env)
    assert proc.returncode == 0, proc.stderr.decode()[-800:]
    feed = json.loads(proc.stdout.decode().strip().splitlines()[-1]
                      )["feed_in_loop"]
    fa, cs, tot = (feed["feed_alone_s_per_batch"],
                   feed["compute_s_per_step"], feed["step_s"])
    # the injected delay dominates: this IS the feed-bound non-degenerate
    # regime (compute nonzero but smaller)
    assert fa >= delay and cs < fa, feed
    # overlap verdict: total ≈ max(fa, cs), not fa + cs.  Slack covers
    # CI timer noise; a synchronous feed (total = fa + cs) must fail.
    assert tot < fa + 0.5 * cs, feed
    assert tot < 1.35 * fa, feed
    assert feed["bound"] == "feed"


def test_compile_cache_placed_from_outside_else_in_checkout(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX (nothing is set
    in code); otherwise the cache is <checkout>/.jax_cache, never a
    temporary name."""
    import jax

    from sparknet_tpu.utils.compile_cache import use_compile_cache
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert use_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            root, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_the_cpu():
    """Off the chip chip_smoke.py exits non-zero before building anything,
    names the backend it found, and prints no result."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py")],
        capture_output=True, timeout=120, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert b"'cpu'" in proc.stderr and b"not 'tpu'" in proc.stderr
    assert proc.stdout.strip() == b""


def test_bench_rejects_bad_dtype():
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py")],
        capture_output=True, timeout=60, cwd=root,
        env=dict(os.environ, BENCH_DTYPE="fp32"))
    assert proc.returncode == 2
    assert b"BENCH_DTYPE" in proc.stderr


def test_time_net_runs_and_trace_degrades(capsys):
    """time_net whole-net timing works on CPU; --trace degrades gracefully
    when the platform has no device plane (TPU feature)."""
    from sparknet_tpu.tools import time_net
    time_net.main(["--model", "lenet", "--batch", "4", "--iterations", "1",
                   "--trace"])
    out = capsys.readouterr().out
    assert "Average Forward-Backward" in out
    assert ("Per-layer device time" in out      # TPU/GPU rig
            or "layer scopes" in out            # captured, no device plane
            or "device plane" in out)           # no plane at all


def test_caffe_cli_resolves_test_net_files(tmp_path):
    """`test_net:` file references load into test_net_param (the
    Solver::InitTestNets path), alongside `net:` resolution."""
    (tmp_path / "train.prototxt").write_text("""
layer { name: "data" type: "DummyData" top: "data" top: "label"
  dummy_data_param { shape { dim: 4 dim: 3 } shape { dim: 4 } } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param { num_output: 2 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" }
""")
    (tmp_path / "test.prototxt").write_text("""
layer { name: "data" type: "DummyData" top: "data" top: "label"
  dummy_data_param { shape { dim: 2 dim: 3 } shape { dim: 2 } } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param { num_output: 2 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" }
""")
    solver_path = tmp_path / "solver.prototxt"
    solver_path.write_text('train_net: "train.prototxt"\n'
                           'test_net: "test.prototxt"\n'
                           'base_lr: 0.1\ntest_iter: 1\n')
    from sparknet_tpu.proto import load_solver_prototxt
    from sparknet_tpu.solvers import Solver
    from sparknet_tpu.tools.caffe_cli import _resolve_solver_net
    sp = load_solver_prototxt(str(solver_path))
    _resolve_solver_net(sp, str(solver_path))
    assert len(sp.test_net_param) == 1
    solver = Solver(sp, seed=0)
    # dedicated test net: batch 2, not the train net's 4
    scores = solver.test(1)
    assert "loss" in scores
    assert solver.test_net.blob_shapes["data"] == (2, 3)


def test_parse_log_roundtrip(tmp_path, capsys):
    """parse_log (tools/extra/parse_log.py analog) splits a real solve()
    log into train/test CSVs."""
    import contextlib
    import csv
    import io as _io

    from sparknet_tpu.proto import load_solver_prototxt_with_net, \
        load_net_prototxt
    from sparknet_tpu.solvers import Solver
    from sparknet_tpu.tools.parse_log import parse_log, write_csvs

    netp = load_net_prototxt("""
layer { name: "data" type: "DummyData" top: "data" top: "label"
  dummy_data_param { shape { dim: 4 dim: 3 } shape { dim: 4 }
    data_filler { type: "gaussian" std: 1.0 }
    data_filler { type: "constant" value: 1.0 } } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param { num_output: 2 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" }
layer { name: "acc" type: "Accuracy" bottom: "ip" bottom: "label"
  top: "accuracy" include { phase: TEST } }
""")
    sp = load_solver_prototxt_with_net(
        "base_lr: 0.1\nmax_iter: 6\ndisplay: 2\ntest_interval: 3\n"
        "test_iter: 2\ntest_initialization: true\n", netp)
    solver = Solver(sp, seed=0)
    buf = _io.StringIO()
    with contextlib.redirect_stdout(buf):
        solver.solve()
    log = tmp_path / "train.log"
    log.write_text(buf.getvalue())

    train, test = parse_log(str(log))
    iters = [it for it, _ in train]
    assert 6 in iters and all(np.isfinite(l) for _, l in train)
    assert (0, 0) in test          # test_initialization pass at iter 0
    assert any(it == 6 for it, _ in test)  # final pass
    assert all("accuracy" in row and "loss" in row
               for row in test.values())

    tr_path, te_path = write_csvs(str(log), str(tmp_path))
    rows = list(csv.reader(open(tr_path)))
    assert rows[0] == ["NumIters", "Seconds", "LearningRate", "loss"]
    assert len(rows) > 1
    # glog timestamps + lr lines are emitted by the Solver now: every
    # train row carries Seconds (monotone from 0) and LearningRate
    secs = [float(r[1]) for r in rows[1:]]
    assert secs == sorted(secs) and secs[0] >= 0.0
    assert all(float(r[2]) == 0.1 for r in rows[1:])  # base_lr, fixed
    te_rows = list(csv.reader(open(te_path)))
    assert te_rows[0][:3] == ["NumIters", "Seconds", "TestNet"]
    assert "accuracy" in te_rows[0]
    assert all(r[1] != "" for r in te_rows[1:])

    # all 8 reference chart types render from this real log
    # (plot_training_log.py.example supported_chart_types)
    from sparknet_tpu.tools.plot_training_log import main as plot_main
    for ct in range(8):
        out = tmp_path / f"chart{ct}.png"
        assert plot_main([str(ct), str(out), str(log)]) == 0
        assert out.stat().st_size > 1000


def test_parse_log_resume_and_inf(tmp_path):
    """Scores printed by a pre-training test pass on RESUME key to the
    solver's iteration (via the 'Testing net' marker), and inf/nan
    losses parse instead of crashing."""
    from sparknet_tpu.tools.parse_log import parse_log

    log = tmp_path / "resume.log"
    log.write_text(
        "Iteration 300, Testing net (#0)\n"
        "    Test net output: accuracy = 0.75\n"
        "Iteration 302, loss = -inf\n"
        "Iteration 304, loss = nan\n"
        "Iteration 304, Testing net (#1)\n"
        "    Test net output: loss = 1e+30\n")
    train, test = parse_log(str(log))
    assert train[0] == (302, float("-inf"))
    assert np.isnan(train[1][1])
    assert test[(300, 0)]["accuracy"] == 0.75
    assert test[(304, 1)]["loss"] == 1e30


def test_parse_log_non_leap_feb28_mar1_span(tmp_path):
    """Regression (ADVICE.md): _glog_seconds used a FIXED leap year
    (2024) for day-of-year, so a non-leap-year log spanning
    Feb 28 → Mar 1 gained a phantom Feb 29: +86400 s.  The year now
    comes from the log's mtime and deltas from full datetimes."""
    import calendar
    import datetime
    import os as _os

    from sparknet_tpu.tools.parse_log import parse_log

    log = tmp_path / "wrap.log"
    log.write_text(
        "I0228 23:59:50.000000  1 solver.py:1] Iteration 0, loss = 1.0\n"
        "I0301 00:00:10.000000  1 solver.py:1] Iteration 2, loss = 0.9\n")
    # pin the file into a non-leap year (the log "was written" then)
    mt = datetime.datetime(2025, 3, 1, 1, 0, 0).timestamp()
    _os.utime(log, (mt, mt))
    train, _ = parse_log(str(log))
    deltas = [row.seconds for row in train]
    assert deltas == [0.0, 20.0]   # was 86420.0 with the 2024 anchor

    # a leap-year log keeps its real Feb 29: same stamps, 2024 mtime
    mt = datetime.datetime(2024, 3, 1, 1, 0, 0).timestamp()
    _os.utime(log, (mt, mt))
    train, _ = parse_log(str(log))
    assert [row.seconds for row in train] == [0.0, 86420.0]

    # Feb 29 stamps in a log whose mtime landed in a later, non-leap
    # year (copied file) walk back to the nearest leap year, not crash
    leap = tmp_path / "leap.log"
    leap.write_text(
        "I0229 10:00:00.000000  1 solver.py:1] Iteration 0, loss = 1.0\n"
        "I0301 10:00:00.000000  1 solver.py:1] Iteration 2, loss = 0.9\n")
    _os.utime(leap, (mt + 370 * 86400, mt + 370 * 86400))  # 2025 mtime
    train, _ = parse_log(str(leap))
    assert [row.seconds for row in train] == [0.0, 86400.0]

    # new-year wrap: Dec 31 → Jan 1 is one day, leap or not
    wrap = tmp_path / "newyear.log"
    wrap.write_text(
        "I1231 23:59:00.000000  1 solver.py:1] Iteration 0, loss = 1.0\n"
        "I0101 00:01:00.000000  1 solver.py:1] Iteration 2, loss = 0.9\n")
    mt = datetime.datetime(2026, 1, 1, 2, 0, 0).timestamp()
    _os.utime(wrap, (mt, mt))
    train, _ = parse_log(str(wrap))
    assert [row.seconds for row in train] == [0.0, 120.0]
    assert not calendar.isleap(2025) and not calendar.isleap(2026)


def test_plot_training_log(tmp_path):
    """plot_training_log (tools/extra analog): charts parse_log output;
    unsupported Seconds/lr chart types refuse clearly."""
    from sparknet_tpu.tools.plot_training_log import main, plot

    log = tmp_path / "t.log"
    log.write_text(
        "Iteration 0, Testing net (#0)\n"
        "    Test net output: accuracy = 0.1\n"
        "    Test net output: loss = 2.3\n"
        "Iteration 2, loss = 2.0\n"
        "Iteration 4, loss = 1.5\n"
        "Iteration 4, Testing net (#0)\n"
        "    Test net output: accuracy = 0.6\n"
        "    Test net output: loss = 1.4\n")
    for ct, name in ((0, "acc.png"), (2, "tloss.png"), (6, "loss.png")):
        out = tmp_path / name
        assert main([str(ct), str(out), str(log)]) == 0
        assert out.stat().st_size > 1000  # a real png
    # a log with no glog timestamps / lr lines refuses the Seconds and
    # LearningRate chart types with a clear message
    with pytest.raises(ValueError, match="timestamp"):
        plot(1, str(tmp_path / "x.png"), [str(log)])
    with pytest.raises(ValueError, match="lr"):
        plot(4, str(tmp_path / "x.png"), [str(log)])
    with pytest.raises(ValueError, match="unknown chart type"):
        plot(9, str(tmp_path / "x.png"), [str(log)])


DEPLOY_NET = """
name: "deploy"
input: "data"
input_shape { dim: 1 dim: 3 dim: 8 dim: 8 }
layer { name: "conv" type: "Convolution" bottom: "data" top: "conv"
  convolution_param { num_output: 4 kernel_size: 3 pad: 1 stride: 2
    weight_filler { type: "xavier" } } }
layer { name: "ip" type: "InnerProduct" bottom: "conv" top: "ip"
  inner_product_param { num_output: 3 weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "ip" top: "prob" }
"""


def test_classify_cli(tmp_path):
    """classify CLI (python/classify.py analog): image dir and npy
    inputs -> probability npy; channel_swap honored."""
    from PIL import Image

    from sparknet_tpu.tools import classify_cli

    model = tmp_path / "deploy.prototxt"
    model.write_text(DEPLOY_NET)
    rng = np.random.default_rng(0)
    imgdir = tmp_path / "imgs"
    imgdir.mkdir()
    for i in range(3):
        Image.fromarray(rng.integers(0, 256, size=(10, 12, 3)
                                     ).astype(np.uint8)).save(
            str(imgdir / f"im{i}.jpg"))
    out = tmp_path / "probs.npy"
    rc = classify_cli.main([str(imgdir), str(out),
                            "--model_def", str(model),
                            "--images_dim", "8,8", "--center_only"])
    assert rc == 0
    probs = np.load(out)
    assert probs.shape == (3, 3)
    np.testing.assert_allclose(probs.sum(1), 1.0, rtol=1e-4)

    # npy input path + oversampling
    batch = rng.uniform(size=(2, 10, 10, 3)).astype(np.float32)
    npy_in = tmp_path / "batch.npy"
    np.save(npy_in, batch)
    out2 = tmp_path / "probs2.npy"
    assert classify_cli.main([str(npy_in), str(out2),
                              "--model_def", str(model),
                              "--images_dim", "10,10"]) == 0
    assert np.load(out2).shape == (2, 3)


def test_classifier_channel_swap(tmp_path):
    """channel_swap permutes channels before scaling: swapping the input
    channels and un-swapping via the flag gives identical predictions."""
    from sparknet_tpu.classify import Classifier

    model = tmp_path / "deploy.prototxt"
    model.write_text(DEPLOY_NET)
    rng = np.random.default_rng(1)
    img = rng.uniform(size=(8, 8, 3)).astype(np.float32)
    base = Classifier(str(model), image_dims=(8, 8))
    swapped = Classifier(str(model), image_dims=(8, 8),
                         channel_swap=(2, 1, 0))
    p1 = base.predict([img], oversample_crops=False)
    p2 = swapped.predict([img[:, :, ::-1]], oversample_crops=False)
    np.testing.assert_allclose(p1, p2, rtol=1e-5, atol=1e-6)


def test_detect_cli(tmp_path):
    """detect CLI (python/detect.py analog, crop_mode=list): window CSV
    in, per-window class scores CSV out."""
    import csv as _csv

    from PIL import Image

    from sparknet_tpu.tools import detect_cli

    model = tmp_path / "deploy.prototxt"
    model.write_text(DEPLOY_NET)
    rng = np.random.default_rng(2)
    img_path = tmp_path / "scene.jpg"
    Image.fromarray(rng.integers(0, 256, size=(24, 24, 3)
                                 ).astype(np.uint8)).save(str(img_path))
    wins = tmp_path / "windows.csv"
    wins.write_text(
        "filename,ymin,xmin,ymax,xmax\n"
        f"{img_path},0,0,12,12\n"
        f"{img_path},8,8,24,24\n")
    out = tmp_path / "dets.csv"
    rc = detect_cli.main([str(wins), str(out), "--model_def", str(model),
                          "--context_pad", "2"])
    assert rc == 0
    rows = list(_csv.reader(open(out)))
    assert rows[0] == ["filename", "ymin", "xmin", "ymax", "xmax",
                       "class0", "class1", "class2"]
    assert len(rows) == 3
    scores = np.asarray([[float(v) for v in r[5:]] for r in rows[1:]])
    np.testing.assert_allclose(scores.sum(1), 1.0, rtol=1e-4)


def test_detector_channel_swap_and_vector_mean(tmp_path):
    """detect path honors channel_swap (swap+unswap is identity) and a
    per-channel vector mean broadcasts on the channel axis."""
    from sparknet_tpu.classify import Detector

    model = tmp_path / "deploy.prototxt"
    model.write_text(DEPLOY_NET)
    rng = np.random.default_rng(3)
    img = rng.uniform(size=(3, 16, 16)).astype(np.float32)
    wins = [(0, 0, 8, 8)]
    base = Detector(str(model), mean=np.array([0.1, 0.2, 0.3]
                                              ).reshape(3, 1, 1))
    swapped = Detector(str(model), channel_swap=(2, 1, 0),
                       mean=np.array([0.1, 0.2, 0.3]).reshape(3, 1, 1))
    p1 = base.detect_windows([(img, wins)])[0]["prediction"]
    p2 = swapped.detect_windows([(img[::-1], wins)])[0]["prediction"]
    np.testing.assert_allclose(p1, p2, rtol=1e-5, atol=1e-6)


def test_caffe_cli_multi_device_weights_and_errors(db_net, capsys):
    """--devices finetune path (--weights from a single-device
    .caffemodel) plus the clean-error contracts: non-integer --devices,
    .solverstate resume rejection, distributed flags without --devices."""
    tmp_path, model = db_net
    solver = tmp_path / "solver_w.prototxt"
    solver.write_text(f"""
net: "{model}"
base_lr: 0.01
lr_policy: "fixed"
max_iter: 2
snapshot_prefix: "{tmp_path / 'seed'}"
snapshot: 1
""")
    assert caffe_cli.main(["train", "--solver", str(solver)]) == 0
    capsys.readouterr()
    weights = tmp_path / "seed_iter_2.caffemodel"
    state = tmp_path / "seed_iter_2.solverstate"
    assert weights.exists() and state.exists()

    rc = caffe_cli.main(["train", "--solver", str(solver),
                         "--devices", "2", "--weights", str(weights)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Finetuning from" in out and "Optimization Done." in out

    with pytest.raises(SystemExit, match="integer or 'all'"):
        caffe_cli.main(["train", "--solver", str(solver),
                        "--devices", "two"])
    with pytest.raises(SystemExit, match="solverstate"):
        caffe_cli.main(["train", "--solver", str(solver),
                        "--devices", "2", "--snapshot", str(state)])
    with pytest.raises(SystemExit, match="require --devices"):
        caffe_cli.main(["train", "--solver", str(solver),
                        "--strategy", "local_sgd"])


def test_plot_learning_proxy_renders_png(tmp_path):
    """The paper's headline figure renders from a RESULTS JSON — per-row
    wall_s when present, else a linear reconstruction from the curve
    total, and corrupt walls are dropped rather than plotted wrong."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    curve = [{"iter": i, "lr": 0.001, "train_loss": 1.0,
              "train_acc": 0.5 + 0.04 * n, "test_acc": 0.4 + 0.04 * n}
             for n, i in enumerate(range(100, 1100, 100))]
    rows_with_wall = [dict(r, wall_s=2.0 * n + 1)
                      for n, r in enumerate(curve)]
    results = {
        "config": {"scale": 10, "max_iter": 1000,
                   "stepvalues": [600, 800], "batch": 100},
        "device": "cpu/test",
        "curve_1x": rows_with_wall,          # per-row wall: used as-is
        "curve_8way": curve,                 # no rows: reconstructed
        "curve_hier": curve,                 # corrupt total: dropped
        "final": {"acc_1x": 0.8, "acc_8way": 0.76, "acc_hier": 0.75,
                  "wall_s_1x": 99.0, "wall_s_8way": 50.0,
                  "wall_s_hier": 0.1},
    }
    src = tmp_path / "r.json"
    src.write_text(json.dumps(results))
    out = tmp_path / "r.png"
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools",
                                      "plot_learning_proxy.py"),
         "--in", str(src), "--out", str(out)],
        capture_output=True, timeout=120)
    assert proc.returncode == 0, proc.stderr.decode()
    assert out.exists() and out.stat().st_size > 10_000
    verdict = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert verdict["synthesized_wall"] == ["8way"]
    assert verdict["dropped"] == ["hierarchical 2×4"]


def test_perf_probe_time_block_typed_skip(capsys):
    """A candidate that raises is a typed ``skipped`` record and a None
    time, never an aborted probe run and never 0."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("perf_probe", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "perf_probe.py"))
    perf_probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(perf_probe)

    def bad_iter(s):
        raise ValueError("no backend for this op")

    got = perf_probe.time_block("probe_bad", bad_iter, extra={"tag": 1})
    assert got is None
    out = [json.loads(line) for line in
           capsys.readouterr().out.strip().splitlines() if line]
    rec = next(r for r in out if r.get("exp") == "probe_bad")
    assert rec["skipped"].startswith("ValueError: no backend")
    assert rec["tag"] == 1
