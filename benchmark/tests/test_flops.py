"""The FLOPs-from-shapes function against hand counts."""

import pytest

from benchmark.lib import flops, harness, peaks


def train_rows(builder, **kw):
    from sparknet_tpu import models
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    net = getattr(models, builder)(2, 2, **kw).filtered(NetState(Phase.TRAIN))
    return net, {r["name"]: r for r in flops.layer_table(net)}


def test_caffenet_conv1_and_fc6_by_hand():
    net, rows = train_rows("caffenet")
    # conv1: 96 maps of 55x55, each output one 3x11x11 window
    assert rows["conv1"]["out"] == [(2, 96, 55, 55)]
    assert rows["conv1"]["macs"] == 2 * 96 * 55 * 55 * 3 * 11 * 11
    assert rows["conv1"]["from_data"] and not rows["conv2"]["from_data"]
    # conv2 is grouped: each of 256 outputs sees 96/2 inputs
    assert rows["conv2"]["macs"] == 2 * 256 * 27 * 27 * 48 * 5 * 5
    # fc6: 4096 outputs over pool5's 256x6x6
    assert rows["pool5"]["out"] == [(2, 256, 6, 6)]
    assert rows["fc6"]["macs"] == 2 * 4096 * 9216
    assert flops.forward_macs_per_image(net) == 724_406_816
    # conv1's input is the data and needs no gradient: it counts twice
    by_hand = 2 * (3 * 724_406_816 - 105_415_200)
    assert flops.train_flops_per_image(net) == by_hand


def test_ceil_mode_pools_and_concat():
    _, rows = train_rows("googlenet", crop=224)
    assert rows["pool1/3x3_s2"]["out"] == [(2, 64, 56, 56)]   # ceil(109/2)+1
    assert rows["inception_3a/output"]["out"] == [(2, 256, 28, 28)]
    assert rows["loss1/ave_pool"]["out"] == [(2, 512, 4, 4)]
    assert rows["pool5/7x7_s1"]["out"] == [(2, 1024, 1, 1)]


def test_lrn_bytes_from_shapes():
    net, _ = train_rows("caffenet")
    need = flops.lrn_min_bytes_per_step(net, itemsize=2)
    elems = 2 * (96 * 27 * 27 + 256 * 13 * 13)
    assert need == {"layers": 2, "fwd": 2 * elems * 2, "bwd": 3 * elems * 2}


@pytest.mark.parametrize("name", ["caffenet", "googlenet"])
def test_configuration_files_state_what_the_program_builds(name):
    cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/{name}.json")
    cell = harness.Cell(name, cfg, {}, 1, 0, "")
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    net = cell.net_param(4, 4).filtered(NetState(Phase.TRAIN))  # checks
    built = cfg["as_built"]
    assert flops.forward_macs_per_image(net) == built[
        "forward_macs_per_image"]
    assert flops.train_flops_per_image(net) == built["train_flops_per_image"]


def test_a_changed_width_is_refused():
    cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/caffenet.json")
    cfg["as_built"]["layers"][1][2] = 64          # conv1's num_output
    with pytest.raises(SystemExit, match="not the one"):
        harness.Cell("x", cfg, {}, 1, 0, "").net_param(4, 4)


def test_an_unknown_chip_is_an_error():
    assert peaks.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no row"):
        peaks.peaks("TPU v9")
