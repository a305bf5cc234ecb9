"""Performance observatory tests: perf-ledger statistics (median/MAD
bands, small-sample refusal, fingerprint isolation, verdict taxonomy),
the artifact ingesters, the op-profile differ + fusion worklist, the
regress sentinel's stage attribution, the trajectory renderer, and the
serving SLO monitor's burn-rate state machine."""

import json
import os
import sys

import pytest

from sparknet_tpu.utils import perfledger as pl
from sparknet_tpu.utils import telemetry

pytestmark = pytest.mark.perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import perfwatch  # noqa: E402


# ---------------------------------------------------------------------------
# Baseline math
# ---------------------------------------------------------------------------

def test_band_is_median_plus_k_mad():
    hist = [100.0, 102.0, 98.0, 101.0, 99.0]
    b = pl.compute_baseline("train_img_s", "fpk", hist, k=4.0)
    assert b.gated
    assert b.median == 100.0
    assert b.mad == 1.0                      # median(|v-100|) = 1
    assert b.lo == pytest.approx(100.0 - 4.0 * 1.4826)
    assert b.hi == pytest.approx(100.0 + 4.0 * 1.4826)


def test_band_mad_robust_to_one_outlier():
    # one wild run must not blow the band open (k·stdev would reach
    # ~100 ± 711 here; k·1.4826·MAD stays at ~100 ± 6)
    wild = pl.compute_baseline("train_img_s", "fpk",
                               [100, 101, 99, 100, 500.0])
    assert wild.median == 100.0
    assert wild.mad == 1.0
    assert wild.hi < 110.0


def test_min_band_frac_floors_zero_width_band():
    # three identical smoke runs -> MAD 0; the wide-CPU-bands knob keeps
    # the band non-degenerate
    tight = pl.compute_baseline("train_img_s", "fpk", [100.0] * 3)
    assert tight.lo == tight.hi == 100.0
    wide = pl.compute_baseline("train_img_s", "fpk", [100.0] * 3,
                               min_band_frac=0.10)
    assert wide.lo == pytest.approx(90.0)
    assert wide.hi == pytest.approx(110.0)


def test_window_uses_trailing_values_only():
    hist = [10.0] * 10 + [100.0] * 8        # old regime must age out
    b = pl.compute_baseline("train_img_s", "fpk", hist, window=8)
    assert b.median == 100.0


def test_small_sample_refuses_to_gate():
    for n in (0, 1, 2):
        b = pl.compute_baseline("train_img_s", "fpk", [100.0] * n)
        assert not b.gated
        assert "refusing to gate" in b.reason
        assert pl.verdict("train_img_s", 1.0, b) == "not_gated"
    assert pl.compute_baseline("train_img_s", "fpk", [100.0] * 3).gated


def test_unknown_metric_direction_never_gates():
    b = pl.compute_baseline("mystery_widgets", "fpk", [1.0] * 5)
    assert not b.gated
    assert pl.verdict("mystery_widgets", 9.0, b) == "not_gated"


# ---------------------------------------------------------------------------
# Verdicts
# ---------------------------------------------------------------------------

def _base(metric, hist, **kw):
    return pl.compute_baseline(metric, "fpk", hist, **kw)


def test_verdict_taxonomy_higher_is_better():
    b = _base("train_img_s", [100.0, 101.0, 99.0, 100.0])
    assert pl.verdict("train_img_s", 100.5, b) == "within_band"
    assert pl.verdict("train_img_s", 50.0, b) == "regression"
    assert pl.verdict("train_img_s", 200.0, b) == "improvement"


def test_verdict_taxonomy_lower_is_better():
    # _ms metrics: DOWN is good — direction must flip the verdicts
    b = _base("serve_sat_p99_ms", [10.0, 10.5, 9.5, 10.0])
    assert pl.verdict("serve_sat_p99_ms", 10.2, b) == "within_band"
    assert pl.verdict("serve_sat_p99_ms", 50.0, b) == "regression"
    assert pl.verdict("serve_sat_p99_ms", 1.0, b) == "improvement"


def test_direction_heuristics():
    assert pl.higher_is_better("train_img_s") is True
    assert pl.higher_is_better("serve_sat_qps") is True
    assert pl.higher_is_better("mfu") is True
    assert pl.higher_is_better("step_ms") is False
    assert pl.higher_is_better("round_stall_async_s") is False
    assert pl.higher_is_better("cat_ms/loop fusion") is False
    assert pl.higher_is_better("cat_gbs/loop fusion") is True
    assert pl.higher_is_better("what_is_this") is None


# ---------------------------------------------------------------------------
# Fingerprint isolation
# ---------------------------------------------------------------------------

def test_fingerprint_isolates_device_and_dtype(tmp_path):
    led = pl.PerfLedger(str(tmp_path / "L.jsonl"))
    tpu = pl.fingerprint(model="caffenet", dtype="bf16", batch=256,
                         world=1, device="tpu/TPU v5 lite")
    cpu = pl.fingerprint(model="caffenet", dtype="bf16", batch=256,
                         world=1, device="cpu/cpu")
    f32 = pl.fingerprint(model="caffenet", dtype="f32", batch=256,
                         world=1, device="tpu/TPU v5 lite")
    for i in range(4):
        led.append(pl.make_entry("bench", None, tpu,
                                 {"train_img_s": 18000.0 + i}, t=float(i)))
    # plenty of TPU bf16 history; the CPU and f32 fingerprints must see
    # NONE of it — a CPU capture never gates against TPU baselines
    assert led.baseline("train_img_s", pl.fp_key(tpu)).gated
    for other in (cpu, f32):
        b = led.baseline("train_img_s", pl.fp_key(other))
        assert not b.gated
        assert b.n == 0
    assert pl.fp_key(tpu) != pl.fp_key(cpu) != pl.fp_key(f32)


def test_backend_defaults_from_device():
    fp = pl.fingerprint(model="m", device="tpu/TPU v5 lite")
    assert fp["backend"] == "tpu"
    assert pl.fingerprint(model="m")["backend"] == "unknown"


# ---------------------------------------------------------------------------
# Ledger IO
# ---------------------------------------------------------------------------

def test_ledger_appends_and_survives_torn_lines(tmp_path):
    path = str(tmp_path / "L.jsonl")
    led = pl.PerfLedger(path)
    fp = pl.fingerprint(model="lenet", dtype="f32", batch=8,
                        device="cpu/cpu")
    led.append(pl.make_entry("bench", "a.json", fp,
                             {"train_img_s": 100.0}, t=1.0))
    led.append(pl.make_entry("bench", "b.json", fp,
                             {"train_img_s": 101.0}, t=2.0))
    with open(path, "a") as f:
        f.write('{"torn": ')             # crash mid-append
    led2 = pl.PerfLedger(path)
    assert [e["path"] for e in led2.entries()] == ["a.json", "b.json"]
    assert led2.skipped_lines == 1
    assert led2.history("train_img_s", pl.fp_key(fp)) == [100.0, 101.0]


def test_make_entry_drops_non_numeric_and_non_finite():
    e = pl.make_entry("bench", None, pl.fingerprint(),
                      {"ok": 1.5, "nan": float("nan"),
                       "inf": float("inf"), "text": "fast"})
    assert e["metrics"] == {"ok": 1.5}
    assert e["v"] == pl.SCHEMA_VERSION


def test_history_before_t_excludes_self(tmp_path):
    led = pl.PerfLedger(str(tmp_path / "L.jsonl"))
    fp = pl.fingerprint(model="m", dtype="f32", batch=1, device="cpu/cpu")
    for i in range(3):
        led.append(pl.make_entry("bench", None, fp,
                                 {"train_img_s": 100.0}, t=float(i)))
    led.append(pl.make_entry("bench", None, fp,
                             {"train_img_s": 42.0}, t=10.0))
    assert led.history("train_img_s", pl.fp_key(fp),
                       before_t=10.0) == [100.0] * 3


def test_round_tag_from_path():
    assert pl.round_tag_from_path("BENCH_r05.json") == "r05"
    assert pl.round_tag_from_path("BENCH_serving_r07.json") == "r07"
    assert pl.round_tag_from_path("RESULTS_bench_tpu.json") is None


# ---------------------------------------------------------------------------
# Ingesters
# ---------------------------------------------------------------------------

def _bench_doc():
    return {
        "metric": "lenet_train_images_per_sec", "value": 120.0,
        "dtype": "f32", "batch": 8, "device": "cpu/cpu",
        "by_dtype": {"f32": {"images_per_sec": 120.0,
                             "eval_images_per_sec": 3000.0,
                             "block_20x256_s": 1.2, "mfu": 0.01}},
        "feed_in_loop": {"batch": 8, "images_per_sec": 800.0,
                         "step_s": 0.01, "staged_dtype": "uint8",
                         "decode_s": 0.001, "transform_s": 0.0,
                         "device_put_s": 0.002},
        "provenance": {"git_sha": "abc1234", "run": "run-x", "rank": 0},
    }


def test_bench_ingester_splits_train_and_feed_entries():
    entries = pl.entries_from_bench(_bench_doc(), "BENCH_r09.json",
                                    round_tag="r09")
    by_src = {e["source"]: e for e in entries}
    assert set(by_src) == {"bench", "bench_feed"}
    assert by_src["bench"]["metrics"]["train_img_s"] == 120.0
    assert by_src["bench"]["sha"] == "abc1234"
    assert by_src["bench"]["fp"]["model"] == "lenet"
    assert by_src["bench_feed"]["metrics"]["feed_decode_s"] == 0.001
    assert all(e["round"] == "r09" for e in entries)


def test_bench_ingester_skips_failed_captures():
    assert pl.entries_from_bench({"parsed": None, "rc": 1}) == []
    assert pl.entries_from_bench({"error": "boom"}) == []
    assert pl.entries_from_bench({"metric": "m", "value": 0}) == []


def test_driver_wrapper_unwraps():
    doc = {"n": 2, "rc": 0, "tail": "...", "parsed": _bench_doc()}
    entries = pl.entries_from_any(doc, "BENCH_r09.json")
    assert {e["source"] for e in entries} == {"bench", "bench_feed"}


def test_op_table_ingester_prefixes_profile_metrics():
    doc = {"summary": {"model": "caffenet", "dtype": "bf16", "batch": 256,
                       "device": "tpu/TPU v5 lite", "step_ms": 50.0,
                       "img_s": 5000.0, "mfu": 0.2},
           "by_category": [{"op": "loop fusion", "total_ms": 30.0,
                            "gb_per_s": 1000.0}]}
    (e,) = pl.entries_from_op_table(doc, "profiles/x/op_table.json")
    # profile captures carry profiling overhead: their img_s/mfu must
    # not pool into the bench baselines
    assert "profile_img_s" in e["metrics"]
    assert "profile_mfu" in e["metrics"]
    assert "mfu" not in e["metrics"]
    assert e["metrics"]["cat_ms/loop fusion"] == 30.0


def test_entries_from_any_dispatches_serving():
    doc = {"metric": "serving_dynamic_vs_batch1_speedup_x", "value": 5.9,
           "model": "lenet", "dtype": "bf16", "batch_shapes": [1, 4, 16],
           "device": "cpu/cpu",
           "saturation": {"achieved_qps": 4000.0, "p99_ms": 20.0},
           "batch1": {"achieved_qps": 700.0},
           "overload": {"p99_ms": 110.0, "achieved_qps": 2500.0,
                        "rejected": 100}}
    (e,) = pl.entries_from_any(doc, "BENCH_serving_r07.json")
    assert e["source"] == "serving"
    assert e["round"] == "r07"
    assert e["metrics"]["serve_sat_qps"] == 4000.0
    assert e["metrics"]["serve_speedup_x"] == 5.9


# ---------------------------------------------------------------------------
# The regress sentinel
# ---------------------------------------------------------------------------

def _seeded_ledger(tmp_path, n=3, img_s=800.0):
    led = pl.PerfLedger(str(tmp_path / "L.jsonl"))
    for i in range(n):
        for e in pl.entries_from_bench(_bench_doc(), "seed",
                                       t=float(i)):
            led.append(e)
    assert img_s == 800.0    # seed feed rate the tests regress against
    return led


def test_regress_within_band_exits_ok(tmp_path):
    led = _seeded_ledger(tmp_path)
    out = perfwatch.run_regress(_bench_doc(), led, min_band_frac=0.10)
    assert out["ok"]
    assert out["regressions"] == 0
    assert out["metrics_gated"] > 0


def test_regress_catches_slowed_feed_and_names_decode(tmp_path):
    led = _seeded_ledger(tmp_path)
    slow = _bench_doc()
    # a 4x slower feed leg whose growth sits in the decode stage — the
    # synthetic regression of the acceptance criteria
    slow["feed_in_loop"].update(images_per_sec=200.0, step_s=0.04,
                                decode_s=0.031)
    out = perfwatch.run_regress(slow, led, min_band_frac=0.10)
    assert not out["ok"]
    tripped = {r["metric"]: r for r in out["results"]
               if r["verdict"] == "regression"}
    assert "feed_img_s" in tripped
    attr = tripped["feed_img_s"]["attribution"]
    assert attr["metric"] == "feed_decode_s"
    assert "decode" in attr["stage"]


def test_regress_cpu_capture_never_gates_on_tpu_ledger(tmp_path):
    led = pl.PerfLedger(str(tmp_path / "L.jsonl"))
    tpu_fp = pl.fingerprint(model="lenet", dtype="f32", batch=8,
                            device="tpu/TPU v5 lite")
    for i in range(5):
        led.append(pl.make_entry("bench", None, tpu_fp,
                                 {"train_img_s": 18000.0}, t=float(i)))
    # same model/dtype/batch, CPU device, catastrophically "slower" —
    # and still not a regression, because it has no baseline to gate on
    out = perfwatch.run_regress(_bench_doc(), led)
    assert out["ok"]
    assert out["metrics_gated"] == 0
    assert all(r["verdict"] == "not_gated" for r in out["results"])


def test_regress_stage_metrics_attribute_but_never_gate(tmp_path):
    led = _seeded_ledger(tmp_path)
    out = perfwatch.run_regress(_bench_doc(), led, min_band_frac=0.10)
    checked = {r["metric"] for r in out["results"]}
    assert "feed_decode_s" not in checked
    assert "feed_device_put_s" not in checked


# ---------------------------------------------------------------------------
# The op-profile differ + fusion worklist
# ---------------------------------------------------------------------------

def _profile_fixture(step_ms, lrn_ms, lrn_gbs, with_lrn_cat=True):
    by_cat = [
        {"op": "convolution fusion", "total_ms": 100.0, "pct": 50.0,
         "gb_per_s": 480.0, "gflops_per_s": 80000.0},
        {"op": "loop fusion", "total_ms": 40.0, "pct": 20.0,
         "gb_per_s": 1000.0},
    ]
    if with_lrn_cat:
        by_cat.append({"op": "reduce-window", "total_ms": 15.0,
                       "pct": 7.0, "gb_per_s": 620.0})
    return {
        "summary": {"model": "googlenet", "dtype": "bf16", "batch": 128,
                    "device": "tpu/TPU v5 lite", "step_ms": step_ms},
        "by_category": by_cat,
        "by_layer": [
            # MXU-bound conv: high achieved GFLOP/s, must be excluded
            {"op": "conv2/3x3", "total_ms": 50.0, "pct": 25.0,
             "gb_per_s": 400.0, "gflops_per_s": 90000.0},
            # the unfused LRN chain — the worklist's raison d'etre
            {"op": "conv2/norm2", "total_ms": lrn_ms, "pct": 30.0,
             "gb_per_s": lrn_gbs, "gflops_per_s": 900.0},
            # the fused neighbor that sets the reference bandwidth
            {"op": "inception_3a/output", "total_ms": 20.0, "pct": 10.0,
             "gb_per_s": 1013.0, "gflops_per_s": 1200.0},
            # sub-floor sliver: must not become a candidate
            {"op": "tiny/relu", "total_ms": 0.1, "pct": 0.05,
             "gb_per_s": 100.0, "gflops_per_s": 10.0},
            {"op": "(outside layers)", "total_ms": 5.0, "pct": 2.0,
             "gb_per_s": 50.0},
        ],
    }


def test_diff_joins_categories_and_ranks_lrn_chain():
    a = _profile_fixture(step_ms=60.0, lrn_ms=61.0, lrn_gbs=555.0)
    b = _profile_fixture(step_ms=50.0, lrn_ms=55.0, lrn_gbs=555.0)
    out = perfwatch.diff_profiles(a, b)
    assert out["step_delta_ms"] == pytest.approx(-10.0)
    cats = {c["op"]: c for c in out["categories"]}
    assert cats["convolution fusion"]["status"] == "both"
    assert cats["convolution fusion"]["delta_ms"] == 0.0
    wl = out["fusion_worklist"]
    top = wl["candidates"][0]
    assert top["chain"] == "conv2/norm2"
    assert top["kind"] == "conv+bias+relu+LRN"
    assert top["gb_per_s"] == 555.0
    assert "555 GB/s" in top["note"]
    # reclaimable = total_ms * (1 - gb/ref) against the fused neighbor
    assert top["ref_gb_per_s"] == pytest.approx(1013.0)
    assert top["reclaimable_ms"] == pytest.approx(
        55.0 * (1 - 555.0 / 1013.0), abs=0.02)
    # MXU-bound conv and the sliver are excluded
    names = {c["chain"] for c in wl["candidates"]}
    assert "conv2/3x3" not in names
    assert "tiny/relu" not in names
    assert "(outside layers)" not in names


def test_diff_missing_category_edge():
    # a category vanishing between captures (e.g. LRN custom-call after
    # a fusion pass) must surface as only_in_a with its full time
    a = _profile_fixture(60.0, 61.0, 555.0, with_lrn_cat=True)
    b = _profile_fixture(50.0, 55.0, 555.0, with_lrn_cat=False)
    out = perfwatch.diff_profiles(a, b)
    rw = next(c for c in out["categories"] if c["op"] == "reduce-window")
    assert rw["status"] == "only_in_a"
    assert rw["b_ms"] is None
    assert rw["delta_ms"] == pytest.approx(-15.0)
    out2 = perfwatch.diff_profiles(b, a)
    rw2 = next(c for c in out2["categories"]
               if c["op"] == "reduce-window")
    assert rw2["status"] == "only_in_b"
    assert rw2["delta_ms"] == pytest.approx(15.0)


def test_worklist_reports_fused_chains_against_ref_band():
    doc = {"by_layer": [
        {"op": "a+b+c", "total_ms": 20.0, "pct": 10.0, "gb_per_s": 1000.0},
        {"op": "slow+chain", "total_ms": 10.0, "pct": 5.0,
         "gb_per_s": 400.0},
        {"op": "norm", "total_ms": 30.0, "pct": 20.0, "gb_per_s": 500.0,
         "gflops_per_s": 100.0},
    ]}
    wl = perfwatch.fusion_worklist(doc)
    assert [c["chain"] for c in wl["candidates"]] == ["norm"]
    assert wl["candidates"][0]["kind"] == perfwatch.chain_kind("norm") \
        == "conv+bias+relu+LRN"
    fused = {c["chain"]: c for c in wl["fused_chains"]}
    assert fused["a+b+c"]["at_ref_band"] is True
    assert fused["slow+chain"]["at_ref_band"] is False


def test_worklist_without_by_layer_says_so():
    doc = {"summary": {"model": "m"}, "by_category": []}
    wl = perfwatch.fusion_worklist(doc)
    assert wl["candidates"] == []
    assert "by_layer" in wl["note"]


def test_diff_on_committed_profiles_names_the_verdict_chain():
    # the acceptance pair: the googlenet bf16 LRN chain VERDICT.md pins
    # at 555 GB/s must top the committed-profile worklist
    with open(os.path.join(REPO, "profiles", "googlenet_bf16",
                           "op_table.json")) as f:
        b = json.load(f)
    with open(os.path.join(REPO, "profiles", "googlenet",
                           "op_table.json")) as f:
        a = json.load(f)
    out = perfwatch.diff_profiles(a, b)
    top = out["fusion_worklist"]["candidates"][0]
    assert top["chain"] == "conv2/norm2"
    assert top["gb_per_s"] == pytest.approx(555.2, abs=0.5)


# ---------------------------------------------------------------------------
# Trajectory
# ---------------------------------------------------------------------------

def test_trajectory_builds_rounds_and_splices_idempotently(tmp_path):
    led = pl.PerfLedger(str(tmp_path / "L.jsonl"))
    fp = pl.fingerprint(model="caffenet", dtype="bf16", batch=256,
                        device="tpu/TPU v5 lite")
    led.append(pl.make_entry("bench", "BENCH_r02.json", fp,
                             {"train_img_s": 10000.0, "mfu": 0.2},
                             round_tag="r02", t=1.0, sha="aaa"))
    led.append(pl.make_entry("bench", "BENCH_r05.json", fp,
                             {"train_img_s": 18000.0, "mfu": 0.35},
                             round_tag="r05", t=2.0, sha="bbb"))
    traj = perfwatch.build_trajectory(led)
    assert [r["round"] for r in traj["rounds"]] == ["r02", "r05"]
    assert traj["rounds"][1]["train_img_s"] == 18000.0
    block = perfwatch.render_trajectory_md(traj)
    text = "# RESULTS\n\n## Old section\nbody\n"
    once = perfwatch.splice_markers(text, block)
    twice = perfwatch.splice_markers(once, block)
    assert once == twice                      # idempotent
    assert once.count(perfwatch._TRAJ_BEGIN) == 1
    assert "| r02 |" in once and "| r05 |" in once
    assert "## Old section" in once


def test_trajectory_prefers_best_train_capture_per_round(tmp_path):
    led = pl.PerfLedger(str(tmp_path / "L.jsonl"))
    slow = pl.fingerprint(model="caffenet", dtype="f32", batch=256,
                          device="tpu/TPU v5 lite")
    fast = pl.fingerprint(model="caffenet", dtype="bf16", batch=256,
                          device="tpu/TPU v5 lite")
    led.append(pl.make_entry("bench", None, slow,
                             {"train_img_s": 13000.0}, round_tag="r05",
                             t=1.0))
    led.append(pl.make_entry("bench", None, fast,
                             {"train_img_s": 18000.0}, round_tag="r05",
                             t=1.1))
    (row,) = perfwatch.build_trajectory(led)["rounds"]
    assert row["train_img_s"] == 18000.0
    assert row["dtype"] == "bf16"


# ---------------------------------------------------------------------------
# SLO monitor
# ---------------------------------------------------------------------------

from sparknet_tpu.parallel.serving import ServeConfig, SLOMonitor  # noqa: E402


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def _slo_cfg(**kw):
    kw.setdefault("slo_reject_budget", 0.02)
    kw.setdefault("slo_window_s", 60.0)
    kw.setdefault("slo_fast_window_s", 5.0)
    return ServeConfig(**kw)


class _Stats:
    """Scripted engine counters the monitor samples."""

    def __init__(self):
        self.completed = 0
        self.rejected = 0
        self.failed = 0
        self.p99 = 10.0

    def __call__(self):
        return {"completed": self.completed,
                "rejected": {"queue_full": self.rejected},
                "failed": self.failed, "p99_ms": self.p99}


@pytest.fixture
def tel(monkeypatch):
    for k in ("SPARKNET_TELEMETRY", "SPARKNET_TRACE_DIR",
              "SPARKNET_METRICS_SNAP"):
        monkeypatch.delenv(k, raising=False)
    telemetry.reset()
    yield monkeypatch
    telemetry.reset()


def test_slo_healthy_traffic_stays_ok(tel):
    clock, st = _Clock(), _Stats()
    mon = SLOMonitor(st, _slo_cfg(), clock=clock)
    for _ in range(20):
        clock.t += 0.5
        st.completed += 100           # zero rejections
        doc = mon.evaluate()
    assert doc["state"] == "ok"
    assert doc["breaches"] == []
    assert mon.breaches == 0


def test_slo_sustained_overload_breaches_with_flight_dump(tel, tmp_path):
    tel.setenv("SPARKNET_TRACE_DIR", str(tmp_path))
    telemetry.reset()
    clock, st = _Clock(), _Stats()
    mon = SLOMonitor(st, _slo_cfg(), clock=clock)
    doc = None
    for _ in range(20):               # 10 s of 50% rejections: 25x burn
        clock.t += 0.5
        st.completed += 50
        st.rejected += 50
        doc = mon.evaluate()
    assert doc["state"] == "breach"
    assert "availability" in doc["breaches"]
    assert doc["windows"]["fast"]["burn"] >= 4.0
    assert mon.breaches == 1          # one transition, not one per sample
    assert mon.dumps == 1
    dumps = [p for p in os.listdir(tmp_path) if p.startswith("flight_")]
    assert len(dumps) == 1
    with open(os.path.join(tmp_path, dumps[0])) as f:
        dumped = json.load(f)
    assert any(e["kind"] == "slo_breach" for e in dumped["events"])


def test_slo_short_blip_never_pages(tel):
    # the multi-window pattern: a burst of rejections inside an
    # otherwise long healthy window burns the fast window but not the
    # slow one — no page
    clock, st = _Clock(), _Stats()
    mon = SLOMonitor(st, _slo_cfg(), clock=clock)
    for _ in range(110):              # 55 s of clean traffic
        clock.t += 0.5
        st.completed += 100
        mon.evaluate()
    clock.t += 0.5                    # one bad second
    st.rejected += 200
    st.completed += 60
    doc = mon.evaluate()
    assert doc["windows"]["fast"]["burn"] >= 4.0
    assert doc["windows"]["slow"]["burn"] < 1.0
    assert doc["state"] == "ok"


def test_slo_min_requests_guards_tiny_samples(tel):
    clock, st = _Clock(), _Stats()
    mon = SLOMonitor(st, _slo_cfg(), clock=clock)
    clock.t += 0.5
    st.rejected += 5                  # 100% bad, but only 5 requests
    doc = mon.evaluate()
    assert doc["state"] == "ok"


def test_slo_latency_bound_breaches_and_recovers(tel):
    clock, st = _Clock(), _Stats()
    mon = SLOMonitor(st, _slo_cfg(), clock=clock)
    mon.p99_ms = 100.0                # runtime-declared bound
    for _ in range(4):
        clock.t += 0.5
        st.completed += 100
        st.p99 = 250.0                # sustained over the bound
        doc = mon.evaluate()
    assert doc["state"] == "breach"
    assert doc["breaches"] == ["latency"]
    # p99 windows use max-of-samples, so recovery needs the bad samples
    # to age out of BOTH windows
    clock.t += 61.0
    st.p99 = 20.0
    st.completed += 100
    doc = mon.evaluate()
    assert doc["state"] == "ok"
    assert mon.breaches == 1


def test_slo_undeclared_latency_not_evaluated(tel):
    clock, st = _Clock(), _Stats()
    mon = SLOMonitor(st, _slo_cfg(), clock=clock)
    assert mon.p99_ms is None
    for _ in range(10):
        clock.t += 0.5
        st.completed += 100
        st.p99 = 1e9                  # absurd p99, no declared bound
        doc = mon.evaluate()
    assert doc["state"] == "ok"


def test_slo_reset_fences_history(tel):
    clock, st = _Clock(), _Stats()
    mon = SLOMonitor(st, _slo_cfg(), clock=clock)
    for _ in range(10):
        clock.t += 0.5
        st.completed += 50
        st.rejected += 50
        mon.evaluate()
    assert mon.state == "breach"
    mon.reset()                       # the measurement fence
    assert mon.state == "ok"
    clock.t += 0.5
    st.completed += 100               # clean traffic after the fence
    doc = mon.evaluate()
    assert doc["state"] == "ok"
    assert doc["windows"]["fast"]["bad"] == 0


def test_slo_config_validation():
    with pytest.raises(ValueError):
        _slo_cfg(slo_reject_budget=0.0)
    with pytest.raises(ValueError):
        _slo_cfg(slo_reject_budget=1.5)
    with pytest.raises(ValueError):
        _slo_cfg(slo_p99_ms=-5.0)
    with pytest.raises(ValueError):
        _slo_cfg(slo_window_s=1.0, slo_fast_window_s=5.0)


def test_slo_summary_rides_engine_stats_shape(tel):
    mon = SLOMonitor(_Stats(), _slo_cfg(), clock=_Clock())
    s = mon.summary()
    assert s == {"state": "ok", "breaches": 0}
