"""Lowering autotuner CLI — measured per-(op, shape, dtype, backend)
kernel selection (sparknet_tpu/graph/tuner.py is the library; this is
the capture/CI surface, the generalization of tools/perf_probe.py's
one-off LRN/pool probes into a maintained selection loop).

Subcommands:

  run        Measure the model-zoo key set (CaffeNet/GoogLeNet LRN
             shapes, CaffeNet conv1-3, pool1/2/5, and the two fused
             relu+lrn epilogue shapes) and write the schema-versioned
             winners table ``profiles/<backend>/tuning.json`` that
             ``SPARKNET_TUNE=auto`` consults at trace time.  Every
             candidate's timing is persisted — including disqualified
             (numerics contract violated), ineligible (not forward-bit-
             identical to the default) and typed-skipped ones — so the
             table IS the evidence.  ``--ingest`` appends the capture
             to perf/LEDGER.jsonl.

  staleness  Re-probe the committed table's worst-margin and oldest
             entries within ``--budget-s`` and exit non-zero if any
             persisted winner no longer wins by more than the noise
             band (fresh timings land in the report) — the CI loop
             that catches hardware/compiler drift before users do.

  tunebench  ~10 s CPU self-test for tools/run_tier1.sh
             (SPARKNET_TUNEBENCH=1): tunes a 2-op synthetic net and
             asserts the winner beats a planted 3x-work slow
             candidate, a planted numerics-bad candidate can never be
             persisted as winner, SPARKNET_TUNE=off vs the fresh table
             is forward-bit-identical (grads <= 1e-5 rel) through the
             production layer paths, the fresh table passes the
             staleness gate, and a planted rotten winner fails it.

Usage:
    python tools/tune.py run [--batch-div 16] [--only lrn,conv1]
                             [--out FILE] [--ingest] [--allow-inexact]
    python tools/tune.py staleness [--table FILE] [--budget-s 60]
    python tools/tune.py tunebench [--json FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _log(msg: str) -> None:
    print(f"[tune] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# run: the model-zoo key set
# ---------------------------------------------------------------------------

def zoo_keys(batch_div: int = 16, dtype: str = "f32"):
    """The capture key set: every (op, shape) the LRN-bearing headline
    models consult at trace time, at batch 256//div (CaffeNet) and
    128//div (GoogLeNet) — the same divisor knob perf_probe's
    PROBE_LRN_BATCH_DIV uses so CPU captures stay tractable while TPU
    captures (div=1) run the production batch."""
    from sparknet_tpu.graph import tuner

    div = max(1, batch_div)
    bg, bc = max(1, 128 // div), max(1, 256 // div)
    keys = [
        # the four zoo LRN shapes (perf_probe run_lrn's set)
        tuner.TuneKey("lrn", (bg, 64, 56, 56), dtype, tuner.lrn_extra(5)),
        tuner.TuneKey("lrn", (bg, 192, 56, 56), dtype, tuner.lrn_extra(5)),
        tuner.TuneKey("lrn", (bc, 96, 55, 55), dtype, tuner.lrn_extra(5)),
        tuner.TuneKey("lrn", (bc, 256, 27, 27), dtype, tuner.lrn_extra(5)),
        # CaffeNet conv1-3 (stem stride-4, grouped 5x5, plain 3x3)
        tuner.TuneKey("conv", (bc, 3, 227, 227), dtype,
                      tuner.conv_extra(11, 11, 4, 4, 0, 0, 1, 1, 96, 1)),
        tuner.TuneKey("conv", (bc, 96, 27, 27), dtype,
                      tuner.conv_extra(5, 5, 1, 1, 2, 2, 1, 1, 256, 2)),
        tuner.TuneKey("conv", (bc, 256, 13, 13), dtype,
                      tuner.conv_extra(3, 3, 1, 1, 1, 1, 1, 1, 384, 1)),
        # CaffeNet pool1/2/5 (all MAX k3 s2 p0)
        tuner.TuneKey("pool", (bc, 96, 55, 55), dtype,
                      tuner.pool_extra(3, 3, 2, 2, 0, 0)),
        tuner.TuneKey("pool", (bc, 256, 27, 27), dtype,
                      tuner.pool_extra(3, 3, 2, 2, 0, 0)),
        tuner.TuneKey("pool", (bc, 256, 13, 13), dtype,
                      tuner.pool_extra(3, 3, 2, 2, 0, 0)),
        # CaffeNet's two fused relu+lrn chain epilogues (norm1/norm2)
        tuner.TuneKey("lrn_epilogue", (bc, 96, 55, 55), dtype,
                      tuner.epilogue_extra(5, True)),
        tuner.TuneKey("lrn_epilogue", (bc, 256, 27, 27), dtype,
                      tuner.epilogue_extra(5, True)),
    ]
    return keys


def _ingest(table_path: str) -> int:
    from sparknet_tpu.utils import perfledger as pl
    ledger = pl.PerfLedger()
    with open(table_path) as f:
        doc = json.load(f)
    rel = os.path.relpath(os.path.abspath(table_path), REPO)
    if rel.startswith(".."):
        rel = table_path
    entries = pl.entries_from_any(doc, rel)
    n = ledger.extend(entries)
    _log(f"ingested {n} ledger entr{'y' if n == 1 else 'ies'} "
         f"from {rel} into {os.path.relpath(ledger.path, REPO)}")
    return n


def cmd_run(args) -> int:
    from sparknet_tpu.graph import tuner

    keys = zoo_keys(args.batch_div, args.dtype)
    if args.only:
        pats = [p for p in args.only.split(",") if p]
        keys = [k for k in keys if any(p in str(k) for p in pats)]
    if not keys:
        _log("no keys selected (check --only)")
        return 2
    _log(f"measuring {len(keys)} keys on backend "
         f"{tuner._backend()!r} (batch-div {args.batch_div})")

    t0 = time.monotonic()

    def progress(e):
        tags = []
        for name, rec in e["timings"].items():
            if "skipped" in rec:
                tags.append(f"{name}:skip")
            elif "disqualified" in rec:
                tags.append(f"{name}:DQ {rec['ms']}ms")
            elif "ineligible" in rec:
                tags.append(f"{name}:inel {rec['ms']}ms")
            else:
                tags.append(f"{name}:{rec['ms']}ms")
        flip = " FLIP" if e["flip"] else ""
        _log(f"{e['key']}: winner {e['winner']}{flip} "
             f"(margin {e['margin']}, {'; '.join(tags)})")

    table = tuner.build_table(keys, reps=args.reps, target_s=args.target_s,
                              warmup=args.warmup,
                              allow_inexact=args.allow_inexact,
                              progress=progress)
    out = args.out or tuner.default_table_path()
    table.save(out)
    flips = sum(1 for e in table.entries if e.get("flip"))
    _log(f"wrote {len(table.entries)} entries ({flips} flips vs hardcoded "
         f"defaults) -> {out} [{table.table_id()}] in "
         f"{time.monotonic() - t0:.0f}s")
    if args.ingest:
        _ingest(out)
    print(json.dumps({"ok": True, "table": out,
                      "table_id": table.table_id(),
                      "entries": len(table.entries), "flips": flips}),
          flush=True)
    return 0


# ---------------------------------------------------------------------------
# staleness: the CI re-probe gate
# ---------------------------------------------------------------------------

def cmd_staleness(args) -> int:
    from sparknet_tpu.graph import tuner

    path = args.table or tuner.default_table_path()
    if not os.path.isfile(path):
        _log(f"no tuning table at {path} — nothing to check (run "
             f"`tools/tune.py run` first)")
        return 0 if args.missing_ok else 2
    table = tuner.TuningTable.load(path)
    backend = tuner._backend()
    if table.backend != backend:
        _log(f"{path} was captured on {table.backend!r}; this host is "
             f"{backend!r} — staleness here would compare apples to "
             f"oranges, skipping")
        return 0
    _log(f"re-probing {path} [{table.table_id()}] within "
         f"{args.budget_s:.0f}s budget")
    report = tuner.staleness_check(
        table, budget_s=args.budget_s, reps=args.reps,
        target_s=args.target_s, warmup=args.warmup,
        allow_inexact=args.allow_inexact)
    for rec in report["results"]:
        state = "ROTTEN" if "rotten" in rec else "fresh"
        slack = rec.get("slack")
        _log(f"{rec['key']}: {state} (persisted {rec['persisted_winner']}, "
             f"fresh {rec['fresh_winner']}, slack {slack}, "
             f"band {rec['noise_band']})")
    line = json.dumps(report)
    print(line, flush=True)
    if args.json:
        with open(args.json, "w") as f:
            f.write(line + "\n")
    if not report["ok"]:
        for rec in report["rotten"]:
            _log(f"STALE: {rec['rotten']}")
            _log(f"  fresh timings: "
                 f"{json.dumps(rec['fresh_timings'], sort_keys=True)}")
        _log(f"{len(report['rotten'])}/{report['checked']} re-probed "
             f"entries are stale — re-run `tools/tune.py run` and commit "
             f"the fresh table")
        return 1
    _log(f"{report['checked']}/{report['total_entries']} entries "
         f"re-probed, all winners still win")
    return 0


# ---------------------------------------------------------------------------
# tunebench: the run_tier1.sh self-test
# ---------------------------------------------------------------------------

def _tunebench_net():
    """conv -> lrn -> ip -> loss: the 2-op tunable net (one conv key,
    one lrn key) the self-test tunes."""
    from sparknet_tpu.models.dsl import (
        convolution_layer,
        inner_product_layer,
        layer,
        lrn_layer,
        net_param,
        softmax_with_loss_layer,
    )
    layers = [
        layer("data", "Input", tops=["data", "label"],
              input_param={"shape": [{"dim": [2, 3, 12, 12]},
                                     {"dim": [2]}]}),
        convolution_layer("c1", "data", "c1", num_output=8, kernel=3,
                          pad=1, weight_filler={"type": "gaussian",
                                                "std": 0.05},
                          bias_filler={"type": "constant", "value": 0.1}),
        lrn_layer("n1", "c1", "n1", local_size=5, alpha=1e-4, beta=0.75),
        inner_product_layer("ip", "n1", "ip", num_output=5,
                            weight_filler={"type": "gaussian",
                                           "std": 0.01}),
        softmax_with_loss_layer("loss", ["ip", "label"]),
    ]
    return net_param("tunebench", layers)


def cmd_tunebench(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.graph import tuner
    from sparknet_tpu.graph.net import Net
    from sparknet_tpu.proto import NetState, Phase

    failures: list[str] = []
    t0 = time.monotonic()
    netp = _tunebench_net()

    def build(tune: str) -> Net:
        os.environ["SPARKNET_TUNE"] = tune
        try:
            return Net(netp, NetState(Phase.TRAIN))
        finally:
            os.environ.pop("SPARKNET_TUNE", None)
        # Net build latches the plan id; layer tracing re-reads the env,
        # so apply() below re-sets SPARKNET_TUNE around the trace.

    # -- plant the adversarial candidates --------------------------------
    # planted_slow: genuinely 3x the arithmetic (three base evaluations
    # on inputs XLA cannot prove equal), declared inexact — it must be
    # timed, must lose, and being non-bit-identical must stay ineligible
    def slow_factory(key, prob):
        base = prob.fns["reduce_window"]

        def slow(x):
            return (base(x) + base(x * (1.0 + 1e-5))
                    + base(x * (1.0 - 1e-5))) / 3.0
        return slow

    # planted_bad: declares forward-exact but is off by 9e-4 — the
    # numerics check must disqualify it before it can ever win
    def bad_factory(key, prob):
        native = prob.fns["native"]

        def bad(x, w):
            return native(x, w) * 1.0009
        return bad

    tuner.clear_extra_candidates()
    tuner.register_candidate(
        "lrn",
        tuner.Candidate("planted_slow", exact=False, rtol=1e-3,
                        grad_rtol=1e-3,
                        note="tunebench: 3x-work decoy, must lose"),
        slow_factory)
    tuner.register_candidate(
        "conv",
        tuner.Candidate("planted_bad", exact=True,
                        note="tunebench: wrong numerics, must be DQ'd"),
        bad_factory)

    try:
        probe_net = build("off")
        keys = tuner.keys_for_net(probe_net)
        ops = sorted({k.op for k in keys})
        if ops != ["conv", "lrn"]:
            failures.append(f"expected one conv + one lrn key, got "
                            f"{[str(k) for k in keys]}")

        table = tuner.build_table(keys, reps=args.reps,
                                  target_s=args.target_s,
                                  warmup=args.warmup)

        lrn_e = next(e for e in table.entries if e["op"] == "lrn")
        conv_e = next(e for e in table.entries if e["op"] == "conv")

        slow_rec = lrn_e["timings"].get("planted_slow", {})
        win_ms = lrn_e["timings"][lrn_e["winner"]]["ms"]
        if "ms" not in slow_rec:
            failures.append(f"planted_slow was not timed: {slow_rec}")
        elif slow_rec["ms"] <= win_ms:
            failures.append(
                f"winner {lrn_e['winner']} ({win_ms} ms) did not beat "
                f"planted 3x-work candidate ({slow_rec['ms']} ms) — the "
                f"timer is not measuring")
        if lrn_e["winner"] == "planted_slow":
            failures.append("planted_slow WON the lrn key")

        bad_rec = conv_e["timings"].get("planted_bad", {})
        if "disqualified" not in bad_rec:
            failures.append(f"planted_bad was not disqualified: {bad_rec}")
        if conv_e["winner"] == "planted_bad":
            failures.append("numerics-failing planted_bad was persisted "
                            "as winner")

        # -- off vs fresh-table parity through the production layers -----
        table_path = os.path.join(args.tmpdir, "tunebench_table.json")
        table.save(table_path)
        reloaded = tuner.TuningTable.load(table_path)
        if reloaded.table_id() != table.table_id():
            failures.append("table did not round-trip")

        net_off = build("off")
        net_tab = build(table_path)
        if net_off.tune_plan_id() != "off":
            failures.append(f"SPARKNET_TUNE=off latched "
                            f"{net_off.tune_plan_id()!r}")
        if net_tab.tune_plan_id() != table.table_id():
            failures.append(f"table net latched "
                            f"{net_tab.tune_plan_id()!r} != "
                            f"{table.table_id()!r}")

        rng = jax.random.PRNGKey(0)
        params = net_off.init(rng)
        r = np.random.default_rng(0)
        ins = {"data": jnp.asarray(
            r.normal(size=net_off.input_blobs["data"]), jnp.float32),
            "label": jnp.asarray(
                r.integers(0, 5, size=net_off.input_blobs["label"]),
                jnp.float32)}

        def loss_fn(net, tune):
            def f(p):
                os.environ["SPARKNET_TUNE"] = tune
                try:
                    return net.apply(p, ins, rng=rng).loss
                finally:
                    os.environ.pop("SPARKNET_TUNE", None)
            return f

        l_off, g_off = jax.value_and_grad(loss_fn(net_off, "off"))(params)
        l_tab, g_tab = jax.value_and_grad(
            loss_fn(net_tab, table_path))(params)
        if float(l_off) != float(l_tab):
            failures.append(f"forward loss not bit-identical: "
                            f"{float(l_off)!r} (off) vs {float(l_tab)!r} "
                            f"(tuned)")
        grad_rel = 0.0
        for k in g_off:
            for a, b in zip(g_off[k], g_tab[k]):
                a64 = np.asarray(a, np.float64)
                b64 = np.asarray(b, np.float64)
                denom = float(np.max(np.abs(a64))) or 1.0
                grad_rel = max(grad_rel,
                               float(np.max(np.abs(a64 - b64))) / denom)
        if grad_rel > 1e-5:
            failures.append(f"tuned-vs-off gradient divergence "
                            f"{grad_rel:.3e} exceeds 1e-5")

        # -- staleness gate: fresh table passes --------------------------
        fresh = tuner.staleness_check(table, budget_s=60.0,
                                      reps=args.reps,
                                      target_s=args.target_s,
                                      warmup=args.warmup)
        if not fresh["ok"]:
            failures.append(f"fresh table flagged stale: "
                            f"{[r['rotten'] for r in fresh['rotten']]}")

        # -- staleness gate: planted rotten winner fails ------------------
        # pin the lrn entry's persisted winner to the 3x-work decoy and
        # shrink its recorded margin/noise so the gate must re-probe it
        # first and must see through it
        rot_entries = json.loads(json.dumps(table.entries))
        for e in rot_entries:
            if e["op"] == "lrn":
                e["winner"] = "planted_slow"
                e["margin"] = 0.0
                e["noise_band"] = 0.05
        rotten_table = tuner.TuningTable(table.backend, rot_entries,
                                         table.provenance)
        rot = tuner.staleness_check(rotten_table, budget_s=60.0,
                                    reps=args.reps,
                                    target_s=args.target_s,
                                    warmup=args.warmup)
        if rot["ok"]:
            failures.append("staleness gate missed the planted rotten "
                            "winner")
        else:
            bad = next((r for r in rot["rotten"]
                        if r["persisted_winner"] == "planted_slow"), None)
            if bad is None:
                failures.append(f"rot report does not name the planted "
                                f"winner: {rot['rotten']}")
            elif not bad.get("fresh_timings"):
                failures.append("rot report is missing the re-probed "
                                "timings")
    finally:
        tuner.clear_extra_candidates()
        tuner._clear_caches()

    result = {
        "ok": not failures,
        "failures": failures,
        "backend": jax.default_backend(),
        "table_id": table.table_id(),
        "winners": {e["key"]: e["winner"] for e in table.entries},
        "planted_slow_ms": slow_rec.get("ms"),
        "planted_bad": bad_rec.get("disqualified"),
        "grad_max_rel": grad_rel,
        "staleness_fresh_ok": fresh["ok"],
        "staleness_planted_caught": not rot["ok"],
        "elapsed_s": round(time.monotonic() - t0, 1),
    }
    line = json.dumps(result)
    print(line, flush=True)
    if args.json:
        with open(args.json, "w") as f:
            f.write(line + "\n")
    if failures:
        _log(f"TUNEBENCH FAILURE: {failures}")
        return 1
    _log(f"tunebench ok in {result['elapsed_s']}s: winners "
         f"{result['winners']}, planted_slow timed at "
         f"{result['planted_slow_ms']} ms and lost, planted_bad "
         f"disqualified, off-vs-tuned bit-identical "
         f"(grad ulp {grad_rel:.1e}), staleness gate catches the "
         f"planted rot")
    return 0


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="lowering autotuner: measure, persist, re-probe")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def timing_args(p):
        p.add_argument("--reps", type=int, default=None,
                       help="median-of-k reps (SPARKNET_TUNE_REPS)")
        p.add_argument("--target-s", type=float, default=None,
                       help="per-rep wall target (SPARKNET_TUNE_TARGET_S)")
        p.add_argument("--warmup", type=int, default=None,
                       help="discarded warm-up blocks "
                            "(SPARKNET_TUNE_WARMUP)")
        p.add_argument("--allow-inexact", action="store_true",
                       help="let non-bit-identical candidates win "
                            "(declared rtol still enforced); leaves "
                            "SPARKNET_TUNE=auto no longer bit-equal "
                            "to =off")

    p_run = sub.add_parser("run", help="measure the zoo key set and "
                                       "write profiles/<backend>/"
                                       "tuning.json")
    p_run.add_argument("--batch-div", type=int, default=16,
                       help="divide zoo batches by this (16 -> CaffeNet "
                            "b16 / GoogLeNet b8 for CPU; use 1 on TPU)")
    p_run.add_argument("--dtype", default="f32",
                       choices=["f32", "bf16", "f16"])
    p_run.add_argument("--only", default="",
                       help="comma-separated substring filter on keys")
    p_run.add_argument("--out", default=None,
                       help="table path (default: the committed "
                            "profiles/<backend>/tuning.json)")
    p_run.add_argument("--ingest", action="store_true",
                       help="append the capture to perf/LEDGER.jsonl")
    timing_args(p_run)
    p_run.set_defaults(fn=cmd_run)

    p_st = sub.add_parser("staleness", help="re-probe worst-margin + "
                                            "oldest entries; rc 1 if a "
                                            "winner rotted")
    p_st.add_argument("--table", default=None)
    p_st.add_argument("--budget-s", type=float, default=60.0)
    p_st.add_argument("--json", default=None, help="also write the "
                                                   "report here")
    p_st.add_argument("--missing-ok", action="store_true",
                      help="rc 0 when no table exists yet")
    timing_args(p_st)
    p_st.set_defaults(fn=cmd_staleness)

    p_tb = sub.add_parser("tunebench", help="fast CI self-test "
                                            "(run_tier1.sh "
                                            "SPARKNET_TUNEBENCH=1)")
    p_tb.add_argument("--json", default=None)
    p_tb.add_argument("--tmpdir", default="/tmp")
    p_tb.add_argument("--reps", type=int, default=3)
    p_tb.add_argument("--target-s", type=float, default=0.02)
    p_tb.add_argument("--warmup", type=int, default=1)
    p_tb.set_defaults(fn=cmd_tunebench)

    args = ap.parse_args(argv)
    os.environ.pop("SPARKNET_TUNE", None)  # measure, don't inherit
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
