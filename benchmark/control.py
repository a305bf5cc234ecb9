"""The controls of a cell's comparison: results it has to refuse.

    python benchmark/control.py --workload <name> --seed <n>

One process on the chip, set up as ``run.py`` sets a cell up as far as its
check (the weights and the tokens the seed gives, at the cell's own
sizes), then each control the configuration file lists under
``check.controls`` through the driver's own comparison and limits: one
JSON line a control, the verdict of ``Driver.compare`` with ``refused_by``,
the numbers over their limits.  The exit code is 0 where
every control was refused and 1 where the comparison let one pass: its
limits then do not hold what the control stands for.  Only a driver with a
``control`` method has any (``drivers/solver_seq.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv: list[str] | None = None, *, spec_path: str | None = None,
         traffic_dir: str | None = None) -> int:
    """The keyword arguments are for ``benchmark/tests``, as ``run.main``'s
    are."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    from benchmark.lib import harness
    from sparknet_tpu.utils.compile_cache import use_compile_cache

    spec = harness.load_json(spec_path
                             or os.path.join(REPO, "BENCHMARK.json"))
    cell = harness.resolve_cell(spec, args.workload, args.seed,
                                traffic_dir=traffic_dir)
    use_compile_cache()
    driver = harness.load_driver(cell.mix).Driver(cell)
    passed = []
    try:
        driver.build()
        for name in cell.config["check"]["controls"]:
            verdict = driver.control(name)
            print(json.dumps({"control": name, "seed": args.seed,
                              **verdict}), flush=True)
            if verdict["ok"]:
                passed.append(name)
    finally:
        driver.close()
    if passed:
        print(f"control: the comparison let {passed} pass",
              file=sys.stderr)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
