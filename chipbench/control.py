"""The control of `correct`: the plain reference put in the program's place,
computed in the precision below the one the configuration states.

The configuration states exact fixed-point answers (int64 at scale 4). The
step that would tempt a later PR is floating point on the device's native
32-bit lanes, so the control computes the same view in float32 and hands
that answer to the comparison a run makes (`run.checks_of`) in the place of
all three served answers: the subscriber's stream, the pgwire SELECT and the
HTTP SELECT. It has to come out as not correct.

    python -m chipbench.control --workload <cell> --seeds 1 2 3 [--refreshes 7]

Host only (NumPy): the generator's snapshot at the cell's own scale and as
many refreshes as a run makes (warm-ups and window), then one line per seed
with every number compared beside its limit. Exits 1 if any seed came out
correct. Not run by the benchmark's own runs;
`chipbench/tests/test_reference.py` keeps it at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys

import numpy as np

from .run import HERE, ROOT, checks_of, load_json, one, report, resolve


def control(config: dict, seed: int, refreshes: int, scale: float) -> dict:
    gen = resolve(config["generator"]["class"])(sf=scale, seed=seed)
    gen.snapshot()
    for _ in range(refreshes):
        gen.refresh_rows()
    live = gen.live()
    ref = importlib.import_module(config["reference"]["module"])
    lower = ref.VIEWS[config["reference"]["view"]][0](live, np.float32)
    answers = {name: (lambda: lower) for name in ("subscribe_rows_differ", "pgwire_rows_differ", "http_rows_differ")}
    checks, rows = checks_of(config, live, answers, {"refreshes_undelivered": 0, "state_arrays_off_device": 0})
    return {"seed": seed, "reference_rows": rows, "correct": report(checks), "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--refreshes", type=int, default=None, help="default: the mix's warm-ups and window")
    args = ap.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = one(bench["workloads"], args.workload)
    config = load_json(ROOT / one(bench["configs"], cell["config"])["file"])
    traffic = load_json(HERE / "workloads" / f"{cell['traffic']}.json")
    refreshes = traffic["warmups"] + (traffic.get("refreshes") or 0) if args.refreshes is None else args.refreshes
    came_out_correct = 0
    for seed in args.seeds:
        out = control(config, seed, refreshes, config["scale_factor"])
        came_out_correct += out["correct"]
        print(json.dumps({"workload": args.workload, **out}), flush=True)
    return 1 if came_out_correct else 0


if __name__ == "__main__":
    sys.exit(main())
