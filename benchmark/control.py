"""The control of each cell's correctness check: the reference with one
guarantee the configuration states broken, put in the program's place,
compared with the reference as a run compares the program.

    python benchmark/control.py --workload <name> --seeds 1,2,3

- batch cells: every replica of a unit is scored against the state before
  the unit's first replica (capacity still updates): the placement no
  longer follows the serial scheduler pod by pod, the shortcut a batched
  kernel is tempted by. Reads misplaced_pods.
- what-if cells: the answers' cluster sums are taken in float32 instead of
  exactly. Reads wrong_answers.

It runs on the host only (no JAX), one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import cluster  # noqa: E402
import common  # noqa: E402
import reference  # noqa: E402


def batch_control(cfg: dict, seed: int) -> int:
    import numpy as np

    c = cluster.generate(cfg, seed, 0)
    ref_cls = cluster.reference_for(cfg)
    ref = ref_cls(c).schedule_all()
    ctl = ref_cls(cluster.generate(cfg, seed, 0)).schedule_all(batch_scores=True)
    return reference.misplaced(c, np.concatenate(ctl), ref)


def whatif_control(cfg: dict, traffic: dict, seed: int, seconds: float) -> int:
    sys.path.insert(0, os.path.join(BENCH, "drivers"))
    import whatif

    c = cluster.generate(cfg, seed, 0)
    where = whatif.bind(c, seed, traffic["bind"])
    reqs = whatif.requests_for(traffic, seed, seconds)
    tmpls = [whatif.pool_template(cfg, traffic, nm, ns)
             for nm, ns in whatif.pool_for(cfg, traffic, seed)]
    pick = whatif.pick_checked(seed, reqs, range(len(reqs)), int(traffic["check"]))
    want = whatif.reference_answers(cfg, seed, where, reqs, tmpls, pick)
    got = whatif.reference_answers(cfg, seed, where, reqs, tmpls, pick, batch_scores=True)
    return sum(whatif.wrong_answer(g, g["rows"], w) for g, w in zip(got, want))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = common.cell_named(bench, args.workload)
    with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json"), encoding="utf-8") as f:
        traffic = json.load(f)
    cfg = cluster.load_config(cell["config"])
    for seed in (int(s) for s in args.seeds.split(",")):
        if traffic["driver"] == "whatif":
            doc = {"wrong_answers": whatif_control(cfg, traffic, seed, bench["run_seconds"]),
                   "limit": 0}
        else:
            doc = {"misplaced_pods": batch_control(cfg, seed), "limit": 0}
        print(json.dumps({"workload": args.workload, "seed": seed, "control": doc}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
