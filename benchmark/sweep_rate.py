"""Find the highest rate a what-if cell sustains: one set-up, then an
open-loop window at each rate, on the chip.

    python benchmark/sweep_rate.py --workload cl2-load-5k.whatif --seed 1 \\
        --seconds 10 --rates 50,100,200,400

A rate is sustained when the answers keep up: the last answer comes within
a few dispatches of the last send (drain_s), nothing is missing, and the
tail does not grow with the window. The traffic file's rate_per_s is set by
hand from this sweep at half the highest sustained rate: while a process
holds the chip its machine pauses ~110 ms several times a minute, and at
four fifths the requests queued behind one pause reach the p95 (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [BENCH, os.path.join(BENCH, "drivers"), ROOT]
    import cluster
    import common
    import whatif

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = common.cell_named(bench, args.workload)
    with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json"), encoding="utf-8") as f:
        traffic = json.load(f)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep_rate: no TPU found", file=sys.stderr)
        return 2
    cfg = cluster.load_config(cell["config"])
    st = whatif.setup(cfg, traffic, args.seed)
    ex = None
    for rate in (float(r) for r in args.rates.split(",")):
        reqs = whatif.requests_for(traffic, args.seed, args.seconds, rate)
        tmpls = st["tmpls"]
        res = whatif.open_loop(
            st["svc"], reqs,
            lambda r: whatif.request_pods(tmpls[reqs[r][1]], r, reqs[r][2]),
            int(traffic["clients"]), time.perf_counter)
        ex = res["executor"]
        lat = res["lat_ms"]
        half = len(lat) // 2
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs), "missing": res["missing"],
            "p50_ms": common.percentile(lat, 50), "p95_ms": common.percentile(lat, 95),
            "p95_first_half_ms": common.percentile(lat[:half] or lat, 95),
            "p95_second_half_ms": common.percentile(lat[half:], 95),
            "drain_s": res["drain_s"],
            "late_p95_ms": common.percentile(res["late_s"], 95) * 1e3}), flush=True)
    whatif.stop(st["svc"], ex)
    return 0


if __name__ == "__main__":
    sys.exit(main())
