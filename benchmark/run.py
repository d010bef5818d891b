"""Benchmark entry: one cell per process.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name. BENCHMARK.json names the cell; the cell names
a configuration (benchmark/configs/<config>.json) and a traffic mix
(benchmark/traffic/<traffic>.json); the mix names the driver that runs it
(benchmark/drivers/<driver>.py); each per-layer metric is read by
benchmark/metrics/<metric>.py. Adding any of these is new files plus new
entries: nothing here lists them.

The last stdout line is one JSON object (correct, attempted, failed,
metrics, device, and with --trace 1 breakdown). The numbers that decide
`correct` are printed beside their limits as the last stderr lines and
under "checks", the last key of the result. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations


def _process_start() -> float:
    """time.time() at which this process started (Linux /proc)."""
    import os
    import time

    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            ticks = int(f.read().rpartition(")")[2].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            up = float(f.read().split()[0])
        return time.time() - up + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


T_START = _process_start()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from common import BenchError  # noqa: E402


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise BenchError(f"no file {os.path.relpath(path, ROOT)}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"BENCHMARK.json has no {what} named {name!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2


def run(args) -> int:
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(bench_path):
        raise BenchError("no BENCHMARK.json at the checkout's root")
    if not os.path.isdir(os.path.join(ROOT, "open_simulator_tpu")):
        raise BenchError("no program (open_simulator_tpu/) in this checkout")
    with open(bench_path, encoding="utf-8") as f:
        bench = json.load(f)
    cell = find(bench["workloads"], args.workload, "workload")
    with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json"),
              encoding="utf-8") as f:
        traffic = json.load(f)
    driver = load_module(os.path.join(BENCH, "drivers",
                                      f"{traffic['driver']}.py"),
                         f"bench_driver_{traffic['driver']}")

    # before JAX loads: the compile cache lives at a fixed path inside the
    # checkout (its path is part of the key), every program is cached, and
    # libtpu writes no logs to a fixed /tmp path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(1, ROOT)
    import common

    ctx = common.Context(args, bench, cell, traffic, T_START)
    ctx.require_device()
    out = driver.run(ctx)
    return ctx.finish(out)


if __name__ == "__main__":
    sys.exit(main())
