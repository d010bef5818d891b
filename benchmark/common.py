"""What every driver shares: the device check, the set-up clock, compile
counting, the traced window, per-layer metric readers and the result line."""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")  # scratch inside the checkout (gitignored)


class BenchError(Exception):
    """A run that must exit non-zero and print no result."""


def cell_named(bench: dict, name: str) -> dict:
    """The cell BENCHMARK.json names so, or, for the tools and tests only
    (control.py, sweep_rate.py), a cell it does not hold: <config>.<traffic>
    on one chip."""
    config, _, traffic = name.partition(".")
    return next((w for w in bench["workloads"] if w["name"] == name),
                {"name": name, "config": config, "traffic": traffic, "chips": 1})


def percentile(values: List[float], p: float) -> float:
    """Nearest-rank percentile (p in 0..100) of all values."""
    v = sorted(values)
    k = max(0, min(len(v) - 1, int(-(-p * len(v) // 100)) - 1))
    return v[k]


class Context:
    def __init__(self, args, bench: dict, cell: dict, traffic: dict,
                 t_start: float) -> None:
        self.args = args
        self.bench = bench
        self.cell = cell
        self.traffic = traffic
        self.t_start = t_start
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.config_name = cell["config"]
        self.devices = None
        self.platform = None
        # programs compiled, and programs loaded from the persistent cache
        self.compiles = {"compiled": 0, "cache_loaded": 0}
        self.t_window0: Optional[float] = None
        self.setup_s: Optional[float] = None
        self.notes: List[str] = []

    # --------------------------------------------------------------- device --
    def require_device(self) -> None:
        """No result without the accelerator the cell asks for."""
        import jax

        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise BenchError(f"no TPU found (JAX reports {devs[0].platform} "
                             f"x{len(devs)})")
        if len(devs) < int(self.cell["chips"]):
            raise BenchError(f"the cell asks for {self.cell['chips']} chips, "
                             f"JAX sees {len(devs)}")
        self.use_devices(devs)

    def use_devices(self, devs) -> None:
        self.devices = list(devs)[:int(self.cell["chips"])]
        self.platform = self.devices[0].platform
        from jax import monitoring

        def on_event(event: str, **kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.compiles["cache_loaded"] += 1

        def on_duration(event: str, duration: float, **kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles["compiled"] += 1

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    # --------------------------------------------------------------- window --
    def start_window(self) -> float:
        """Ends set-up: process start to here is setup_s."""
        now = time.time()
        self.setup_s = now - self.t_start
        self.window_compiles0 = dict(self.compiles)
        self.t_window0 = time.perf_counter()
        return self.t_window0

    def end_window(self) -> None:
        self.window_compiles = " ".join(
            f"{k} {v - self.window_compiles0[k]}" for k, v in self.compiles.items())

    def memory_peak(self) -> Optional[int]:
        peaks = []
        for d in self.devices:
            try:
                peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
            except (KeyError, TypeError, AttributeError):
                pass
        return max(peaks) if peaks else None

    @contextlib.contextmanager
    def profiled(self):
        """Profiler on round the block (--trace 1 only); yields a dict that
        holds the reduced trace afterwards."""
        holder: Dict[str, object] = {}
        if not self.trace:
            yield holder
            return
        import jax

        log_dir = os.path.join(OUT, "trace", self.cell["name"])
        shutil.rmtree(log_dir, ignore_errors=True)
        os.makedirs(log_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
        import devtrace as tr

        holder["reduced"] = tr.reduce(tr.load(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)

    # -------------------------------------------------------------- metrics --
    def _applies(self, metric: dict) -> bool:
        wl = metric.get("workloads")
        if wl is not None:
            return self.cell["name"] in wl
        if metric in self.bench["end_to_end"]:
            return True
        moves = next(m for m in self.bench["end_to_end"]
                     if m["name"] == metric["moves"])
        return self._applies(moves)

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"] if self._applies(m)]

    def per_layer(self, layer_data: dict) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        layer_data.setdefault("device_kind", self.devices[0].device_kind)
        mdir = os.path.join(BENCH, "metrics")
        if mdir not in sys.path:
            sys.path.insert(0, mdir)
        for m in self.bench["per_layer"]:
            if not self._applies(m):
                continue
            path = os.path.join(BENCH, "metrics", f"{m['name']}.py")
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            v = mod.read(layer_data)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out

    # --------------------------------------------------------------- result --
    def finish(self, out: dict) -> int:
        """out: correct, attempted, failed, e2e {name: value}, layer (reader
        data), checks [(name, value, limit)], notes [str]."""
        for line in self.notes + out.get("notes", []):
            print(line, file=sys.stderr)
        if self.trace:
            layer = out["layer"]
            metrics = self.per_layer(layer)
        else:
            metrics = {}
            for m in self.end_to_end():
                v = out["e2e"].get(m["name"])
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        d0 = self.devices[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(self.devices),
                  "memory_peak_bytes": out.get("memory_peak_bytes")}
        result = {"correct": bool(out["correct"]),
                  "attempted": int(out["attempted"]),
                  "failed": int(out["failed"]),
                  "metrics": metrics, "device": device}
        red = (out.get("layer") or {}).get("trace")
        if self.trace and red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        checks = {}
        for name, value, limit in out["checks"]:
            print(f"check {name} = {value} (limit {limit})", file=sys.stderr)
            checks[name] = {"value": value, "limit": limit}
        result["checks"] = checks
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
        return 0
