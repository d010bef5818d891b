"""Reduction of a profiler trace to the numbers the benchmark reports.

`load` reads the `.xplane.pb` the JAX profiler wrote into plain data:
{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
duration_ns], ...]}]}]}. `reduce` works on that form only, so a recorded
trace (tests/fixtures) checks it without a chip:

- device busy: the union of the intervals of the device planes' op events
  inside the window, averaged over the devices; idle = window - busy;
- per-kernel device time: the XLA module events on the device planes,
  grouped by module name with the trailing program id dropped;
- idle gaps by host activity: each gap between busy intervals goes to the
  innermost host event covering its midpoint (the benchmark's own
  TraceAnnotation spans and the runtime's, e.g. PjitFunction(...)).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"  # the TraceAnnotation the drivers put round the window
_ID = re.compile(r"\(\d+\)$")
_SKIP_HOST = ("ThreadpoolListener",)


def load(log_dir: str) -> dict:
    import jax

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes = []
    for p in paths:
        pd = jax.profiler.ProfileData.from_file(p)
        for plane in pd.planes:
            lines = []
            for line in plane.lines:
                evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events]
                if evs:
                    lines.append({"name": line.name, "events": evs})
            if lines:
                planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def is_device(plane: dict) -> bool:
    return plane["name"].startswith("/device:") and "CPU" not in plane["name"]


def module_name(name: str) -> str:
    return _ID.sub("", name)


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def window_bounds(trace: dict) -> Optional[Tuple[int, int]]:
    for plane in trace["planes"]:
        if is_device(plane):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name == WINDOW:
                    return s, s + d
    return None


def _op_line(plane: dict) -> Optional[dict]:
    by = {ln["name"]: ln for ln in plane["lines"]}
    return by.get("XLA Ops") or by.get("XLA Modules")


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s, window_s, idle_share, kernels {module: seconds per device},
    device_ops and idle_gaps (each at most `top` entries, largest first)."""
    bounds = window_bounds(trace)
    if bounds is None:
        raise ValueError(f"trace has no {WINDOW!r} span")
    lo, hi = bounds
    devices = [p for p in trace["planes"] if is_device(p)]
    if not devices:
        raise ValueError("trace has no device plane")
    busy_total = 0
    kernels: Dict[str, float] = defaultdict(float)
    busy_sets = []
    for plane in devices:
        ops = _op_line(plane)
        iv = _union(_clip([(s, s + d) for _, s, d in (ops["events"] if ops else [])],
                          lo, hi))
        busy_sets.append(iv)
        busy_total += sum(e - s for s, e in iv)
        for line in plane["lines"]:
            if line["name"] != "XLA Modules":
                continue
            for name, s, d in line["events"]:
                span = min(s + d, hi) - max(s, lo)
                if span > 0:
                    kernels[module_name(name)] += span / 1e9
    n_dev = len(devices)
    window_s = (hi - lo) / 1e9
    busy_s = busy_total / n_dev / 1e9
    # idle gaps of the first device, each to the innermost host event that
    # covers its midpoint (one sweep over host events sorted by start)
    host = sorted((s, s + d, name) for p in trace["planes"] if not is_device(p)
                  for line in p["lines"] for name, s, d in line["events"]
                  if d > 0 and name != WINDOW and not name.startswith(_SKIP_HOST))
    gaps: Dict[str, float] = defaultdict(float)
    active: List[Tuple[int, int, str]] = []
    nxt = 0
    prev = lo
    for s, e in busy_sets[0] + [(hi, hi)]:
        if s > prev:
            mid = (prev + s) // 2
            while nxt < len(host) and host[nxt][0] <= mid:
                active.append(host[nxt])
                nxt += 1
            active = [h for h in active if h[1] > mid]
            name = (min(active, key=lambda h: h[1] - h[0])[2] if active
                    else "(no host span)")
            gaps[name] += (s - prev) / 1e9
        prev = max(prev, e)
    for k in kernels:
        kernels[k] /= n_dev
    ops = sorted(kernels.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "kernels": dict(kernels),
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in idle],
    }


def kernel_seconds(reduced: dict, kernel: str) -> float:
    """Device seconds of the XLA modules compiled from the jitted `kernel`
    (module names carry the jit name, e.g. jit_schedule_wave)."""
    pat = re.compile(rf"(^|[^A-Za-z0-9_]|jit_){re.escape(kernel)}($|[^A-Za-z0-9_])")
    return sum(v for k, v in reduced["kernels"].items() if pat.search(k))
