"""device_idle_pct.whatif: see BENCHMARK.json and PERF.md section 3."""

from _layer import idle_pct


def read(layer: dict):
    return idle_pct(layer)
