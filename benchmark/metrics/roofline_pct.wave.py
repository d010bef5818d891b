"""roofline_pct.wave: see BENCHMARK.json and PERF.md section 3."""

from _layer import kernel_roofline


def read(layer: dict):
    return kernel_roofline(layer, "schedule_wave", "wave", terms=0)
