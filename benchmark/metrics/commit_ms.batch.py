"""commit_ms.batch: see BENCHMARK.json and PERF.md section 3."""

from _layer import phase_ms


def read(layer: dict):
    return phase_ms(layer, "commit")
