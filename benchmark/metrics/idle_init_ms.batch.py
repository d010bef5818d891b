"""idle_init_ms.batch: see BENCHMARK.json and PERF.md section 3."""

from _spans import idle_ms


def read(layer: dict):
    return idle_ms(layer, phases=("simon.init",))
