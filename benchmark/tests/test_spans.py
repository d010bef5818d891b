"""The span-idle readers on a hand-built reduced trace: prefix sums over a
phase and its dotted children, the runtime's PjitFunction events counted as
dispatch, and None where the run has nothing to read."""

import importlib.util
import os

import pytest

import _spans

METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "metrics")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def layer(gaps, sims=2):
    return {"sims": sims, "trace": {"idle_gaps": [[k, v] for k, v in gaps.items()]}}


GAPS = {
    "simon.init": 0.010, "simon.init.nodes": 0.020, "simon.initial": 0.5,
    "simon.encode": 0.004, "simon.encode.table_build": 0.002,
    "simon.route": 0.001, "simon.dispatch": 0.003, "simon.dispatch.affinity": 0.005,
    "PjitFunction(schedule_affinity_wave)": 0.007, "simon.dispatcher": 0.9,
    "simon.commit": 0.030, "simon.commit.bulk": 0.006,
    "bench.simulation": 1.0, "(no host span)": 1.0,
}


@pytest.mark.parametrize("name,want_s", [
    ("idle_init_ms.batch", 0.030),
    ("idle_commit_ms.batch", 0.036),
])
def test_readers_sum_a_phase_and_its_children_per_simulation(name, want_s):
    assert reader(name)(layer(GAPS)) == pytest.approx(1e3 * want_s / 2)


@pytest.mark.parametrize("name", ["idle_init_ms.batch", "idle_commit_ms.batch"])
def test_readers_return_none_with_nothing_to_read(name):
    read = reader(name)
    assert read({"sims": 3}) is None                      # no trace (--trace 0)
    assert read(layer({"bench.simulation": 1.0})) is None  # the parent's trace
    assert read(layer(GAPS, sims=0)) is None


def test_idle_ms_sums_a_phase_and_its_children():
    assert _spans.idle_ms(layer(GAPS), ("simon.encode",)) == pytest.approx(1e3 * 0.006 / 2)
    assert _spans.idle_ms(layer(GAPS), ("simon.route", "simon.dispatch"),
                          ("PjitFunction",)) == pytest.approx(1e3 * 0.016 / 2)


def test_matches_is_exact_or_dotted_child():
    assert _spans.matches("simon.init", ("simon.init",))
    assert _spans.matches("simon.init.nodes", ("simon.init",))
    assert not _spans.matches("simon.initial", ("simon.init",))
    assert _spans.matches("PjitFunction(k)", (), ("PjitFunction",))
    assert not _spans.matches("jit_k", ("simon.dispatch",), ("PjitFunction",))
