"""The main path's kernels compile for a TPU v5e chip that is only described.

No chip is needed: `jax.experimental.topologies` describes a v5e:2x2 host,
and the TPU compiler refuses here what the chip would refuse (HBM overflow,
tiling, partitioning). Shapes are the real ones: the engine's own encode of
the bench headline (10k nodes / 100k pods) and hard-predicate (5k nodes /
50k pods) clusters on one chip; on four chips, the <=2048-node hard cluster
whose waves take the shard_map epoch path. The wave kernels take 20-30 s
each to compile for the chip, the serial ones about a second.

The topology is described inside a module fixture (never at import: one
process at a time may load the TPU library, and xdist workers must all
collect the same tests), and the persistent compile cache is off around the
compiles (a described-device entry cannot be read back without a chip).
"""

import os

import pytest

from open_simulator_tpu.analysis import hlo
from open_simulator_tpu.parallel.mesh import (
    ShardedKernels, make_node_mesh, pad_batch_tables)

HBM_BYTES = 16e9  # one v5e chip

SHAPES = {  # name -> (nodes, pods, hard_predicates)
    "headline": (10_000, 100_000, False),
    "hard": (5_000, 50_000, True),
    "hard_epoch": (2_000, 20_000, True),
}
ONE_CHIP = [(k, s) for s in ("headline", "hard")
            for k in ("schedule_wave", "schedule_affinity_wave",
                      "schedule_group_serial", "schedule_batch")]
FOUR_CHIPS = [(k, "hard_epoch")
              for k in ("schedule_wave", "schedule_affinity_wave")]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


_TABLES: dict = {}


def batch_tables(shape: str):
    """The engine's encode of the shape's cluster (cached per module run)."""
    bt = _TABLES.get(shape)
    if bt is None:
        from open_simulator_tpu.simulator.engine import Simulator
        from open_simulator_tpu.utils.synth import synth_cluster

        n_nodes, n_pods, hard = SHAPES[shape]
        nodes, pods = synth_cluster(n_nodes, n_pods, hard_predicates=hard)
        bt = _TABLES[shape] = Simulator(nodes, use_mesh=False).encode_batch(
            pods)
    return bt


def compile_for(devices, kernel: str, shape: str):
    """Compile `kernel` at `shape` over a node mesh of the described devices,
    through the same jit the engine dispatches (ShardedKernels)."""
    mesh = make_node_mesh(len(devices), devices=devices)
    btp = pad_batch_tables(batch_tables(shape), len(devices))
    jfn, spec, meta = ShardedKernels(mesh).lowerable(
        kernel, n_zones=int(btp.n_zones))
    P = int(btp.pod_group.shape[0])
    args = (hlo._abstract_head(btp, spec.fanout)
            + tuple(hlo._dyn_abs(tok, P) for tok in spec.dyn)
            + meta["statics"])
    return jfn.lower(*args).compile()


def check_compiled(compiled):
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert per_device < HBM_BYTES, f"{per_device / 1e9:.1f} GB per device"
    text = compiled.as_text()
    _, host = hlo.escape_census(text)
    assert host == [], f"host callbacks in the chip program: {host}"
    return text


def check_wave_sorts(kernel: str, text: str) -> None:
    """The wave's serial pick order rests on top_k putting equal keys lowest
    index first: on the chip every sort of schedule_wave must be stable."""
    if kernel == "schedule_wave":
        sorts = [ln for ln in text.splitlines() if " sort(" in ln]
        assert sorts and all("is_stable=true" in ln for ln in sorts), sorts


@pytest.mark.parametrize("kernel,shape", ONE_CHIP)
def test_compiles_for_one_v5e_chip(topo, no_cache, kernel, shape):
    text = check_compiled(compile_for(topo.devices[:1], kernel, shape))
    check_wave_sorts(kernel, text)


@pytest.mark.parametrize("kernel,shape", FOUR_CHIPS)
def test_compiles_sharded_over_four_v5e_chips(topo, no_cache, kernel, shape):
    text = check_compiled(compile_for(list(topo.devices), kernel, shape))
    # the node axis really is split: the program exchanges across chips
    assert hlo.collective_census(text), "no collective in a 4-chip program"
    # the sharded wave ranks inside shard_map, after the gather
    check_wave_sorts(kernel, text)


def test_described_chip_is_a_v5e(topo):
    from open_simulator_tpu.obs import pulse

    kinds = {d.device_kind for d in topo.devices}
    assert len(topo.devices) == 4 and kinds == {"TPU v5 lite"}
    assert pulse.peak_rates(kinds.pop()) == (197e12, 819e9)
    assert {d.platform for d in topo.devices} == {"tpu"}
