"""Whole-cluster simulations back to back: what a platform team runs to
size a cluster.

Each simulation is Simulator(nodes) then schedule_pods(pods) with the
placements read back, on inputs generated from (seed, i) in set-up.
pods_per_s = pods placed by every simulation that ran in the window /
(last completion - window start).

Traffic keys: inputs (distinct inputs; the window cycles through fresh
copies of them), check (simulations compared with the reference: the
slowest, and the rest drawn from the seed), trace_sims (simulations in the
--trace 1 window). Set-up runs every input once: segment lengths, and so
the compiled shapes, follow the drawn order, and only the inputs
themselves are sure to cover every shape the window meets.
"""

from __future__ import annotations

import copy
import time

import numpy as np

KINDS = ("wave", "affinity")  # the segment kinds a roofline reads


def simulate(ns, ps):
    """The timed path: (pods placed, node per pod, -1 = unschedulable)."""
    from open_simulator_tpu.simulator.engine import Simulator

    Simulator(ns).schedule_pods(ps)
    nodes = np.array(ps.node_rows(), np.int64)
    return int((nodes >= 0).sum()), nodes


def misplaced_vs_reference(cfg: dict, seed: int, i: int, nodes) -> int:
    """Pods of simulation input i whose node differs from the reference's."""
    import cluster
    import reference

    c = cluster.generate(cfg, seed, i)
    return reference.misplaced(c, nodes, cluster.reference_for(cfg)(c).schedule_all())


def counters() -> dict:
    from open_simulator_tpu.obs import instruments as obs

    out = {f"pods.{k}": obs.SEGMENT_PODS.labels(kind=k).value for k in KINDS}
    for ph in ("encode", "commit"):
        out[f"phase.{ph}"] = obs.PULSE_PHASE_SECONDS.labels(phase=ph).value
    return out


def run(ctx, simulate_fn=simulate) -> dict:
    import cluster

    tr = ctx.traffic
    cfg = cluster.load_config(ctx.config_name)
    if ctx.trace:
        from open_simulator_tpu.obs import pulse

        pulse.enable()  # phase walls (encode, commit) for the layer readers
    n_in = int(tr["inputs"])
    inputs = [cluster.program_inputs(cluster.generate(cfg, ctx.seed, i))
              for i in range(n_in)]
    for ns, ps in inputs:
        simulate_fn(ns, copy.deepcopy(ps))
    import jax

    t0 = ctx.start_window()
    runs = []  # (input index, seconds, placed, nodes)
    c0 = counters()
    with ctx.profiled() as prof, jax.profiler.TraceAnnotation("bench.window"):
        i = 0
        limit = int(tr["trace_sims"]) if ctx.trace else None
        while True:
            k = i % n_in
            ns, ps = inputs[k][0], copy.deepcopy(inputs[k][1])
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.simulation"):
                placed, nodes = simulate_fn(ns, ps)
            t2 = time.perf_counter()
            runs.append((k, t2 - t1, placed, nodes))
            i += 1
            if limit is not None and i >= limit:
                break
            if limit is None and t2 - t0 >= ctx.seconds:
                break
    t_end = time.perf_counter()
    ctx.end_window()
    c1 = counters()
    peak = ctx.memory_peak()
    inputs = None

    # the check: simulations drawn from the seed, the slowest among them
    rng = cluster.rng_for(ctx.seed, 2)
    order = sorted(range(len(runs)), key=lambda j: -runs[j][1])
    pick = {order[0]} | set(rng.permutation(len(runs))[:max(0, int(tr["check"]) - 1)].tolist())
    bad = sum(misplaced_vs_reference(cfg, ctx.seed, runs[j][0], runs[j][3])
              for j in sorted(pick))
    pods = sum(r[2] for r in runs)
    wall = t_end - t0
    ctx.notes.append(
        f"simulations {len(runs)} pods_placed {pods} window_s {wall:.6f} "
        f"sim_s_median {float(np.median([r[1] for r in runs])):.6f} "
        f"sim_s_max {max(r[1] for r in runs):.6f} window_compiles "
        f"{ctx.window_compiles} setup_s {ctx.setup_s:.6f} checked {sorted(pick)}")
    layer = {
        "trace": prof.get("reduced"),
        "sims": len(runs),
        "n_nodes": int(cfg["nodes"]["count"]),
        "counters": {k: c1[k] - c0[k] for k in c0},
    }
    return {
        "correct": bad == 0,
        "attempted": len(runs),
        "failed": 0,
        "e2e": {"pods_per_s": pods / wall, "setup_s": ctx.setup_s},
        "layer": layer,
        "memory_peak_bytes": peak,
        "checks": [("misplaced_pods", bad, 0)],
    }
