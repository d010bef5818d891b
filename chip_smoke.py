"""Bring-up smoke: drive the main path once on the chip, through the entry
points a user calls, and check what comes out against the CPU.

    python chip_smoke.py             # phases a-d on one TPU chip
    python chip_smoke.py --chips 4   # only the sharded phase and the
                                     # single-chip runs it is compared with

One process does everything (a chip belongs to one process). Each phase
prints one JSON line; a failed check raises after its line is printed, and
nothing is caught. The last stdout line is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Without a TPU, or outside a checkout of this repository, it exits non-zero
and prints no result.

Phases (one chip):
  a) headline: synth_cluster(10_000, 100_000) through Simulator.schedule_pods
  b) hard predicates: synth_cluster(5_000, 50_000, hard_predicates=True)
  c) capacity planning: `simon apply -f examples/simon-config.yaml`
  d) what-if serving: a ResidentImage over 10,000 synthetic nodes, requests
     through WhatIfService.submit, each checked against fresh_probe
a-c run again under jax.default_device(cpu) and must place identically.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
EXPECT = "tpu"  # the platform every device phase must run on


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(doc: dict) -> None:
    print(json.dumps(doc, sort_keys=True), flush=True)


# ---------------------------------------------------------------- probes ----

_CACHE_EVENTS = {"hits": 0, "misses": 0}
_SIMS: list = []   # every Simulator built since the last take_sims()
_SHARDED: list = []  # (tables, carry) of the first sharded transfer


def install_probes() -> None:
    """Count persistent-cache hits/misses, and keep every Simulator and
    sharded transfer, so each phase can report what it ran on."""
    from jax import monitoring

    from open_simulator_tpu.parallel import mesh
    from open_simulator_tpu.simulator import engine

    def on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _CACHE_EVENTS["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _CACHE_EVENTS["misses"] += 1

    monitoring.register_event_listener(on_event)

    init = engine.Simulator.__init__

    def tracked_init(self, *a, **k):
        init(self, *a, **k)
        _SIMS.append(self)

    engine.Simulator.__init__ = tracked_init

    to_device_sharded = mesh.to_device_sharded

    def tracked_transfer(bt, m):
        out = to_device_sharded(bt, m)
        if not _SHARDED:
            _SHARDED.append(out[:2])
        return out

    mesh.to_device_sharded = tracked_transfer


def take_sims() -> tuple:
    """backend_path of every Simulator built since the last call that ran a
    device call (ones that only encoded, e.g. a ResidentImage's, have
    none), plus how many never dispatched."""
    sims = list(_SIMS)
    del _SIMS[:]
    ran = [list(s.backend_path) for s in sims if s.backend_path]
    return ran, len(sims) - len(ran)


def cache_state() -> dict:
    import jax

    return {"dir": jax.config.jax_compilation_cache_dir,
            "enabled": bool(jax.config.jax_enable_compilation_cache),
            **_CACHE_EVENTS}


def common(phase: str, t_cache0: dict) -> dict:
    """The fields every phase prints: guard events, cache, native hash."""
    from open_simulator_tpu import native
    from open_simulator_tpu.resilience import guard

    c = cache_state()
    return {
        "phase": phase,
        "guard_events": [list(e) for e in guard.events()],
        "guard_quarantined": guard.quarantined(),
        "compile_cache": {"dir": c["dir"], "enabled": c["enabled"],
                          "hits": c["hits"] - t_cache0["hits"],
                          "misses": c["misses"] - t_cache0["misses"]},
        "native_hash": native.canon_hash_fn() is not None,
    }


def check_common(doc: dict, paths: list) -> None:
    check(bool(paths), f"{doc['phase']}: no Simulator ran a device call")
    check(all(p == [EXPECT] for p in paths),
          f"{doc['phase']}: backend_path {paths} is not [[{EXPECT!r}]]")
    check(not doc["guard_events"], f"{doc['phase']}: guard fired "
          f"{doc['guard_events']}")
    check(not doc["guard_quarantined"], f"{doc['phase']}: quarantine")


@contextlib.contextmanager
def on_cpu():
    """The CPU reference: the same call, under jax.default_device(cpu)."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        yield


# ------------------------------------------------------------ placements ----

def placement(sim) -> dict:
    """pod -> node index, read without materializing columnar spans: dict
    pods by name, PodStore rows by row number."""
    out = {}
    for i, lst in sim.pods_on_node.nonempty():
        for it in lst.copy_items():
            rows = getattr(it, "rows", None)
            if rows is None:
                out[it["metadata"]["name"]] = i
            else:
                for r in rows.tolist():
                    out[r] = i
    return out


def match_rate(a: dict, b: dict) -> float:
    keys = set(a) | set(b)
    if not keys:
        return 1.0
    return sum(a.get(k, -1) == b.get(k, -1) for k in keys) / len(keys)


def schedule(n_nodes: int, n_pods: int, hard: bool, store: bool = False,
             use_mesh=None):
    """One engine run on fresh synthetic input: (wall_s, sim, n_failed).
    Only schedule_pods is timed."""
    from open_simulator_tpu.simulator.engine import Simulator
    from open_simulator_tpu.utils.synth import synth_cluster, synth_cluster_store

    make = synth_cluster_store if store else synth_cluster
    nodes, pods = make(n_nodes, n_pods, hard_predicates=hard)
    sim = Simulator(nodes, use_mesh=use_mesh)
    t0 = time.perf_counter()
    failed = sim.schedule_pods(pods)
    return time.perf_counter() - t0, sim, len(failed)


def phase_schedule(phase: str, n_nodes: int, n_pods: int, hard: bool) -> None:
    c0 = dict(_CACHE_EVENTS)
    cold, _, _ = schedule(n_nodes, n_pods, hard)
    warm, sim, n_failed = schedule(n_nodes, n_pods, hard)
    got = placement(sim)
    paths, idle = take_sims()
    with on_cpu():
        ref_s, ref, _ = schedule(n_nodes, n_pods, hard, use_mesh=False)
    ref_paths, _ = take_sims()
    rate = match_rate(got, placement(ref))
    doc = common(phase, c0)
    doc.update({
        "shape": {"nodes": n_nodes, "pods": n_pods, "hard": hard},
        "wall_s": {"cold": cold, "warm": warm},
        "pods": {"placed": sim.pods_on_node.total(), "failed": n_failed},
        "backend_path": paths, "sims_without_dispatch": idle,
        "cpu_reference": {"wall_s": ref_s, "backend_path": ref_paths},
        "parity": {"match_rate": rate},
    })
    emit(doc)
    check_common(doc, paths)
    check(ref_paths == [["cpu"]], f"{phase}: CPU reference ran on {ref_paths}")
    check(rate == 1.0, f"{phase}: placements differ from the CPU run "
          f"(match_rate {rate})")


_NEW_NODE = re.compile(r"\bsimon-[a-z0-9]{5}\b")


def normalized_report(text: str) -> list:
    """The apply report with the random new-node names replaced by their
    order in the node table, and each app's node list sorted."""
    alias = {}
    for name in _NEW_NODE.findall(text):
        alias.setdefault(name, f"new-{len(alias)}")
    out = []
    for line in _NEW_NODE.sub(lambda m: alias[m.group(0)],
                              text).splitlines():
        cols = line.split()
        if len(cols) >= 3 and "(" in cols[-1]:
            head, _, nodes = line.rpartition("  ")
            line = head + "  " + ", ".join(sorted(nodes.split(", ")))
        out.append(line)
    return out


def apply_once(config: str):
    """`simon apply -f config` in-process: (wall_s, rc, report lines)."""
    from open_simulator_tpu.cli.main import main as simon

    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        rc = simon(["apply", "-f", config])
    return time.perf_counter() - t0, rc, normalized_report(buf.getvalue())


def apply_pods(report: list) -> int:
    """Pods the report's App Info table places."""
    placed, in_apps = 0, False
    for line in report:
        if line.startswith("App "):
            in_apps = True
        elif in_apps and line.strip():
            placed += int(line.split()[1])
    return placed


def phase_apply(config: str = "examples/simon-config.yaml") -> None:
    c0 = dict(_CACHE_EVENTS)
    cwd = os.getcwd()
    os.chdir(REPO)  # the example config's paths are relative to the repo
    try:
        cold, rc_cold, _ = apply_once(config)
        warm, rc, got = apply_once(config)
        paths, idle = take_sims()
        with on_cpu():
            ref_s, rc_ref, want = apply_once(config)
        ref_paths, _ = take_sims()
    finally:
        os.chdir(cwd)
    same = sum(a == b for a, b in zip(got, want))
    rate = same / max(len(got), len(want), 1)
    doc = common("c_capacity_plan", c0)
    doc.update({
        "config": config, "rc": [rc_cold, rc, rc_ref],
        "wall_s": {"cold": cold, "warm": warm},
        "pods": {"placed": apply_pods(got),
                 "failed": 0 if got and got[0] == "Simulation success!"
                 else None},
        "nodes_added": next((int(m.group(1)) for m in (
            re.search(r"added (\d+) node", ln) for ln in got) if m), 0),
        "backend_path": paths, "sims_without_dispatch": idle,
        "cpu_reference": {"wall_s": ref_s,
                          "backend_path": sorted({p[0] for p in ref_paths})},
        "parity": {"match_rate": rate},
    })
    emit(doc)
    check(doc["rc"] == [0, 0, 0], f"apply exit codes {doc['rc']}")
    check(got and got[0] == "Simulation success!", "apply did not succeed")
    check_common(doc, paths)
    check(all(p == ["cpu"] for p in ref_paths),
          f"apply CPU reference ran on {ref_paths}")
    check(rate == 1.0, f"apply report differs from the CPU run ({rate})")


def whatif_requests(n: int) -> list:
    """n small what-if shapes, as tools/loadgen.py's request pool."""
    from open_simulator_tpu.utils.synth import synth_pod

    return [[synth_pod(100000 + t * 10 + j, cpu_milli=100 * (1 + t % 3),
                       mem_bytes=(256 << 20) * (1 + t % 2),
                       labels={"app": f"whatif-{t}"})
             for j in range(1 + t % 4)]
            for t in range(n)]


def phase_serve(n_nodes: int = 10_000, n_requests: int = 16) -> None:
    from open_simulator_tpu.serve import ResidentImage, WhatIfService
    from open_simulator_tpu.utils.synth import synth_node

    c0 = dict(_CACHE_EVENTS)
    t0 = time.perf_counter()
    # as `simon serve --synthetic-nodes N` builds it: N nodes, no pods
    image = ResidentImage.try_build([synth_node(i) for i in range(n_nodes)])
    build_s = time.perf_counter() - t0
    check(image is not None, "the resident image declined the cluster")
    svc = WhatIfService(image)
    lat, answers = [], []
    for pods in whatif_requests(n_requests):
        t1 = time.perf_counter()
        answers.append(svc.submit(pods))
        lat.append(time.perf_counter() - t1)
    want = [image.fresh_probe(pods) for pods in whatif_requests(n_requests)]
    agree = [a["scheduled"] == w["scheduled"] and a["total"] == w["total"]
             and a["utilization"] == w["utilization"]
             for a, w in zip(answers, want)]
    table_platforms = sorted({d.platform for a in image._tables
                              for d in a.devices()})
    svc.stop()
    paths, idle = take_sims()
    doc = common("d_whatif_serve", c0)
    doc.update({
        "nodes": n_nodes, "requests": n_requests,
        "wall_s": {"image_build": build_s, "cold": lat[0],
                   "warm": statistics.median(lat[1:])},
        "pods": {"placed": sum(a["scheduled"] for a in answers),
                 "failed": sum(a["unscheduled"] for a in answers)},
        "paths": sorted({a["path"] for a in answers}),
        "image_table_platforms": table_platforms,
        "backend_path": paths, "sims_without_dispatch": idle,
        "parity": {"match_rate": sum(agree) / len(agree)},
    })
    emit(doc)
    check_common(doc, paths)
    check(table_platforms == [EXPECT], f"image tables on {table_platforms}")
    check("fresh" not in doc["paths"], "a request took the fresh path")
    check(all(agree), "a resident answer differs from fresh_probe")


# ------------------------------------------------------------ four chips ----

def shard_bytes() -> list:
    """Bytes of one node-sharded table's addressable shards, per device."""
    check(bool(_SHARDED), "no sharded transfer was made")
    tables, _ = _SHARDED[0]
    return [{"device": s.device.id, "bytes": s.data.nbytes}
            for s in tables.alloc.addressable_shards]


def phase_sharded(phase: str, n_nodes: int, n_pods: int, hard: bool,
                  store: bool) -> None:
    c0 = dict(_CACHE_EVENTS)
    del _SHARDED[:]
    cold, _, _ = schedule(n_nodes, n_pods, hard, store, use_mesh=True)
    shards = shard_bytes()
    warm, sim, n_failed = schedule(n_nodes, n_pods, hard, store,
                                   use_mesh=True)
    got = placement(sim)
    paths, idle = take_sims()
    one_s, one, _ = schedule(n_nodes, n_pods, hard, store, use_mesh=False)
    one_paths, _ = take_sims()
    rate = match_rate(got, placement(one))
    doc = common(phase, c0)
    doc.update({
        "shape": {"nodes": n_nodes, "pods": n_pods, "hard": hard,
                  "columnar": store},
        "wall_s": {"cold": cold, "warm": warm},
        "pods": {"placed": sim.pods_on_node.total(), "failed": n_failed},
        "alloc_shard_bytes": shards,
        "backend_path": paths, "sims_without_dispatch": idle,
        "single_chip": {"wall_s": one_s, "backend_path": one_paths},
        "parity": {"match_rate": rate},
    })
    emit(doc)
    check_common(doc, paths + one_paths)
    check(len({s["device"] for s in shards}) == 4
          and min(s["bytes"] for s in shards) > 0,
          f"{phase}: the table is not spread over four devices: {shards}")
    check(rate == 1.0, f"{phase}: sharded placements differ from one chip "
          f"(match_rate {rate})")


# ------------------------------------------------------------------ main ----

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "open_simulator_tpu")):
        print("chip_smoke: not in a checkout of the repository "
              "(no open_simulator_tpu/ beside this script)", file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    import jax

    devs = jax.devices()
    if devs[0].platform != EXPECT:
        print(f"chip_smoke: no TPU found (JAX reports "
              f"{devs[0].platform} x{len(devs)})", file=sys.stderr)
        return 1
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              f"devices", file=sys.stderr)
        return 1
    from open_simulator_tpu.utils.devices import enable_compilation_cache

    enable_compilation_cache()  # before the first compile
    install_probes()
    emit({"phase": "device", "platform": devs[0].platform,
          "kind": devs[0].device_kind, "count": len(devs),
          "jax": jax.__version__, "compile_cache": cache_state()})
    t0 = time.perf_counter()
    if args.chips == 4:
        # columnar 1M pods / 100k nodes (bench.py's mesh8_1m shape), and a
        # hard-predicate cluster small enough (<= 2048 nodes) for the
        # shard_map epoch path
        phase_sharded("sharded_1m_100k", 100_000, 1_000_000, False, True)
        phase_sharded("sharded_hard_2k", 2_000, 20_000, True, False)
    else:
        phase_schedule("a_headline", 10_000, 100_000, False)
        phase_schedule("b_hard_predicates", 5_000, 50_000, True)
        phase_apply()
        phase_serve()
    emit({"phase": "total", "wall_s": time.perf_counter() - t0})
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
