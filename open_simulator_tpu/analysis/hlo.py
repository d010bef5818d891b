"""simonaudit: compile-time dispatch certificates for every hot kernel.

simonlint (rules.py) proves source-level invariants; this module proves the
COMPILED ARTIFACT. Every kernel in ops.kernels.HOT_KERNELS is abstractly
traced at canonical shape buckets x mesh shapes (1/2/8 shards), lowered via
jit(...).lower() on CPU (no accelerator needed — `.compile()` runs the full
XLA SPMD partitioner, which is where collectives are born), and reduced to a
**dispatch certificate**:

  * collective census — count and estimated byte volume of every all-reduce /
    all-gather / reduce-scatter / collective-permute / all-to-all in the
    optimized HLO (static occurrences: a collective inside a while body is
    counted once per textual occurrence, i.e. per epoch/round of the loop);
  * escape census — custom_call targets and host callbacks (a host round trip
    hiding inside a "compiled" kernel costs a device-host round trip);
  * donation effectiveness — how many of the declared donate_argnums carry
    buffers XLA actually aliased into outputs (silent donation loss is
    invisible until device memory blows up at scale);
  * carry dtype promotions — output carry leaves whose dtype differs from the
    input contract (a promotion recompiles every chained dispatch);
  * the static-argument digest that keys recompiles — statics + abstract
    input signature + mesh; instability means the warm-path cache is lying.

Certificates are golden-filed under tests/golden/audit/ with a budget block;
`simon audit --check` fails on any new collective kind, count growth past the
budget, dropped donation, new custom_call/host-callback escape, or digest
drift; `--update` regenerates the goldens with a human-reviewable diff.

The executables audited here are built by the SAME code path the engine's
dispatch wrappers use (parallel.mesh.ShardedKernels._kernel_jit, via
`lowerable`), with identical shardings, statics, and donation — equivalent
by construction to the artifact production traffic runs (the audit
instantiates its own ShardedKernels so certification never mutates the
engine's cached executable set).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

SCHEMA = 1
S_LANES = 8          # candidate lanes in every probe fan-out audit
K_SEGS = 4           # wave-segment chain depth in the sweep fan-out audit
DEFAULT_SHARDS = (1, 2, 8)
CHAIN_TARGET = "schedule_wave_chain2"
EPOCH_TARGET = "schedule_affinity_epoch"
FIXTURE_TARGET = "fixture-extra-collective"  # CI negative control, opt-in

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(
    r"\b(pred|bf16|f16|f32|f64|s8|s16|s32|s64|u8|u16|u32|u64|c64|c128)"
    r"\[([0-9,]*)\]")
# one def line per op: `%name = <result-type> all-reduce(...)`; operand
# references (`%all-reduce.5, ...`) never put a `(` right after the op name,
# and `-done` halves of async pairs fail the `(?:-start)?\(` tail.
_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|\S+)\s+(" + "|".join(_COLLECTIVES) +
    r")(?:-start)?\(")
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9,\s]*\}:\s*\(\d+")
_CUSTOM_CALL_RE = re.compile(r'custom_call_target="([^"]+)"')


class Bucket(NamedTuple):
    """One canonical encode: a synthetic cluster/workload mix that populates
    the table families a kernel family reads (zones -> spread/DNS rows,
    anti -> carrier/anti rows), sized for fast CPU lowering."""

    nodes: int
    pods: int
    zones: int
    anti: bool = False


BUCKETS: Dict[str, Bucket] = {
    # small: the default CI gate — spread pods populate DNS/topo tables
    "s16x32": Bucket(nodes=16, pods=32, zones=2),
    # medium: adds required anti-affinity (carrier rows live) + more zones
    "m48x96": Bucket(nodes=48, pods=96, zones=4, anti=True),
}
DEFAULT_BUCKETS = ("s16x32", "m48x96")


# --------------------------------------------------------------- encoding ----

_ENCODE_CACHE: Dict[str, object] = {}


def _encode_bucket(bucket_key: str):
    """BatchTables for a canonical bucket (cached per process). Uses the real
    encoder so certificate shapes can never drift from production encodes."""
    bt = _ENCODE_CACHE.get(bucket_key)
    if bt is not None:
        return bt
    from ..simulator.engine import Simulator
    from ..utils.synth import synth_node, synth_pod

    b = BUCKETS[bucket_key]
    nodes = [synth_node(i, n_zones=b.zones) for i in range(b.nodes)]
    pods = []
    for i in range(b.pods):
        anti = b.anti and i % 5 == 4
        pods.append(synth_pod(
            i,
            labels={"app": "anti" if anti else "synth"},
            anti_affinity_on="anti" if anti else None,
            spread_zone=(i % 3 == 0) and not anti,
        ))
    sim = Simulator(nodes, use_mesh=False)
    bt = sim.encode_batch(pods)
    _ENCODE_CACHE[bucket_key] = bt
    return bt


def _abs_of(x):
    import numpy as np

    import jax

    a = np.asarray(x)
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


def _sds(shape, dtype):
    import numpy as np

    import jax

    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


def _abstract_head(btp, fanout: bool):
    """(tables, carry[, active_s]) as ShapeDtypeStructs from a padded
    BatchTables; fan-out carries grow the leading [S] candidate axis."""
    from ..ops import kernels
    from ..parallel.mesh import tables_from_batch

    tables = kernels.Tables(*(_abs_of(v) for v in tables_from_batch(btp)))
    seeds = dict(
        requested=btp.seed_requested, nonzero=btp.seed_nonzero,
        port_used=btp.seed_port_used, counter=btp.seed_counter,
        carrier=btp.seed_carrier, dev_used=btp.seed_dev_used,
        vg_req=btp.seed_vg_req, sdev_alloc=btp.seed_sdev_alloc)
    if fanout:
        import numpy as np

        carry = kernels.Carry(**{
            k: _sds((S_LANES,) + np.asarray(v).shape, np.asarray(v).dtype)
            for k, v in seeds.items()})
        active = _sds((S_LANES, btp.seed_requested.shape[0]), bool)
        return (tables, carry, active)
    return (tables, kernels.Carry(**{k: _abs_of(v) for k, v in seeds.items()}))


def _dyn_abs(token: str, P: int):
    import numpy as np

    kinds = {
        "g": ((), np.int32), "m": ((), np.int32), "forced": ((), np.int32),
        "cap1": ((), np.bool_), "valid1": ((), np.bool_),
        "valid_p": ((P,), np.bool_),
        "valid_sp": ((S_LANES, P), np.bool_),  # serve fan-out per-lane masks
        "g_s": ((S_LANES,), np.int32), "m_s": ((S_LANES,), np.int32),
        "cap1_s": ((S_LANES,), np.bool_),      # serve wave per-lane (g, m)
        "pod_group": ((P,), np.int32), "forced_node": ((P,), np.int32),
        # sweep fan-out: per-lane wave-segment chains and per-lane pod rows
        "g_sk": ((S_LANES, K_SEGS), np.int32),
        "m_sk": ((S_LANES, K_SEGS), np.int32),
        "cap1_sk": ((S_LANES, K_SEGS), np.bool_),
        "pod_group_s": ((S_LANES, P), np.int32),
        "forced_node_s": ((S_LANES, P), np.int32),
    }
    shape, dtype = kinds[token]
    return _sds(shape, dtype)


def _mesh_for(fanout: bool, shards: int):
    import numpy as np

    import jax

    from ..parallel.mesh import (
        NODE_AXIS, SCENARIO_AXIS, make_node_mesh, make_scenario_mesh)

    if not fanout:
        return make_node_mesh(shards), f"nodes{shards}"
    if shards == 1:
        # make_scenario_mesh(1) collapses to a 1-D node mesh; the fan-out
        # head needs the scenario axis present even at one shard
        from jax.sharding import Mesh

        devs = np.asarray(jax.devices()[:1]).reshape(1, 1)
        return Mesh(devs, (SCENARIO_AXIS, NODE_AXIS)), "scenarios1"
    return make_scenario_mesh(shards), f"scenarios{shards}"


# ------------------------------------------------------------- extraction ----


def _shape_bytes(result_tok: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(result_tok):
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_census(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """{op: {count, bytes}} over the optimized HLO module text. Bytes are the
    summed result-shape sizes (async -start tuples include the aliased input
    halves — an over-estimate, flagged by the schema as 'estimated')."""
    out: Dict[str, Dict[str, int]] = {}
    for m in _COLL_RE.finditer(hlo_text):
        kind = m.group(2)
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += _shape_bytes(m.group(1))
    return out


def _split_computations(hlo_text: str) -> Dict[str, str]:
    """{computation name: body text} over an optimized HLO module. Headers
    are non-indented `%name (args) -> result {` lines (ENTRY included);
    bodies run to the column-0 closing brace."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[List[str]] = None
    for line in hlo_text.splitlines():
        if not line.startswith((" ", "\t")) and line.rstrip().endswith("{"):
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", line)
            if m:
                cur = comps.setdefault(m.group(1), [])
                continue
        if line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return {k: "\n".join(v) for k, v in comps.items()}


_CALLEE_RE = re.compile(
    r"(?:to_apply|body|condition|calls)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")


def while_body_census(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """{while-body computation: transitive collective census} for every
    `while` op in the module — the PER-ITERATION collective cost of each
    loop (nested to_apply/calls/branch computations included). This is what
    the epoch-amortization contract pins: collective_census() counts a
    collective inside a loop body once per textual occurrence, but only the
    body attribution says whether the loop pays it every round."""
    comps = _split_computations(hlo_text)
    callees: Dict[str, set] = {}
    for name, body in comps.items():
        refs = set()
        for m in _CALLEE_RE.finditer(body):
            if m.group(1):
                refs.add(m.group(1))
            elif m.group(2):
                refs.update(r.strip().lstrip("%")
                            for r in m.group(2).split(",") if r.strip())
        callees[name] = refs

    def census_of(name: str, seen: set) -> Dict[str, int]:
        if name in seen:
            return {}
        seen.add(name)
        out: Dict[str, int] = {}
        for m in _COLL_RE.finditer(comps.get(name, "")):
            out[m.group(2)] = out.get(m.group(2), 0) + 1
        for ref in callees.get(name, ()):
            for k, v in census_of(ref, seen).items():
                out[k] = out.get(k, 0) + v
        return out

    out: Dict[str, Dict[str, int]] = {}
    for body in comps.values():
        for line in body.splitlines():
            if " while(" not in line:
                continue
            bm = re.search(r"\bbody=%?([\w.\-]+)", line)
            if bm:
                out[bm.group(1)] = census_of(bm.group(1), set())
    return out


def _alias_block(hlo_text: str) -> str:
    """The module header's input_output_alias block text (nested braces:
    balance by hand, regexes can't), or '' when absent."""
    head = hlo_text.split("\n", 1)[0]
    start = head.find("input_output_alias={")
    if start < 0:
        return ""
    i = head.index("{", start)
    depth = 0
    for j in range(i, len(head)):
        if head[j] == "{":
            depth += 1
        elif head[j] == "}":
            depth -= 1
            if depth == 0:
                return head[i:j + 1]
    return ""


def _alias_count(hlo_text: str) -> int:
    """Aliased buffer count from the module header's input_output_alias block."""
    return len(_ALIAS_ENTRY_RE.findall(_alias_block(hlo_text)))


def image_alias_count(lowered, n_image_params: int) -> int:
    """Donated leaves inside the shared-image table range: the first
    `n_image_params` flattened argument leaves (the `tables` head is always
    argument 0) of the lowered artifact's args_info. jax.stages.Lowered
    records per-leaf donation EXACTLY as declared to XLA (donated_invars),
    and unlike the optimized HLO's input_output_alias header it is immune to
    unused-parameter pruning renumbering the entries.

    The serving subsystem keeps one long-lived device-resident cluster image
    that every dispatch reads; donating any of its leaves would let a
    watchdog-abandoned zombie dispatch keep writing into buffers every other
    request still reads (the PR 9 hazard, now on shared state). The carry is
    the ONLY legal donation target, so a donated table leaf is a
    certification failure — on every kernel, since the engine's tables are
    equally long-lived across segments."""
    import jax

    leaves = jax.tree_util.tree_leaves(
        lowered.args_info, is_leaf=lambda x: hasattr(x, "donated"))
    return sum(1 for a in leaves[:n_image_params] if a.donated)


def escape_census(hlo_text: str) -> Tuple[List[str], List[str]]:
    """(custom_calls, host_callbacks): every custom_call target, split into
    host-callback escapes (python callbacks, infeed/outfeed) vs the rest."""
    targets = sorted(set(_CUSTOM_CALL_RE.findall(hlo_text)))
    host = [t for t in targets
            if "callback" in t.lower() or "infeed" in t.lower()
            or "outfeed" in t.lower()]
    if re.search(r"\b(?:infeed|outfeed)\(", hlo_text):
        host.append("infeed/outfeed-op")
    return [t for t in targets if t not in host], sorted(set(host))


class _Label(str):
    """A string whose repr is itself: stands in for a Mesh inside the
    statics repr."""

    def __repr__(self) -> str:
        return str(self)


def _digest(name: str, statics, abs_args, mesh_label: str,
            donate: Sequence[int]) -> str:
    """The stable identity of one compiled dispatch: everything jax keys the
    executable cache on that the engine controls. A drift here without a
    reviewed golden update means the warm path silently recompiles. A Mesh
    among the statics is hashed by its axis names and sizes, not by its
    repr, which changes between jax versions."""
    import jax
    from jax.sharding import Mesh

    statics = tuple(
        _Label("Mesh(" + ", ".join(f"{k}={v}" for k, v in s.shape.items())
               + ")") if isinstance(s, Mesh) else s
        for s in statics)
    leaves = jax.tree_util.tree_leaves(abs_args)
    payload = {
        "kernel": name,
        "statics": repr(statics),
        "in": [f"{tuple(a.shape)}:{a.dtype}" for a in leaves],
        "mesh": mesh_label,
        "donate": sorted(donate),
    }
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()[:16]


def dispatch_digest(kernel: str, dims) -> str:
    """The runtime sibling of `_digest`: the stable identity of one live
    dispatch from its `obs.record_dispatch` signature (kernel + the static
    shape/config dims the compile cache keys on). simonpulse keys its
    performance ledger on this — two records sharing a digest ran the same
    executable, so a wall-time delta between them is environmental; a digest
    change means the executable itself changed. Same construction as
    `_digest` (sha256 over a sorted-json payload, 16 hex chars) so ledger
    keys and audit certificates read as one digest family. No jax: dims are
    host scalars by the record_dispatch contract."""
    payload = {
        "kernel": kernel,
        "dims": {str(k): repr(v) for k, v in dims.items()},
    }
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()[:16]


def cost_census(compiled) -> dict:
    """FLOPs / bytes-accessed of one compiled executable. The roofline
    source: simonaudit embeds this as the certificate's `cost` field,
    simonpulse turns it into model-optimal seconds. Returns zeros when the
    backend offers no cost model — the field stays present so goldens keep a
    stable shape (check_cert never inspects it; drift here is informational,
    printed by --update only)."""
    from ..obs.pulse import normalize_cost

    try:
        raw = compiled.cost_analysis()
    # simonlint: ignore[swallowed-exception] -- diagnostics-only harvest: a
    # backend without a cost model must not fail certification of the
    # artifact's real contracts (collectives/donation/escapes)
    except Exception:
        raw = None
    return normalize_cost(raw) or {"flops": 0.0, "bytes_accessed": 0.0}


def _carry_promotions(name: str, spec, statics, head_abs, dyn_abs):
    """Output-carry leaves whose dtype left the input contract."""
    import jax

    from ..ops import kernels
    from ..parallel.mesh import _unwrap

    if spec.out is None:
        return []
    raw = _unwrap(getattr(kernels, name))
    out = jax.eval_shape(lambda *dyn: raw(*dyn, *statics), *head_abs, *dyn_abs)
    out_carry = out[0]
    in_carry = head_abs[1]
    return [
        {"leaf": f, "in": str(i.dtype), "out": str(o.dtype)}
        for f, i, o in zip(kernels.Carry._fields, in_carry, out_carry)
        if i.dtype != o.dtype
    ]


# ------------------------------------------------------------ certificates ----


def _budget_for(cert: dict) -> dict:
    """The machine-checked contract regenerated at --update time: 'no worse
    than this artifact'. Hand-tighten in the golden file to pin a stronger
    invariant (e.g. the ROADMAP affinity-epoch collective budget)."""
    budget = {
        "max_collective_count": sum(
            c["count"] for c in cert["collectives"].values()),
        "forbid_new_custom_calls": True,
    }
    if cert["donation"]["declared"]:
        budget["require_donation"] = cert["donation"]["held"]
    if "boundary_collectives" in cert:
        budget["max_boundary_collectives"] = 0
    if "epoch_contract_held" in cert:
        budget["require_epoch_contract"] = True
    return budget


def audit_kernel(name: str, bucket_key: str, shards: int) -> dict:
    """Lower + compile one registered hot kernel at (bucket, mesh) and
    extract its dispatch certificate."""
    from ..ops import kernels
    from ..parallel.mesh import ShardedKernels, pad_batch_tables

    spec = kernels.HOT_KERNELS[name]
    bt = _encode_bucket(bucket_key)
    mesh, mesh_label = _mesh_for(spec.fanout, shards)
    # fan-out tables live on the scenario mesh's node axis (size 1 at S>1)
    node_shards = mesh.shape["nodes"]
    btp = pad_batch_tables(bt, max(node_shards, 1))
    P = int(btp.pod_group.shape[0])

    # certify the DONATED artifact — the accelerator production executable.
    # Built directly (not via the sharded_kernels factory, which downgrades
    # donation on multi-device CPU meshes for RUNTIME safety): lowering
    # never executes anything, and the donation-effectiveness field exists
    # precisely to certify the aliasing of the donated program.
    sk = ShardedKernels(mesh)
    jfn, spec, meta = sk.lowerable(name, n_zones=int(btp.n_zones))
    head_abs = _abstract_head(btp, spec.fanout)
    dyn_abs = tuple(_dyn_abs(tok, P) for tok in spec.dyn)
    statics = meta["statics"]
    args = head_abs + dyn_abs + statics

    lowered = jfn.lower(*args)
    compiled = lowered.compile()
    text = compiled.as_text()
    colls = collective_census(text)
    custom, host = escape_census(text)
    declared = len(kernels.Carry._fields) if meta["donate_argnums"] else 0
    aliased = _alias_count(text)
    cert = {
        "schema": SCHEMA,
        "kernel": name,
        "bucket": bucket_key,
        "mesh": mesh_label,
        "static_digest": _digest(name, statics, head_abs + dyn_abs,
                                 mesh_label, meta["donate_argnums"]),
        "collectives": {k: colls[k] for k in sorted(colls)},
        "collective_count": sum(c["count"] for c in colls.values()),
        "collective_bytes": sum(c["bytes"] for c in colls.values()),
        "custom_calls": custom,
        "host_callbacks": host,
        "donation": {
            "declared": declared,
            "aliased": aliased,
            "held": aliased >= declared,
            # the cluster-image/table head must NEVER be donated into an
            # output: structural non-donatability of shared state (serve/)
            "image_leaf_aliased": image_alias_count(
                lowered, len(kernels.Tables._fields)),
        },
        "carry_promotions": _carry_promotions(
            name, spec, statics, head_abs, dyn_abs),
        # roofline source (simonpulse): model-optimal seconds derive from
        # these at the configured peak rates; never checked by check_cert
        "cost": cost_census(compiled),
    }
    cert["budget"] = _budget_for(cert)
    return cert


def audit_wave_chain(bucket_key: str, shards: int) -> dict:
    """The PR 8 invariant as a certificate: two chained schedule_wave
    dispatches under the SAME in/out shardings may contain at most 2x one
    dispatch's collectives — the dispatch boundary itself inserts ZERO
    resharding collectives (the static proof behind reshard_bytes == 0) —
    and the chain still aliases its donated carry."""
    import jax

    from ..ops import kernels
    from ..parallel.mesh import (
        _unwrap, carry_shardings, make_node_mesh, pad_batch_tables,
        table_shardings)

    bt = _encode_bucket(bucket_key)
    mesh = make_node_mesh(shards)
    mesh_label = f"nodes{shards}"
    btp = pad_batch_tables(bt, shards)
    head_abs = _abstract_head(btp, False)
    dyn_abs = tuple(_dyn_abs(tok, 0) for tok in ("g", "m", "cap1"))
    statics = kernels.HOT_KERNELS["schedule_wave"].statics(int(btp.n_zones))
    # trailing mesh static: the kernel-internal shard_map epoch loop (the
    # same value ShardedKernels._wave_mesh passes on a node-sharding mesh)
    statics = statics + (mesh if shards > 1 else None,)
    raw = _unwrap(kernels.schedule_wave)

    def single(tb, cry, g, m, cap1):
        return raw(tb, cry, g, m, cap1, *statics)

    def chain(tb, cry, g, m, cap1):
        c1, j1, p1, _ = raw(tb, cry, g, m, cap1, *statics)
        c2, j2, p2, o2 = raw(tb, c1, g, m, cap1, *statics)
        return c2, j1 + j2, p1 + p2, o2

    from jax.sharding import NamedSharding, PartitionSpec as P

    ts, cs = table_shardings(mesh), carry_shardings(mesh)
    rep = NamedSharding(mesh, P())
    node_sh = NamedSharding(mesh, P("nodes"))
    kw = dict(in_shardings=(ts, cs, rep, rep, rep),
              out_shardings=(cs, node_sh, rep, rep), donate_argnums=(1,))
    args = head_abs + dyn_abs
    t1 = jax.jit(single, **kw).lower(*args).compile().as_text()
    low2 = jax.jit(chain, **kw).lower(*args)
    t2 = low2.compile().as_text()
    c1 = collective_census(t1)
    c2 = collective_census(t2)
    n1 = sum(c["count"] for c in c1.values())
    n2 = sum(c["count"] for c in c2.values())
    custom, host = escape_census(t2)
    declared = len(kernels.Carry._fields)
    aliased = _alias_count(t2)
    cert = {
        "schema": SCHEMA,
        "kernel": CHAIN_TARGET,
        "bucket": bucket_key,
        "mesh": mesh_label,
        "static_digest": _digest(CHAIN_TARGET, statics, args, mesh_label,
                                 (1,)),
        "collectives": {k: c2[k] for k in sorted(c2)},
        "collective_count": n2,
        "collective_bytes": sum(c["bytes"] for c in c2.values()),
        "single_collective_count": n1,
        "boundary_collectives": max(0, n2 - 2 * n1),
        "custom_calls": custom,
        "host_callbacks": host,
        "donation": {"declared": declared, "aliased": aliased,
                     "held": aliased >= declared,
                     "image_leaf_aliased": image_alias_count(
                         low2, len(kernels.Tables._fields))},
        "carry_promotions": [],
    }
    cert["budget"] = _budget_for(cert)
    return cert


def audit_affinity_epoch(bucket_key: str, shards: int) -> dict:
    """The epoch-amortization contract as a certificate: on a node-sharding
    mesh, each wave kernel's epoch while-loop pays exactly ONE all-reduce
    (every normalizer reduction batched into one stacked max-space operand)
    plus ONE all-gather (the score-table payload — the cross-shard argmax at
    the epoch boundary) per epoch, and NO other loop in either module
    contains a collective. At one shard the loops contain no collectives at
    all. collective_census() alone cannot pin this — a prologue collective
    and a per-round collective count the same there; while_body_census()
    attributes them to the loop that pays them every iteration."""
    from ..ops import kernels
    from ..parallel.mesh import ShardedKernels, pad_batch_tables

    bt = _encode_bucket(bucket_key)
    epoch: Dict[str, dict] = {}
    total: Dict[str, Dict[str, int]] = {}
    custom_u: set = set()
    host_u: set = set()
    held = True
    digest_args: list = []
    mesh_label = f"nodes{shards}"
    for name in ("schedule_wave", "schedule_affinity_wave"):
        spec = kernels.HOT_KERNELS[name]
        mesh, mesh_label = _mesh_for(spec.fanout, shards)
        btp = pad_batch_tables(bt, max(mesh.shape["nodes"], 1))
        P = int(btp.pod_group.shape[0])
        sk = ShardedKernels(mesh)
        jfn, spec, meta = sk.lowerable(name, n_zones=int(btp.n_zones))
        head_abs = _abstract_head(btp, spec.fanout)
        dyn_abs = tuple(_dyn_abs(tok, P) for tok in spec.dyn)
        text = jfn.lower(
            *(head_abs + dyn_abs + meta["statics"])).compile().as_text()
        bodies = {k: dict(sorted(v.items()))
                  for k, v in while_body_census(text).items() if v}
        # loop keys, not raw computation names: XLA pass pipelines rename
        # computations freely, and a golden keyed on them would churn on
        # every toolchain bump without any semantic change
        epoch[name] = {f"loop{i}": v for i, (_, v)
                       in enumerate(sorted(bodies.items()))}
        if shards > 1:
            held &= (len(bodies) == 1
                     and next(iter(bodies.values()))
                     == {"all-gather": 1, "all-reduce": 1})
        else:
            held &= not bodies
        for k, rec in collective_census(text).items():
            t = total.setdefault(k, {"count": 0, "bytes": 0})
            t["count"] += rec["count"]
            t["bytes"] += rec["bytes"]
        custom, host = escape_census(text)
        custom_u.update(custom)
        host_u.update(host)
        digest_args.append((meta["statics"], head_abs + dyn_abs))
    cert = {
        "schema": SCHEMA,
        "kernel": EPOCH_TARGET,
        "bucket": bucket_key,
        "mesh": mesh_label,
        "static_digest": _digest(
            EPOCH_TARGET, tuple(repr(s) for s, _ in digest_args),
            tuple(a for _, args in digest_args for a in args), mesh_label,
            ()),
        "collectives": {k: total[k] for k in sorted(total)},
        "collective_count": sum(c["count"] for c in total.values()),
        "collective_bytes": sum(c["bytes"] for c in total.values()),
        "epoch_census": epoch,
        "epoch_contract_held": bool(held),
        "custom_calls": sorted(custom_u),
        "host_callbacks": sorted(host_u),
        "donation": {"declared": 0, "aliased": 0, "held": True},
        "carry_promotions": [],
    }
    cert["budget"] = _budget_for(cert)
    return cert


def audit_fixture(shards: int = 8) -> dict:
    """Deliberately collective-heavy toy kernel — NOT a product kernel. CI
    checks it against a doctored golden (one all-reduce fewer than reality)
    to prove the --check gate actually fails on a new collective."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import NODE_AXIS, make_node_mesh

    mesh = make_node_mesh(shards)
    sh = NamedSharding(mesh, P(NODE_AXIS))
    rep = NamedSharding(mesh, P())

    def fx(x):
        x = x - jnp.mean(x)       # cross-shard all-reduce #1
        return jnp.max(jnp.abs(x))  # cross-shard all-reduce #2 (the "extra")

    jfn = jax.jit(fx, in_shardings=(sh,), out_shardings=rep)
    arg = _sds((16 * shards,), np.float32)
    text = jfn.lower(arg).compile().as_text()
    colls = collective_census(text)
    custom, host = escape_census(text)
    mesh_label = f"nodes{shards}"
    cert = {
        "schema": SCHEMA,
        "kernel": FIXTURE_TARGET,
        "bucket": "fixture",
        "mesh": mesh_label,
        "static_digest": _digest(FIXTURE_TARGET, (), (arg,), mesh_label, ()),
        "collectives": {k: colls[k] for k in sorted(colls)},
        "collective_count": sum(c["count"] for c in colls.values()),
        "collective_bytes": sum(c["bytes"] for c in colls.values()),
        "custom_calls": custom,
        "host_callbacks": host,
        "donation": {"declared": 0, "aliased": 0, "held": True},
        "carry_promotions": [],
    }
    cert["budget"] = _budget_for(cert)
    return cert


# ---------------------------------------------------------------- targets ----


def target_names() -> List[str]:
    from ..ops import kernels

    return list(kernels.HOT_KERNELS) + [CHAIN_TARGET, EPOCH_TARGET]


def run_targets(select: Optional[Sequence[str]], buckets: Sequence[str],
                shards_list: Sequence[int], log=None) -> List[dict]:
    """Certificates for the selected targets over buckets x shards. The
    wave-chain target audits at the largest multi-shard mesh only (its
    budget is the cross-dispatch boundary, meaningless at one shard);
    the CI fixture runs only when explicitly selected."""
    names = list(select) if select else target_names()
    certs: List[dict] = []
    multi = [s for s in shards_list if s > 1]
    for name in names:
        if name == FIXTURE_TARGET:
            certs.append(audit_fixture(max(shards_list)))
            if log:
                log(certs[-1])
            continue
        for bucket in buckets:
            if name == CHAIN_TARGET:
                if multi:
                    certs.append(audit_wave_chain(bucket, max(multi)))
                    if log:
                        log(certs[-1])
                continue
            if name == EPOCH_TARGET:
                for shards in shards_list:
                    certs.append(audit_affinity_epoch(bucket, shards))
                    if log:
                        log(certs[-1])
                continue
            for shards in shards_list:
                certs.append(audit_kernel(name, bucket, shards))
                if log:
                    log(certs[-1])
    return certs


# ------------------------------------------------------------- golden files ----


def _cert_key(cert: dict) -> str:
    return f"{cert['bucket']}/{cert['mesh']}"


def golden_path(golden_dir: str, kernel: str) -> str:
    return os.path.join(golden_dir, f"{kernel}.json")


def load_golden(golden_dir: str, kernel: str) -> Optional[dict]:
    path = golden_path(golden_dir, kernel)
    if not os.path.exists(path):
        return None
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def _merge_budget(old: Optional[dict], new: dict) -> dict:
    """--update must never silently LOOSEN a hand-tightened golden budget:
    keep the stricter of each bound (smaller max_*, sticky require_*/
    forbid_*). Loosening a pinned contract takes a hand edit of the golden
    file, in a reviewed diff."""
    if not old:
        return new
    out = dict(new)
    for key in ("max_collective_count", "max_boundary_collectives"):
        if key in old and old[key] < out.get(key, old[key] + 1):
            out[key] = old[key]
    for key in ("require_donation", "forbid_new_custom_calls"):
        if old.get(key):
            out[key] = True
    for key in ("note",):  # hand-written rationale survives regeneration
        if key in old:
            out[key] = old[key]
    return out


def write_goldens(golden_dir: str, certs: Sequence[dict],
                  full: bool = False) -> List[str]:
    """Write certificates into per-kernel golden files. Partial runs
    (--select / subset shards) MERGE into existing docs; `full` (the default
    --update matrix) REGENERATES — stale cert keys and golden files for
    kernels no longer in the live set are pruned, so the goldens never
    advertise coverage that no longer runs. In both modes, hand-tightened
    budget bounds in the existing goldens are preserved (_merge_budget)."""
    os.makedirs(golden_dir, exist_ok=True)
    by_kernel: Dict[str, Dict[str, dict]] = {}
    for c in certs:
        by_kernel.setdefault(c["kernel"], {})[_cert_key(c)] = c
    written = []
    for kernel, cmap in sorted(by_kernel.items()):
        prev = load_golden(golden_dir, kernel)
        doc = (None if full else prev) or {
            "schema": SCHEMA, "kernel": kernel, "certs": {}}
        for key, cert in cmap.items():
            old = (prev or {}).get("certs", {}).get(key)
            cert = dict(cert)
            cert["budget"] = _merge_budget(
                (old or {}).get("budget"), cert["budget"])
            doc["certs"][key] = cert
        doc["certs"] = {k: doc["certs"][k] for k in sorted(doc["certs"])}
        path = golden_path(golden_dir, kernel)
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
            fh.write("\n")
        written.append(path)
    if full:
        keep = {f"{k}.json" for k in by_kernel}
        for fn in sorted(os.listdir(golden_dir)):
            if fn.endswith(".json") and fn not in keep:
                os.remove(os.path.join(golden_dir, fn))
                print(f"  pruned stale golden {fn}")
    return written


def check_cert(live: dict, golden: dict) -> List[str]:
    """Regressions of `live` vs its golden: new collective kinds, counts
    past the golden budget, dropped donation, new escapes, digest drift,
    fresh carry promotions, a non-zero chain boundary."""
    out: List[str] = []
    where = f"{live['kernel']} {_cert_key(live)}"
    if live["static_digest"] != golden["static_digest"]:
        out.append(
            f"{where}: dispatch signature drift "
            f"{golden['static_digest']} -> {live['static_digest']} "
            f"(statics/shapes changed: review + `simon audit --update`)")
    budget = golden.get("budget", {})
    gcolls = golden.get("collectives", {})
    for kind, rec in live["collectives"].items():
        if kind not in gcolls:
            out.append(f"{where}: NEW collective kind {kind} "
                       f"(x{rec['count']}, ~{rec['bytes']}B)")
        elif rec["count"] > gcolls[kind]["count"]:
            out.append(f"{where}: {kind} count grew "
                       f"{gcolls[kind]['count']} -> {rec['count']}")
    maxc = budget.get("max_collective_count")
    if maxc is not None and live["collective_count"] > maxc:
        out.append(f"{where}: collective total {live['collective_count']} "
                   f"exceeds budget {maxc}")
    if budget.get("forbid_new_custom_calls", True):
        for field in ("custom_calls", "host_callbacks"):
            new = set(live[field]) - set(golden.get(field, []))
            if new:
                out.append(f"{where}: new {field.replace('_', ' ')} escape: "
                           f"{sorted(new)}")
    gdon = golden.get("donation", {})
    ldon = live["donation"]
    if ldon["aliased"] < gdon.get("aliased", 0):
        out.append(f"{where}: donation dropped — {ldon['aliased']}/"
                   f"{ldon['declared']} buffers aliased "
                   f"(golden {gdon.get('aliased')})")
    if budget.get("require_donation") and not ldon["held"]:
        out.append(f"{where}: donation no longer held "
                   f"({ldon['aliased']}/{ldon['declared']} aliased)")
    if ldon.get("image_leaf_aliased", 0):
        # unconditional (no golden opt-out): a table/cluster-image leaf
        # aliased into an output means a dispatch can write into shared
        # long-lived state — the serve zombie-write hazard, never budgetable
        out.append(f"{where}: {ldon['image_leaf_aliased']} shared-image "
                   f"table leaf(s) aliased into outputs — image/table "
                   f"buffers are structurally non-donatable")
    gprom = {p["leaf"] for p in golden.get("carry_promotions", [])}
    for p in live.get("carry_promotions", []):
        if p["leaf"] not in gprom:
            out.append(f"{where}: carry dtype promotion on '{p['leaf']}' "
                       f"{p['in']} -> {p['out']}")
    mbc = budget.get("max_boundary_collectives")
    if mbc is not None and live.get("boundary_collectives", 0) > mbc:
        out.append(f"{where}: dispatch boundary inserted "
                   f"{live['boundary_collectives']} collectives (budget {mbc})")
    if budget.get("require_epoch_contract") \
            and not live.get("epoch_contract_held", True):
        out.append(f"{where}: epoch collective contract broken — a loop "
                   f"body strayed from one all-reduce + one all-gather per "
                   f"epoch: {live.get('epoch_census')}")
    return out


def check_certs(certs: Sequence[dict], golden_dir: str) -> Tuple[List[str], List[str]]:
    """(regressions, notes). Missing goldens are regressions — an unaudited
    hot kernel is exactly what the gate exists to prevent."""
    regressions: List[str] = []
    notes: List[str] = []
    for live in certs:
        doc = load_golden(golden_dir, live["kernel"])
        golden = (doc or {}).get("certs", {}).get(_cert_key(live))
        if golden is None:
            regressions.append(
                f"{live['kernel']} {_cert_key(live)}: no golden certificate "
                f"in {golden_dir} (run `simon audit --update`)")
            continue
        regressions.extend(check_cert(live, golden))
        if live["collective_count"] < golden["collective_count"]:
            notes.append(
                f"{live['kernel']} {_cert_key(live)}: collectives improved "
                f"{golden['collective_count']} -> {live['collective_count']} "
                f"(tighten with `simon audit --update`)")
    return regressions, notes


def diff_cert(live: dict, golden: Optional[dict]) -> List[str]:
    """Human-reviewable field diff for --update output."""
    if golden is None:
        return [f"  NEW {live['kernel']} {_cert_key(live)}: "
                f"{live['collective_count']} collective(s), donation "
                f"{live['donation']['aliased']}/{live['donation']['declared']}"]
    out = []
    for field in ("static_digest", "collectives", "collective_count",
                  "collective_bytes", "custom_calls", "host_callbacks",
                  "donation", "carry_promotions", "boundary_collectives",
                  "epoch_census", "epoch_contract_held", "budget"):
        if field in live or field in golden:
            a, b = golden.get(field), live.get(field)
            if a != b:
                out.append(f"  {live['kernel']} {_cert_key(live)}: "
                           f"{field} {a} -> {b}")
    return out


# ---------------------------------------------------------------------- CLI ----


def _default_golden_dir() -> str:
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(pkg_root, "tests", "golden", "audit")


def _human_line(cert: dict) -> str:
    colls = ", ".join(f"{k} x{v['count']}"
                      for k, v in cert["collectives"].items()) or "none"
    don = cert["donation"]
    extra = ""
    if "boundary_collectives" in cert:
        extra = f" boundary={cert['boundary_collectives']}"
    esc = ""
    if cert["custom_calls"] or cert["host_callbacks"]:
        esc = (f" escapes={cert['custom_calls'] + cert['host_callbacks']}")
    return (f"{cert['kernel']:<28} {cert['bucket']:>7}/{cert['mesh']:<10} "
            f"collectives: {colls} (~{cert['collective_bytes']}B) "
            f"donation {don['aliased']}/{don['declared']}{extra}{esc} "
            f"digest {cert['static_digest'][:8]}")


def run_audit(argv: Optional[Sequence[str]] = None) -> int:
    """The `simon audit` command."""
    parser = argparse.ArgumentParser(
        prog="simon audit",
        description="simonaudit: compile-time dispatch certificates — "
                    "collective census, donation effectiveness, host-callback "
                    "escapes, and recompile-keying digests for every "
                    "registered hot kernel, lowered on CPU at canonical "
                    "shape buckets x mesh shapes.",
    )
    mode = parser.add_mutually_exclusive_group()
    mode.add_argument("--check", action="store_true",
                      help="diff live certificates against the goldens; "
                           "exit 1 on any regression (the CI gate)")
    mode.add_argument("--update", action="store_true",
                      help="regenerate the golden certificates and print a "
                           "human-reviewable diff")
    parser.add_argument("--select", default="",
                        help="comma-separated target names (default: every "
                             "registered hot kernel + the wave-chain target; "
                             "the CI fixture only runs when named here)")
    parser.add_argument("--buckets", default=",".join(DEFAULT_BUCKETS),
                        help=f"comma-separated shape buckets "
                             f"(known: {', '.join(BUCKETS)})")
    parser.add_argument("--shards", default="1,2,8",
                        help="comma-separated mesh shard counts")
    parser.add_argument("--golden-dir", default=_default_golden_dir(),
                        help="golden certificate directory "
                             "(default: tests/golden/audit)")
    parser.add_argument("--format", choices=("human", "json"),
                        default="human")
    args = parser.parse_args(list(argv) if argv is not None else None)

    try:
        shards_list = tuple(
            int(s) for s in args.shards.split(",") if s.strip())
    except ValueError:
        parser.error(f"--shards must be comma-separated integers "
                     f"(got {args.shards!r})")
    if not shards_list or any(s < 1 for s in shards_list):
        parser.error(f"--shards needs at least one positive shard count "
                     f"(got {args.shards!r})")
    buckets = tuple(b.strip() for b in args.buckets.split(",") if b.strip())
    unknown = [b for b in buckets if b not in BUCKETS]
    if unknown:
        parser.error(f"unknown bucket(s): {', '.join(unknown)}")
    select = [s.strip() for s in args.select.split(",") if s.strip()] or None
    if select:
        known = set(target_names()) | {FIXTURE_TARGET}
        bad = [s for s in select if s not in known]
        if bad:
            parser.error(f"unknown target(s): {', '.join(bad)}")
        if CHAIN_TARGET in select and not any(s > 1 for s in shards_list):
            # never silently drop an explicitly requested target: the chain
            # invariant is meaningless at one shard, so refuse loudly
            parser.error(f"{CHAIN_TARGET} needs a multi-shard mesh in "
                         f"--shards (got {args.shards})")
    if select is None and not any(s > 1 for s in shards_list):
        # the default target list includes the chain invariant; dropping it
        # because --shards has no multi-shard mesh must be visible, not a
        # silently-narrower green gate
        print(f"note: {CHAIN_TARGET} skipped — no multi-shard mesh in "
              f"--shards (got {args.shards})", file=sys.stderr)

    # the 8-shard meshes need 8 virtual CPU devices BEFORE backend init
    from ..utils.devices import force_cpu_platform, request_cpu_devices

    request_cpu_devices(max(shards_list))
    force_cpu_platform()
    import jax

    if len(jax.devices()) < max(shards_list):
        print(f"audit error: need {max(shards_list)} devices, have "
              f"{len(jax.devices())} (the JAX backend initialized before "
              f"the virtual-CPU flag could be set)", file=sys.stderr)
        return 2

    human = args.format == "human"
    certs = run_targets(
        select, buckets, shards_list,
        log=(lambda c: print(_human_line(c), flush=True)) if human and not args.update
        else None)
    if not certs:
        # a gate that checked nothing must not report green (e.g. the chain
        # target selected with only single-shard meshes)
        print("audit error: the selection produced no certificates "
              "(schedule_wave_chain2 needs a multi-shard mesh in --shards)",
              file=sys.stderr)
        return 2

    full_matrix = (select is None
                   and set(buckets) == set(DEFAULT_BUCKETS)
                   and set(shards_list) == set(DEFAULT_SHARDS))
    if args.update:
        diffs: List[str] = []
        for c in certs:
            doc = load_golden(args.golden_dir, c["kernel"])
            golden = (doc or {}).get("certs", {}).get(_cert_key(c))
            diffs.extend(diff_cert(c, golden))
        written = write_goldens(args.golden_dir, certs, full=full_matrix)
        print("\n".join(diffs) if diffs
              else "  goldens unchanged (certificates identical)")
        print(f"simonaudit: wrote {len(written)} golden file(s), "
              f"{len(certs)} certificate(s) -> {args.golden_dir}")
        return 0

    if args.check:
        regressions, notes = check_certs(certs, args.golden_dir)
        for n in notes:
            print(f"note: {n}")
        for r in regressions:
            print(f"REGRESSION: {r}", file=sys.stderr)
        verdict = ("FAIL" if regressions else "ok")
        print(f"simonaudit --check: {len(certs)} certificate(s), "
              f"{len(regressions)} regression(s) — {verdict}")
        return 1 if regressions else 0

    if args.format == "json":
        print(json.dumps(certs, indent=1, sort_keys=True))
    else:
        total = sum(c["collective_count"] for c in certs)
        print(f"simonaudit: {len(certs)} certificate(s), {total} "
              f"collective(s) total (use --check against "
              f"{args.golden_dir}, --update to regenerate)")
    return 0
