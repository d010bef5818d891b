"""Plain reference of the scheduling semantics the configurations use.

A straightforward serial kube-scheduler v1.20 over the generator's plain
data (benchmark/cluster.py): pods one at a time in input order, each
filtered and scored against every node, the best score taken, ties to the
lowest node index. It imports nothing of the program and takes nothing it
made.

Filters: NodeResourcesFit (cpu, memory, pod count), PodTopologySpread
(DoNotSchedule), InterPodAffinity (required anti-affinity, both
directions). Scores: NodeResourcesLeastAllocated and
NodeResourcesBalancedAllocation at weight 1 (least_allocated.go,
balanced_allocation.go: integer least-requested, float64 fractions, over
the non-zero requests). Every other default score plugin is the same on
every node for these inputs (no taints, images, preferred terms, soft
spread or selector-spread selectors, identical node allocatable), which
`check_supported` asserts, so they cannot move an argmax.

The only shortcut is a cache: a node's score for a request class changes
only when that node receives a pod, so each placement rescores one node.
"""

from __future__ import annotations

import re
from fractions import Fraction
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

HOSTNAME = "kubernetes.io/hostname"
DEFAULT_MILLI_CPU = 100           # schedutil.DefaultMilliCPURequest
DEFAULT_MEMORY = 200 * 1024 * 1024  # schedutil.DefaultMemoryRequest

_SUFFIX = {"": 1, "k": 10**3, "M": 10**6, "G": 10**9, "T": 10**12,
           "P": 10**15, "E": 10**18, "Ki": 2**10, "Mi": 2**20, "Gi": 2**30,
           "Ti": 2**40, "Pi": 2**50, "Ei": 2**60, "m": Fraction(1, 1000)}
_QTY = re.compile(r"^([0-9.]+)([a-zA-Z]*)$")


def quantity(s) -> Fraction:
    m = _QTY.match(str(s).strip())
    if not m or m.group(2) not in _SUFFIX:
        raise ValueError(f"unsupported quantity {s!r}")
    return Fraction(m.group(1)) * _SUFFIX[m.group(2)]


def milli_cpu(s) -> int:
    return int(-(-quantity(s) * 1000 // 1))  # rounded up, as MilliValue()


def value(s) -> int:
    return int(-(-quantity(s) // 1))  # rounded up, as Value()


class PodKind:
    """What the filters and scores read of one pod template."""

    def __init__(self, t: dict) -> None:
        md = t.get("metadata") or {}
        spec = t.get("spec") or {}
        self.namespace = md.get("namespace") or "default"
        self.labels = dict(md.get("labels") or {})
        cpu = mem = 0
        nz_cpu = nz_mem = 0
        for c in spec.get("containers") or []:
            req = (c.get("resources") or {}).get("requests") or {}
            ccpu = milli_cpu(req["cpu"]) if "cpu" in req else 0
            cmem = value(req["memory"]) if "memory" in req else 0
            cpu += ccpu
            mem += cmem
            nz_cpu += ccpu if "cpu" in req else DEFAULT_MILLI_CPU
            nz_mem += cmem if "memory" in req else DEFAULT_MEMORY
        self.cpu, self.mem, self.nz_cpu, self.nz_mem = cpu, mem, nz_cpu, nz_mem
        aff = spec.get("affinity") or {}
        self.anti = [(term["topologyKey"], term.get("labelSelector") or {},
                      tuple(term.get("namespaces") or (self.namespace,)))
                     for term in (aff.get("podAntiAffinity") or {}).get(
                         "requiredDuringSchedulingIgnoredDuringExecution") or []]
        self.spread = [(c["topologyKey"], int(c["maxSkew"]),
                        c.get("labelSelector") or {})
                       for c in spec.get("topologySpreadConstraints") or []
                       if c.get("whenUnsatisfiable") == "DoNotSchedule"]


def selector_matches(sel: dict, labels: dict) -> bool:
    for k, v in (sel.get("matchLabels") or {}).items():
        if labels.get(k) != v:
            return False
    for e in sel.get("matchExpressions") or []:
        op, k, vals = e["operator"], e["key"], e.get("values") or []
        if op == "In" and labels.get(k) not in vals:
            return False
        if op == "NotIn" and k in labels and labels[k] in vals:
            return False
        if op == "Exists" and k not in labels:
            return False
        if op == "DoesNotExist" and k in labels:
            return False
    return True


def check_supported(cluster) -> None:
    """Refuse inputs whose semantics this reference does not implement."""
    nt = cluster.node_template
    if (nt.get("spec") or {}).get("taints") or (nt.get("status") or {}).get("images"):
        raise ValueError("reference: tainted nodes or node images")
    for u in cluster.units:
        spec = u.template.get("spec") or {}
        aff = spec.get("affinity") or {}
        if (spec.get("nodeSelector") or spec.get("nodeName")
                or aff.get("nodeAffinity") or aff.get("podAffinity")
                or (aff.get("podAntiAffinity") or {}).get(
                    "preferredDuringSchedulingIgnoredDuringExecution")
                or any(c.get("whenUnsatisfiable") != "DoNotSchedule"
                       for c in spec.get("topologySpreadConstraints") or [])
                or any(cc.get("hostPort") for c in spec.get("containers") or []
                       for cc in c.get("ports") or [])):
            raise ValueError(f"reference: unit {u.name} needs semantics it lacks")


class Reference:
    """Serial scheduler state over one cluster."""

    def __init__(self, cluster) -> None:
        check_supported(cluster)
        self.c = cluster
        n = cluster.n_nodes
        st = cluster.node_template.get("status") or {}
        alloc = st.get("allocatable") or st.get("capacity") or {}
        self.a_cpu = milli_cpu(alloc.get("cpu", 0))
        self.a_mem = value(alloc.get("memory", 0))
        self.a_pods = int(alloc.get("pods", 0))
        self.used_cpu = np.zeros(n, np.int64)
        self.used_mem = np.zeros(n, np.int64)
        self.nz_cpu = np.zeros(n, np.int64)
        self.nz_mem = np.zeros(n, np.int64)
        self.pods = np.zeros(n, np.int64)
        # topology domains: key -> [N] domain id (-1 = label absent)
        self.domains: Dict[str, np.ndarray] = {HOSTNAME: np.arange(n)}
        if cluster.zone_key is not None:
            self.domains[cluster.zone_key] = np.asarray(cluster.node_zone)
        self.kinds: Dict[int, PodKind] = {}
        # placed pods per node, per kind id: only kept where some pod has a
        # term that counts pods (selector counts for anti-affinity, spread)
        self.counting = any(PodKind(u.template).anti or PodKind(u.template).spread
                            for u in cluster.units)
        self.kind_count: Dict[int, np.ndarray] = {}
        self._score_cache: Dict[Tuple[int, int, int, int], np.ndarray] = {}

    # ----------------------------------------------------------- scoring --
    def _scores(self, k: PodKind) -> np.ndarray:
        """[N] LeastAllocated + BalancedAllocation, -1 where the pod does
        not fit."""
        fit = ((self.used_cpu + k.cpu <= self.a_cpu)
               & (self.used_mem + k.mem <= self.a_mem)
               & (self.pods + 1 <= self.a_pods))
        rc = self.nz_cpu + k.nz_cpu
        rm = self.nz_mem + k.nz_mem

        def least(req, cap):
            if cap == 0:
                return np.zeros_like(req)
            return np.where(req > cap, 0, ((cap - req) * 100) // cap)

        la = (least(rc, self.a_cpu) + least(rm, self.a_mem)) // 2
        cf = rc / self.a_cpu if self.a_cpu else np.ones(rc.shape)
        mf = rm / self.a_mem if self.a_mem else np.ones(rm.shape)
        ba = np.where((cf >= 1) | (mf >= 1), 0,
                      ((1.0 - np.abs(cf - mf)) * 100.0).astype(np.int64))
        return np.where(fit, la + ba, -1)

    def _score_one(self, k, node: int) -> int:
        """_scores for one node, in Python integers and floats."""
        if (int(self.used_cpu[node]) + k.cpu > self.a_cpu
                or int(self.used_mem[node]) + k.mem > self.a_mem
                or int(self.pods[node]) + 1 > self.a_pods):
            return -1
        rc = int(self.nz_cpu[node]) + k.nz_cpu
        rm = int(self.nz_mem[node]) + k.nz_mem

        def least(req, cap):
            return 0 if cap == 0 or req > cap else ((cap - req) * 100) // cap

        la = (least(rc, self.a_cpu) + least(rm, self.a_mem)) // 2
        cf = rc / self.a_cpu if self.a_cpu else 1.0
        mf = rm / self.a_mem if self.a_mem else 1.0
        ba = 0 if (cf >= 1 or mf >= 1) else int((1.0 - abs(cf - mf)) * 100.0)
        return la + ba

    def _class_scores(self, k: PodKind) -> np.ndarray:
        key = (k.cpu, k.mem, k.nz_cpu, k.nz_mem)
        s = self._score_cache.get(key)
        if s is None:
            s = self._score_cache[key] = self._scores(k)
        return s

    # ---------------------------------------------------------- filtering --
    def _matching(self, sel: dict, namespaces: Sequence[str]) -> np.ndarray:
        """[N] count of placed pods matching sel within namespaces."""
        out = np.zeros(self.c.n_nodes, np.int64)
        for kid, cnt in self.kind_count.items():
            k = self.kinds[kid]
            if k.namespace in namespaces and selector_matches(sel, k.labels):
                out += cnt
        return out

    def _constraint_mask(self, k: PodKind) -> Optional[np.ndarray]:
        if not self.counting:
            return None
        n = self.c.n_nodes
        ok = np.ones(n, bool)
        for key, sel, nss in k.anti:  # the incoming pod's terms
            dom = self.domains.get(key)
            if dom is None:
                continue
            bad = np.unique(dom[(self._matching(sel, nss) > 0) & (dom >= 0)])
            ok &= ~np.isin(dom, bad)
        for kid, cnt in self.kind_count.items():  # existing pods' terms
            ek = self.kinds[kid]
            for key, sel, nss in ek.anti:
                if k.namespace in nss and selector_matches(sel, k.labels):
                    dom = self.domains.get(key)
                    if dom is None:
                        continue
                    bad = np.unique(dom[(cnt > 0) & (dom >= 0)])
                    ok &= ~np.isin(dom, bad)
        for key, max_skew, sel in k.spread:
            dom = self.domains.get(key)
            if dom is None:
                return np.zeros(n, bool)
            has = dom >= 0
            per_node = self._matching(sel, (k.namespace,))
            nd = int(dom.max()) + 1
            counts = np.bincount(dom[has], weights=per_node[has],
                                 minlength=nd).astype(np.int64)
            present = np.bincount(dom[has], minlength=nd) > 0
            min_match = counts[present].min()
            self_match = 1 if selector_matches(sel, k.labels) else 0
            skew = np.where(has, counts[np.where(has, dom, 0)] + self_match
                            - min_match, max_skew + 1)
            ok &= has & (skew <= max_skew)
        return ok

    # ------------------------------------------------------------ placing --
    def _commit(self, kid: int, k: PodKind, node: int) -> None:
        self.used_cpu[node] += k.cpu
        self.used_mem[node] += k.mem
        self.nz_cpu[node] += k.nz_cpu
        self.nz_mem[node] += k.nz_mem
        self.pods[node] += 1
        if self.counting:
            cnt = self.kind_count.get(kid)
            if cnt is None:
                cnt = self.kind_count[kid] = np.zeros(self.c.n_nodes, np.int64)
            cnt[node] += 1
        for key, s in self._score_cache.items():
            s[node] = self._score_one(_KeyKind(key), node)

    def kind_id(self, template: dict) -> int:
        kid = id(template)
        if kid not in self.kinds:
            self.kinds[kid] = PodKind(template)
        return kid

    def place(self, template: dict, count: int,
              batch_scores: bool = False) -> np.ndarray:
        """Place `count` replicas in order; node per pod, -1 = unschedulable.
        batch_scores: the control's shortcut, scoring every replica against
        the state before the first one (capacity still updates)."""
        kid = self.kind_id(template)
        k = self.kinds[kid]
        out = np.full(count, -1, np.int64)
        frozen = self._class_scores(k).copy() if batch_scores else None
        for r in range(count):
            s = self._class_scores(k)
            mask = self._constraint_mask(k)
            if batch_scores:
                fit = s >= 0
                s = np.where(fit, frozen, -1)
            if mask is not None:
                s = np.where(mask, s, -1)
            best = int(np.argmax(s))
            if s[best] < 0:
                continue  # unschedulable: nothing commits
            out[r] = best
            self._commit(kid, k, best)
        return out

    def bind(self, template: dict, nodes: np.ndarray) -> None:
        """Pre-bound pods (a live cluster's state): commit without scoring."""
        kid = self.kind_id(template)
        k = self.kinds[kid]
        for node in np.asarray(nodes).tolist():
            self._commit(kid, k, int(node))

    def schedule_all(self, batch_scores: bool = False) -> List[np.ndarray]:
        return [self.place(u.template, u.count, batch_scores)
                for u in self.c.units]

    # ------------------------------------------------------------ what-if --
    def whatif(self, template: dict, count: int,
               batch_scores: bool = False) -> dict:
        """A what-if answer against the current state, never committed: the
        state is restored after placing. "rows" is the [N, 2] requested
        cpu (milli) and memory (bytes) of every node with the request
        placed: where each replica landed."""
        saved = (self.used_cpu.copy(), self.used_mem.copy(), self.nz_cpu.copy(),
                 self.nz_mem.copy(), self.pods.copy(),
                 {k: v.copy() for k, v in self.kind_count.items()},
                 {k: v.copy() for k, v in self._score_cache.items()})
        try:
            placed = int((self.place(template, count, batch_scores) >= 0).sum())
            n = self.c.n_nodes
            return {"scheduled": placed, "total": count,
                    "unscheduled": count - placed,
                    "utilization": {"cpu_used": float(int(self.used_cpu.sum())),
                                    "cpu_alloc": float(self.a_cpu * n),
                                    "mem_used": float(int(self.used_mem.sum())),
                                    "mem_alloc": float(self.a_mem * n)},
                    "rows": np.stack([self.used_cpu, self.used_mem], axis=1)}
        finally:
            (self.used_cpu, self.used_mem, self.nz_cpu, self.nz_mem, self.pods,
             self.kind_count, self._score_cache) = saved


class _KeyKind:
    """A PodKind stand-in carrying only a score-cache key's requests."""

    def __init__(self, key) -> None:
        self.cpu, self.mem, self.nz_cpu, self.nz_mem = key


def runs_of_identical_pods(units) -> List[Tuple[int, int]]:
    """(first unit, end unit) of each maximal run of consecutive units whose
    pods are identical to the scheduler (equal templates): pods in such a
    run are interchangeable, so which of them lands where is no answer."""
    out: List[Tuple[int, int]] = []
    for k, u in enumerate(units):
        if out and units[out[-1][0]].template == u.template:
            out[-1] = (out[-1][0], k + 1)
        else:
            out.append((k, k + 1))
    return out


def misplaced(cluster, program_nodes: np.ndarray,
              ref_nodes: List[np.ndarray]) -> int:
    """Pods whose node (or unschedulable verdict) the program got wrong,
    each run of interchangeable pods compared as a multiset of nodes."""
    offs = np.cumsum([0] + [u.count for u in cluster.units])
    bad = 0
    for a, b in runs_of_identical_pods(cluster.units):
        got = np.asarray(program_nodes[offs[a]:offs[b]], np.int64)
        ref = np.concatenate(ref_nodes[a:b]).astype(np.int64)
        if not np.array_equal(np.sort(got), np.sort(ref)):
            vals, cg = np.unique(got, return_counts=True)
            have = dict(zip(vals.tolist(), cg.tolist()))
            vals, cr = np.unique(ref, return_counts=True)
            bad += len(ref) - sum(min(have.get(x, 0), c)
                                  for x, c in zip(vals.tolist(), cr.tolist()))
    return bad
