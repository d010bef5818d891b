"""Open-loop what-if requests against a resident image of a live cluster:
what tools asking "would this fit now?" send.

Set-up binds every pod of the configuration to a node as the traffic's
`bind` says (`bind`), builds a ResidentImage over that state and a
WhatIfService with its defaults. Each request is "deploy a Deployment of k
replicas" of the traffic's pod template: k is drawn from the traffic's
size classes in their ratio, the Deployment from a pool of names that
set-up interns and warms, so the window leaves out on purpose the restage
of every device table that a name seen for the first time costs (PERF.md,
Open questions). Requests are never committed.

The check compares a sample of answers drawn from the seed, the largest
requests in it, with the plain reference on the same bound state: every
field of the answer, and the per-node requested cpu and memory that the
fan-out kernel returned for that request (`capture_rows`), so a replica on
the wrong node reads wrong even where the sums agree.

Arrivals are open-loop at `rate_per_s`: the gaps are the exponential
distribution's quantiles, shuffled by the seed and scaled to fill the
window exactly, so every seed sends the same sizes at the same gaps in
another order. A request is timed from its scheduled send to its answer;
one that fails or never answers counts as missing and as slower than any
answer. whatif_p95_ms is the 95th percentile over every request sent in
the window.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

GRACE_S = 60.0  # how long past the window an answer is awaited


def pool_for(cfg: dict, traffic: dict, seed: int) -> list:
    """The Deployments requests draw from: [(name, namespace)]."""
    import cluster

    rng = cluster.rng_for(seed, 6)
    n_ns = int(cfg.get("namespaces", 1))
    letters = list("abcdefghijklmnopqrstuvwxyz")
    return [(f"whatif-{j}-{''.join(rng.choice(letters, 5))}",
             cfg.get("namespace_fmt", "default").format(j % n_ns))
            for j in range(int(traffic["pool"]))]


def requests_for(traffic: dict, seed: int, seconds: float, rate: float = None):
    """[(due offset s, pool index, k)]: the same sizes at the same gaps for
    every seed, in an order the seed draws."""
    import cluster

    rng = cluster.rng_for(seed, 4)
    rate = traffic["rate_per_s"] if rate is None else rate
    n = max(1, round(rate * seconds))
    ks, ws = zip(*traffic["sizes"])
    counts = [int(w * n // sum(ws)) for w in ws]
    for j in np.argsort([-(w * n / sum(ws) - c) for w, c in zip(ws, counts)]):
        if sum(counts) >= n:
            break
        counts[j] += 1
    sizes = np.repeat(np.array(ks), counts)[rng.permutation(n)]
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)[rng.permutation(n)]
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    names = rng.integers(0, int(traffic["pool"]), n)
    return list(zip(due.tolist(), names.tolist(), sizes.tolist()))


def pool_template(cfg: dict, traffic: dict, name: str, ns: str) -> dict:
    base = cfg["templates"][traffic["template"]]
    md = dict(base.get("metadata") or {})
    md["labels"] = {**(md.get("labels") or {}), "name": name}
    md["namespace"] = ns
    return {"apiVersion": "v1", "kind": "Pod", "metadata": md,
            "spec": base["spec"]}


def request_pods(tmpl: dict, r: int, k: int) -> list:
    md = tmpl["metadata"]
    return [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": f"{md['labels']['name']}-{r}-{j}",
                          "namespace": md["namespace"],
                          "labels": md["labels"]},
             "spec": tmpl["spec"]} for j in range(k)]


def bind(c, seed: int, how: str) -> np.ndarray:
    """Node of every pod, units in order (-1: left unbound). "even": a
    seeded even spread, as a live cluster of unconstrained pods holds them;
    "scheduled": where the plain reference scheduler places them, as a
    cluster holds pods under constraints once its workload has run."""
    import cluster

    if how == "even":
        return cluster.rng_for(seed, 3).permutation(c.n_pods) % c.n_nodes
    if how == "scheduled":
        return np.concatenate(cluster.reference_for(c.config)(c).schedule_all())
    raise ValueError(f"unknown bind {how!r}")


def bound_pods(c, nodes: np.ndarray) -> list:
    out = []
    off = 0
    for u in c.units:
        md = u.template["metadata"]
        for j in range(u.count):
            if nodes[off + j] < 0:
                continue
            out.append({"apiVersion": "v1", "kind": "Pod",
                        "metadata": {"name": f"{u.name}-{j}",
                                     "namespace": md["namespace"],
                                     "labels": md.get("labels") or {}},
                        "spec": {**u.template["spec"],
                                 "nodeName": c.node_names[int(nodes[off + j])]}})
        off += u.count
    return out


def pick_checked(seed: int, reqs, candidates, n_check: int) -> list:
    """The requests compared: drawn from the seed, the largest among them."""
    import cluster

    rng = cluster.rng_for(seed, 5)
    candidates = list(candidates)
    n_check = min(len(candidates), n_check)
    biggest = sorted(candidates, key=lambda r: -reqs[r][2])[:max(1, n_check // 10)]
    rest = [r for r in rng.permutation(candidates).tolist() if r not in set(biggest)]
    return sorted(set(biggest) | set(rest[:n_check - len(biggest)]))


def reference_answers(cfg: dict, seed: int, where, reqs, tmpls, pick,
                      batch_scores: bool = False) -> list:
    """The reference's answer to each picked request, on the bound state.
    batch_scores: the control (every replica scored against the state
    before the first)."""
    import cluster

    c = cluster.generate(cfg, seed, 0)
    ref = cluster.reference_for(cfg)(c)
    off = 0
    for u in c.units:
        w = where[off:off + u.count]
        ref.bind(u.template, w[w >= 0])
        off += u.count
    return [ref.whatif(tmpls[reqs[r][1]], reqs[r][2], batch_scores) for r in pick]


FIELDS = ("scheduled", "total", "unscheduled", "utilization")


def wrong_answer(resp: dict, rows, want: dict) -> bool:
    """An answer is wrong where any field differs from the reference's, or
    where the per-node requested rows the timed path fetched (rows: [N, 2]
    cpu milli, memory bytes) differ: a replica on another node. The
    traffic's requests all route to the fan-out, so an answer without rows
    did not come from the path under test and reads wrong too."""
    if any(resp.get(f) != want[f] for f in FIELDS):
        return True
    return rows is None or not np.array_equal(
        np.asarray(rows, np.float64), want["rows"].astype(np.float64))


def capture_rows(image, n_nodes: int, keep) -> dict:
    """Wrap the image's answer assembly so that, for each request in keep,
    the per-node requested cpu and memory the fan-out kernel returned (the
    rows its answer is summed from) are kept: {request index: [N, 2]}. A
    window request's pods are named <deployment>-<r>-<j>."""
    from open_simulator_tpu.ops.resources import CPU_I, MEM_I

    keep = set(keep)
    rows = {}
    orig = image._responses

    def responses(sessions, totals, placed_s, requested_s, active_s, lanes):
        for li, s in enumerate(sessions):
            r = int(s.pods[0]["metadata"]["name"].rsplit("-", 2)[1])
            if r in keep:
                rows[r] = np.array(requested_s[li][:n_nodes][:, [CPU_I, MEM_I]])
        return orig(sessions, totals, placed_s, requested_s, active_s, lanes)

    image._responses = responses
    return rows


def setup(cfg: dict, traffic: dict, seed: int) -> dict:
    """Bound cluster, resident image, service, pool templates; every shape
    the window can form warmed."""
    import cluster
    from open_simulator_tpu.serve import ResidentImage, WhatIfService

    c = cluster.generate(cfg, seed, 0)
    where = bind(c, seed, traffic["bind"])
    ns, _ = cluster.program_inputs(c)
    image = ResidentImage.try_build(ns, pods=bound_pods(c, where))
    if image is None:
        raise RuntimeError("ResidentImage declined the cluster")
    svc = WhatIfService(image)
    tmpls = [pool_template(cfg, traffic, nm, nsp)
             for nm, nsp in pool_for(cfg, traffic, seed)]
    # intern every pool Deployment (one restage), then every lane count x
    # size class shape the service can form, then the service path
    sessions = [image.session(request_pods(t, -1, 1)) for t in tmpls]
    image.dispatch_sessions(sessions[:1])
    sizes = sorted({k for k, _ in traffic["sizes"]})
    lanes = 1
    while lanes <= svc.fanout:
        for k in sizes:
            image.dispatch_sessions([image.session(request_pods(tmpls[j], -1, k))
                                     for j in range(lanes)])
        lanes *= 2
    for j, k in enumerate(sizes * 4):
        svc.submit(request_pods(tmpls[j % len(tmpls)], -2, k))
    return {"image": image, "svc": svc, "where": where, "tmpls": tmpls,
            "n_nodes": c.n_nodes}


def open_loop(svc, reqs, make_pods, clients: int, start, keep=()) -> dict:
    """Send each request at its due time whatever came back; wait for every
    answer up to GRACE_S past the last send. start() marks the window's
    start and returns its perf_counter time. The clients share the
    server's process, so they hold as little as a client in a process of
    its own would: a request's pods are built when it is sent and dropped
    after, and only the answers of the requests in `keep` are kept."""
    n = len(reqs)
    keep = set(keep)
    done_at = [None] * n
    answers = {}
    late = [0.0] * n
    pending = threading.Semaphore(0)

    def work(r: int) -> None:
        try:
            resp = svc.submit(make_pods(r))
            done_at[r] = time.perf_counter()
            if r in keep:
                answers[r] = resp
        except Exception as e:  # a failed request counts as missing
            answers[r] = e
        finally:
            pending.release()

    ex = ThreadPoolExecutor(max_workers=clients)
    try:
        t0 = start()
        for r, (due, _, _) in enumerate(reqs):
            wait = t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[r] = time.perf_counter() - (t0 + due)
            ex.submit(work, r)
        t_last = t0 + reqs[-1][0]
        got = 0
        while got < n and pending.acquire(
                timeout=max(0.0, t_last + GRACE_S - time.perf_counter())):
            got += 1
        t_close = time.perf_counter()
    finally:
        ex.shutdown(wait=False, cancel_futures=True)
    lat_ms = [((done_at[r] if done_at[r] is not None else t_close) - (t0 + due)) * 1e3
              for r, (due, _, _) in enumerate(reqs)]
    return {"answers": answers, "lat_ms": lat_ms, "late_s": late,
            "missing": sum(d is None for d in done_at),
            "drain_s": t_close - t_last, "executor": ex}


def stop(svc, ex) -> None:
    svc.stop()
    ex.shutdown(wait=True, cancel_futures=True)
    for t in threading.enumerate():  # the service's dispatcher, stopped above
        if t.name == "simon-serve-dispatch":
            t.join(GRACE_S)


def run(ctx) -> dict:
    import jax

    import cluster
    import common

    tr = ctx.traffic
    cfg = cluster.load_config(ctx.config_name)
    st = setup(cfg, tr, ctx.seed)
    image, svc = st["image"], st["svc"]
    seconds = min(ctx.seconds, float(tr["trace_seconds"])) if ctx.trace else ctx.seconds
    reqs = requests_for(tr, ctx.seed, seconds)
    tmpls = st["tmpls"]
    pick = pick_checked(ctx.seed, reqs, range(len(reqs)), int(tr["check"]))

    rows = capture_rows(image, st["n_nodes"], pick)
    spans = []  # (lanes, seconds) per dispatch_sessions call, --trace 1
    if ctx.trace:
        orig = image.dispatch_sessions

        def timed(sess):
            t = time.perf_counter()
            out = orig(sess)
            spans.append((len(sess), time.perf_counter() - t))
            return out

        image.dispatch_sessions = timed

    with ctx.profiled() as prof, jax.profiler.TraceAnnotation("bench.window"):
        res = open_loop(svc, reqs,
                        lambda r: request_pods(tmpls[reqs[r][1]], r, reqs[r][2]),
                        int(tr["clients"]), ctx.start_window, keep=pick)
    ctx.end_window()
    del image._responses  # the capture's wrapper
    stop(svc, res["executor"])
    peak = ctx.memory_peak()
    lat_ms, answers, missing = res["lat_ms"], res["answers"], res["missing"]
    p95 = common.percentile(lat_ms, 95)

    # the check: the sample drawn from the seed before the window, the
    # largest requests in it; a missing answer is counted apart
    del image, svc, st["image"], st["svc"]
    ok = [r for r in pick if isinstance(answers.get(r), dict)]
    want = reference_answers(cfg, ctx.seed, st["where"], reqs, tmpls, ok)
    wrong = sum(wrong_answer(answers[r], rows.get(r), w)
                for r, w in zip(ok, want))
    ctx.notes.append(
        f"requests {len(reqs)} rate_per_s {tr['rate_per_s']} window_s {seconds} "
        f"p50_ms {common.percentile(lat_ms, 50):.6f} p95_ms {p95:.6f} "
        f"p99_ms {common.percentile(lat_ms, 99):.6f} max_ms {max(lat_ms):.6f} "
        f"generator_late_p95_ms {common.percentile(res['late_s'], 95) * 1e3:.6f} "
        f"generator_late_max_ms {max(res['late_s']) * 1e3:.6f} "
        f"drain_after_last_send_s {res['drain_s']:.6f} missing {missing} "
        f"window_compiles {ctx.window_compiles} setup_s {ctx.setup_s:.6f} "
        f"checked {len(ok)}")
    return {
        "correct": wrong == 0 and missing == 0,
        "attempted": len(reqs),
        "failed": missing,
        "e2e": {"whatif_p95_ms": p95, "setup_s": ctx.setup_s},
        "layer": {"trace": prof.get("reduced"), "serve_spans": spans},
        "memory_peak_bytes": peak,
        "checks": [("wrong_answers", wrong, 0), ("missing_answers", missing, 0)],
    }
