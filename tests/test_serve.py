"""simonserve: resident what-if serving.

The contract under test (README "Serving", PARITY.md "Resident-vs-fresh"):

- **Resident-vs-fresh parity.** Every response served off the persistent
  device-resident cluster image — through delta ingest, copy-on-write drain
  overlays, and micro-batched dispatch — is bit-identical to probing the same
  request serially on a fresh Simulator built from scratch over the final
  cluster state (counts AND f64 utilization sums).
- **Micro-batching determinism.** Lane padding and union-batch padding never
  change a placement: each lane's per-request valid mask makes foreign rows
  provable no-ops.
- **Epoch safety.** A from-scratch image rebuild (generation bump) makes
  existing sessions stale — detected and re-encoded, never silently wrong.
- **Non-donation.** The shared image's device buffers survive every dispatch
  (the runtime half of the simonaudit image_leaf_aliased certificate).
"""

import json
import threading

import numpy as np
import pytest

from open_simulator_tpu.core.types import ResourceTypes
from open_simulator_tpu.serve import (
    ImageDonatedError,
    ResidentImage,
    StaleImageError,
    WhatIfService,
)
from open_simulator_tpu.server.http import ClusterSnapshot, Server

from fixtures import make_node, make_pod


def make_cluster(n_nodes=12, n_bound=6):
    nodes = [make_node(f"n-{i}", cpu="8", memory="16Gi") for i in range(n_nodes)]
    bound = [make_pod(f"bound-{i}", cpu="2", memory="2Gi",
                      node_name=f"n-{i % max(1, n_nodes // 3)}",
                      labels={"app": f"svc-{i % 2}"})
             for i in range(n_bound)]
    return nodes, bound


def whatif_pods(tag, n=4, cpu="1", memory="1Gi", anti_on=None):
    affinity = None
    if anti_on:
        affinity = {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "labelSelector": {"matchLabels": {"app": anti_on}},
                "topologyKey": "kubernetes.io/hostname",
            }]}}
    return [make_pod(f"wi-{tag}-{i}", cpu=cpu, memory=memory,
                     labels={"app": f"wi-{tag}"}, affinity=affinity)
            for i in range(n)]


def assert_same_response(resident: dict, fresh: dict) -> None:
    assert resident["scheduled"] == fresh["scheduled"], (resident, fresh)
    assert resident["total"] == fresh["total"]
    assert resident["unscheduled"] == fresh["unscheduled"]
    assert resident["utilization"] == fresh["utilization"], (
        resident["utilization"], fresh["utilization"])


# ----------------------------------------------------------- basic parity ----


def test_resident_matches_fresh_encode():
    nodes, bound = make_cluster()
    img = ResidentImage.try_build(nodes, pods=bound)
    assert img is not None
    req = whatif_pods("a", 5)
    assert_same_response(img.session(req).run(), img.fresh_probe(req))


def test_request_drain_overlay_parity():
    """Per-request drains overlay the shared image copy-on-write: the lane
    sees the cluster without the drained node AND without its pods —
    including their inter-pod-affinity counter contributions (the adjusted
    seed copy), which the anti-affinity request here reads."""
    nodes, bound = make_cluster(10, 8)
    img = ResidentImage.try_build(nodes, pods=bound)
    req = whatif_pods("anti", 6, anti_on="svc-0")
    for drains in ([], ["n-0"], ["n-0", "n-1"]):
        got = img.session(req, drains=drains).run()
        want = img.fresh_probe(req, drains=drains)
        assert_same_response(got, want)
    # the image itself is untouched by request overlays
    assert img.n_nodes == 10 and not img.drained


def test_overlarge_cluster_saturates_identically():
    nodes, _ = make_cluster(6, 0)
    img = ResidentImage.try_build(nodes)
    req = whatif_pods("big", 9, cpu="6", memory="12Gi")  # only 6 fit
    got = img.session(req).run()
    assert got["scheduled"] == 6 and got["unscheduled"] == 3
    assert_same_response(got, img.fresh_probe(req))


# ------------------------------------------------------------ delta ingest ----


def _trace_events(rng, nodes, live_counter):
    """One seeded event batch: pod churn + node drain + node add."""
    evs = []
    kind = rng.integers(0, 4)
    if kind == 0:  # pod adds onto random live nodes
        for j in range(int(rng.integers(1, 4))):
            i = int(rng.integers(0, len(nodes)))
            live_counter[0] += 1
            evs.append({"type": "pod_add", "pod": make_pod(
                f"churn-{live_counter[0]}", cpu="1", memory="1Gi",
                node_name=f"n-{i}", labels={"app": "churn"})})
    elif kind == 1:  # delete previously churned pods
        for j in range(int(rng.integers(1, 3))):
            if live_counter[0] > live_counter[1]:
                live_counter[1] += 1
                evs.append({"type": "pod_delete", "namespace": "default",
                            "name": f"churn-{live_counter[1]}"})
    elif kind == 2:  # drain a random node (it and its pods leave)
        evs.append({"type": "node_drain",
                    "name": f"n-{int(rng.integers(0, len(nodes)))}"})
    else:  # add a fresh node
        live_counter[2] += 1
        evs.append({"type": "node_add",
                    "node": make_node(f"added-{live_counter[2]}",
                                      cpu="16", memory="32Gi")})
    return evs


@pytest.mark.parametrize("seed", [7, 23, 101])
def test_delta_ingest_trace_matches_from_scratch(seed):
    """Property-style (ISSUE satellite): a seeded sequence of node add /
    node drain / pod churn event batches applied to the resident image must
    produce what-if answers bit-identical to (a) a fresh Simulator probe of
    the final cluster state and (b) a BRAND-NEW ResidentImage built from
    scratch over that final state."""
    rng = np.random.default_rng(seed)
    nodes, bound = make_cluster(8, 5)
    img = ResidentImage.try_build(nodes, pods=[dict(p) for p in bound])
    live_counter = [0, 0, 0]  # churn adds, churn deletes, node adds
    req = whatif_pods("trace", 5, anti_on="churn")
    for step in range(4):
        evs = _trace_events(rng, nodes, live_counter)
        img.apply_events(evs)
        got = img.session(req).run()
        assert_same_response(got, img.fresh_probe(req))
    # from-scratch image over the final state answers identically
    final_nodes = img.current_nodes()
    final_bound = img.cluster_pods()
    img2 = ResidentImage.try_build(final_nodes, pods=final_bound)
    assert img2 is not None
    assert_same_response(img.session(req).run(), img2.session(req).run())


def test_pod_churn_refreshes_seeds_without_restage():
    """Pod add/delete must move ZERO device table bytes: the staged tables
    are placed-independent, only the host-side seeds re-aggregate."""
    nodes, bound = make_cluster()
    img = ResidentImage.try_build(nodes, pods=bound)
    staged_before = img._tables
    out = img.apply_events([
        {"type": "pod_add", "pod": make_pod("c-1", cpu="1", memory="1Gi",
                                            node_name="n-2")},
        {"type": "pod_delete", "namespace": "default", "name": "bound-0"},
    ])
    assert out["applied"] == 2 and not out["restaged"]
    assert img._tables is staged_before  # same device buffers, untouched
    req = whatif_pods("churn", 4)
    assert_same_response(img.session(req).run(), img.fresh_probe(req))


def test_node_drain_moves_no_bytes_and_add_restages():
    nodes, bound = make_cluster()
    img = ResidentImage.try_build(nodes, pods=bound)
    staged = img._tables
    out = img.apply_events([{"type": "node_drain", "name": "n-3"}])
    assert out["applied"] == 1 and not out["restaged"]
    assert img._tables is staged and img.n_nodes == 11
    out = img.apply_events([
        {"type": "node_add", "node": make_node("n-new", cpu="4", memory="8Gi")}])
    assert out["restaged"] and img.n_nodes == 12
    req = whatif_pods("nodes", 6, cpu="3", memory="6Gi")
    assert_same_response(img.session(req).run(), img.fresh_probe(req))


def test_intra_batch_event_ordering():
    """Events inside ONE ingest batch must see each other: the natural
    watch-stream order [node_add X, pod_add onto X] commits the pod (the
    live mask extends mid-batch), and draining a just-added node sticks."""
    nodes, bound = make_cluster(6, 3)
    img = ResidentImage.try_build(nodes, pods=bound)
    out = img.apply_events([
        {"type": "node_add", "node": make_node("nx", cpu="16", memory="32Gi")},
        {"type": "pod_add", "pod": make_pod("on-nx", cpu="4", memory="4Gi",
                                            node_name="nx")},
    ])
    assert out["applied"] == 2 and out["skipped"] == 0
    req = whatif_pods("order", 4)
    assert_same_response(img.session(req).run(), img.fresh_probe(req))
    out = img.apply_events([
        {"type": "node_add", "node": make_node("ny", cpu="16", memory="32Gi")},
        {"type": "node_drain", "name": "ny"},
    ])
    assert out["applied"] == 2 and "ny" in img.drained
    assert_same_response(img.session(req).run(), img.fresh_probe(req))


def test_unexpressible_event_rebuilds_not_approximates():
    """A node-add the delta path cannot express (new resource axis) forces a
    from-scratch re-encode with a generation bump — never a wrong answer."""
    nodes, bound = make_cluster(8, 4)
    img = ResidentImage.try_build(nodes, pods=bound)
    gen = img.generation
    sess = img.session(whatif_pods("stale", 3))
    img.apply_events([{"type": "node_add", "node": make_node(
        "gpu-node", cpu="8", memory="16Gi",
        extra_resources={"example.com/widget": "4"})}])
    assert img.generation == gen + 1
    with pytest.raises(StaleImageError):
        sess.run()
    sess.ensure_current()  # the service's transparent path
    assert_same_response(sess.run(), img.fresh_probe(sess.pods))


# ----------------------------------------------------------- micro-batching ----


def test_micro_batch_demux_and_parity():
    """Concurrent heterogeneous requests coalesce onto one fan-out dispatch;
    every demuxed response equals the serial fresh-encode probe."""
    nodes, bound = make_cluster(10, 6)
    img = ResidentImage.try_build(nodes, pods=bound)
    svc = WhatIfService(img, window_ms=20.0, fanout=8)
    shapes = [whatif_pods("m0", 3), whatif_pods("m1", 5, cpu="2"),
              whatif_pods("m2", 2, anti_on="svc-1"),
              whatif_pods("m3", 4, memory="2Gi")]
    results = [None] * len(shapes)

    def go(i):
        results[i] = svc.submit(shapes[i])

    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(shapes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is not None for r in results)
    assert max(r["lanes"] for r in results) > 1  # actually coalesced
    for i, r in enumerate(results):
        assert r["path"] == "batched"
        assert_same_response(r, img.fresh_probe(shapes[i]))
    svc.stop()


def test_ineligible_requests_route_fresh():
    nodes, bound = make_cluster(8, 3)
    img = ResidentImage.try_build(nodes, pods=bound)
    svc = WhatIfService(img, window_ms=0.0)
    spread = make_pod("spread-1", cpu="1", memory="1Gi",
                      labels={"app": "sp"})
    spread["spec"]["topologySpreadConstraints"] = [{
        "maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
        "whenUnsatisfiable": "DoNotSchedule",
        "labelSelector": {"matchLabels": {"app": "sp"}}}]
    r = svc.submit([spread])
    assert r["path"] == "fresh" and r["total"] == 1
    prebound = make_pod("pre-1", cpu="1", memory="1Gi", node_name="n-0")
    assert svc.submit([prebound])["path"] == "fresh"
    svc.stop()


def test_gated_request_signs_each_pod_once(monkeypatch):
    """A request gated to the fresh path probes copies that carry the
    session's signatures: the conversions sign each request pod once (a
    new group's template is signed where the encoder interns it)."""
    import sys

    from open_simulator_tpu.simulator import encode

    nodes, bound = make_cluster(8, 3)
    img = ResidentImage.try_build(nodes, pods=bound)
    svc = WhatIfService(img, window_ms=0.0)
    pods = whatif_pods("gate", 4)
    pods[0]["spec"]["nodeName"] = "n-1"
    names = sorted(p["metadata"]["name"] for p in pods)
    signed = []
    real = encode.scheduling_signature

    def spy(pod):
        if sys._getframe(1).f_code.co_name == "of":  # PodStore.of
            signed.append(pod["metadata"]["name"])
        return real(pod)

    monkeypatch.setattr(encode, "scheduling_signature", spy)
    try:
        r = svc.submit(pods)
    finally:
        svc.stop()
    assert r["path"] == "fresh" and r["total"] == 4
    assert sorted(n for n in signed if n.startswith("wi-gate")) == names
    monkeypatch.setattr(encode, "scheduling_signature", real)
    assert_same_response(r, img.fresh_probe(whatif_pods("gate", 4)[1:] + [
        dict(pods[0])]))


# ------------------------------------------------------------- non-donation ----


def test_image_buffers_survive_dispatches():
    nodes, bound = make_cluster()
    img = ResidentImage.try_build(nodes, pods=bound)
    for _ in range(3):
        img.session(whatif_pods("alive", 3)).run()
    img.assert_image_alive()  # also runs inside every dispatch


def test_assert_image_alive_catches_donation():
    """Negative control: a (forbidden) donating jit over the image tables
    consumes the buffers; the runtime assertion must catch it."""
    import jax

    nodes, _ = make_cluster(8, 0)
    img = ResidentImage.try_build(nodes)
    eat = jax.jit(lambda a: a + 1.0, donate_argnums=(0,))
    eat(img._tables.alloc)  # output aliases the donated buffer -> deleted
    with pytest.raises(ImageDonatedError):
        img.assert_image_alive()


def test_image_alias_census_flags_donating_jit():
    """Compile-time half (simonaudit): args_info-based census counts donated
    leaves inside the tables range — 0 for every registered kernel (asserted
    by the goldens), nonzero for a deliberately donating jit."""
    import jax
    import jax.numpy as jnp

    from open_simulator_tpu.analysis.hlo import image_alias_count

    args = (jnp.zeros((4, 4)), jnp.zeros((4,)))
    good = jax.jit(lambda t, c: (t * 1.0, c + 1.0), donate_argnums=(1,))
    bad = jax.jit(lambda t, c: (t * 1.0, c + 1.0), donate_argnums=(0, 1))
    assert image_alias_count(good.lower(*args), 1) == 0
    assert image_alias_count(bad.lower(*args), 1) == 1


def test_serve_goldens_pin_zero_image_alias():
    from pathlib import Path

    doc = json.loads((Path(__file__).parent / "golden" / "audit" /
                      "serve_whatif_fanout.json").read_text())
    assert doc["certs"], "serve kernel has no golden certificates"
    for key, cert in doc["certs"].items():
        assert cert["donation"]["image_leaf_aliased"] == 0, key
        assert cert["donation"]["held"], key


# ------------------------------------------------------------ HTTP serving ----


def _serve_server(n_nodes=10, n_bound=4, window_ms=20.0, fanout=8):
    nodes, bound = make_cluster(n_nodes, n_bound)
    rt = ResourceTypes(nodes=nodes, pods=bound)
    snap = ClusterSnapshot(rt, [], [], [])
    return Server(snapshot_fn=lambda: snap, whatif=True,
                  whatif_window_ms=window_ms, whatif_fanout=fanout)


def test_http_whatif_smoke_16_concurrent():
    """The CI smoke (ISSUE satellite): spin the server in-process, fire 16
    concurrent /v1/whatif requests through the REAL HTTP stack, assert every
    response demuxes to its own request and matches the serial fresh-encode
    probe; then ingest a drain delta and confirm the image moved."""
    import http.client

    server = _serve_server()
    httpd = server.build_httpd(port=0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        results = [None] * 16

        def call(i):
            # generous timeout: the first requests pay the cold XLA compile
            # of the fan-out shape bucket
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
            body = json.dumps({"pods": [
                {"metadata": {"name": f"h{i}-{j}", "namespace": "default",
                              "labels": {"app": f"h{i}"}},
                 "spec": {"containers": [{"name": "c", "image": "nginx",
                                          "resources": {"requests": {
                                              "cpu": "1",
                                              "memory": "1Gi"}}}]}}
                for j in range(1 + i % 3)]})
            try:
                conn.request("POST", "/v1/whatif", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                results[i] = (resp.status, json.loads(resp.read()))
            except Exception as e:  # surfaced by the assertion below
                results[i] = (None, {"error": repr(e)})
            finally:
                conn.close()

        threads = [threading.Thread(target=call, args=(i,)) for i in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        svc = server.whatif_service()
        for i, (status, body) in enumerate(results):
            assert status == 200, body
            assert body["total"] == 1 + i % 3  # demuxed to the right request
            assert body["scheduled"] == body["total"]
            want = svc.image.fresh_probe([make_pod(
                f"h{i}-{j}", cpu="1", memory="1Gi", labels={"app": f"h{i}"})
                for j in range(1 + i % 3)])
            assert_same_response(body, want)
        assert any(body["lanes"] > 1 for _, body in results)  # coalesced

        # delta ingest over HTTP: drain one node, the image epoch moves
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/v1/ingest", json.dumps(
            {"events": [{"type": "node_drain", "name": "n-9"}]}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = json.loads(resp.read())
        assert resp.status == 200 and out["applied"] == 1
        conn.request("GET", "/v1/serve/stats", None, {})
        stats = json.loads(conn.getresponse().read())
        assert stats["nodes"] == 9 and stats["drained"] == ["n-9"]
        conn.close()
    finally:
        httpd.shutdown()


def test_whatif_off_by_default_404():
    nodes, _ = make_cluster(4, 0)
    snap = ClusterSnapshot(ResourceTypes(nodes=nodes), [], [], [])
    server = Server(snapshot_fn=lambda: snap, whatif=False)
    code, body = server.handle_whatif({"pods": [make_pod("x")]})
    assert code == 404 and "error" in body


def test_whatif_declined_cluster_501():
    # node-advertised images decline the resident image (ImageLocality)
    nodes, _ = make_cluster(4, 0)
    nodes[0]["status"]["images"] = [{"names": ["nginx:1.25"],
                                    "sizeBytes": 1 << 20}]
    snap = ClusterSnapshot(ResourceTypes(nodes=nodes), [], [], [])
    server = Server(snapshot_fn=lambda: snap, whatif=True)
    code, body = server.handle_whatif({"pods": [make_pod("x")]})
    assert code == 501


def test_whatif_empty_request_400():
    server = _serve_server(4, 0)
    code, body = server.handle_whatif({})
    assert code == 400


def test_grpc_whatif_rpc_roundtrip():
    from open_simulator_tpu.server.grpcbridge import (
        GrpcBridge,
        decode_simulate_response,
        encode_simulate_request,
    )

    bridge = GrpcBridge(server=_serve_server(6, 2))
    req = json.dumps({"pods": [make_pod("g-1", cpu="1", memory="1Gi")]}).encode()
    code, payload = decode_simulate_response(
        bridge._whatif(encode_simulate_request(req), None))
    assert code == 200
    body = json.loads(payload)
    assert body["total"] == 1 and body["path"] in ("batched", "fresh")


def test_cli_serve_parser():
    from open_simulator_tpu.cli.main import build_parser

    args = build_parser().parse_args(
        ["serve", "--synthetic-nodes", "8", "--window-ms", "1",
         "--fanout", "4", "--port", "0"])
    assert args.command == "serve" and args.synthetic_nodes == 8
    assert args.window_ms == 1.0 and args.fanout == 4


# ------------------------------------------------------- simonha over HTTP ----


def _ha_server(state_dir, n_nodes=8, n_bound=3, **kw):
    nodes, bound = make_cluster(n_nodes, n_bound)
    rt = ResourceTypes(nodes=nodes, pods=bound)
    snap = ClusterSnapshot(rt, [], [], [])
    return Server(snapshot_fn=lambda: snap, whatif=True,
                  whatif_window_ms=0.0, state_dir=str(state_dir), **kw)


def test_http_state_dir_stamps_epoch_and_staleness(tmp_path):
    server = _ha_server(tmp_path)
    code, body = server.handle_ingest(
        {"events": [{"type": "node_drain", "name": "n-7"}]})
    assert code == 200 and body["applied"] == 1
    code, body = server.handle_whatif({"pods": [make_pod("s-1", cpu="1",
                                                         memory="1Gi")]})
    assert code == 200
    assert body["staleness_s"] == 0.0  # healthy: stamped, not stale
    assert body["epoch"] == server._ha.image.epoch
    server.drain(deadline=0.1)


def test_http_restart_from_state_dir_bit_identical(tmp_path):
    """The serve-level restart oracle: kill server A (drain = the graceful
    half; test_ha covers SIGKILL semantics on the raw files), boot server B
    over the same --state-dir, require the same epoch and the same answers."""
    req = {"pods": [make_pod(f"rs-{j}", cpu="1", memory="1Gi")
                    for j in range(3)]}
    a = _ha_server(tmp_path, checkpoint_every=2)
    for i in range(3):
        code, _ = a.handle_ingest({"events": [{
            "type": "pod_add", "pod": make_pod(
                f"live-{i}", cpu="1", memory="1Gi",
                node_name=f"n-{i}")}]})
        assert code == 200
    code, want = a.handle_whatif(dict(req))
    assert code == 200
    epoch = a._ha.image.epoch
    a.drain(deadline=0.1)

    b = _ha_server(tmp_path, checkpoint_every=2)
    code, got = b.handle_whatif(dict(req))
    assert code == 200
    assert b._ha.image.epoch == epoch
    assert b._ha.skipped + b._ha.replayed >= 1  # restored, not rebuilt
    assert_same_response(got, want)
    assert got["epoch"] == want["epoch"]
    b.drain(deadline=0.1)


def test_http_healthz_flips_503_past_staleness_ceiling(tmp_path):
    import http.client

    server = _ha_server(tmp_path, staleness_ceiling_s=0.0)
    server.whatif_service()  # boot the HA state
    httpd = server.build_httpd(port=0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/healthz")
        assert conn.getresponse().read() and True
        server._ha._enter_degraded("ingest")
        server._ha._last_ok -= 1.0  # degraded for a solid second
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 503 and body["reason"] == "ingest"
        assert body["staleness_s"] > 0
        # recovery via successful ingest: healthz flips back
        server._ha.ingest([{"type": "node_drain", "name": "n-6"}])
        conn.request("GET", "/healthz")
        assert conn.getresponse().status == 200
        conn.close()
    finally:
        httpd.shutdown()
        server.drain(deadline=0.1)


def test_http_ingest_payload_caps(tmp_path):
    """Satellite: the unbounded-memory hazard is closed BEFORE the body is
    read — oversized payload 413, in-flight byte budget 429, both
    structured and counted."""
    import http.client

    server = _ha_server(tmp_path, ingest_max_bytes=1024)
    httpd = server.build_httpd(port=0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        big = json.dumps({"events": [{"type": "node_drain",
                                      "name": "x" * 2048}]})
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("POST", "/v1/ingest", big,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 413 and body["code"] == 413
        conn.close()  # the server dropped the connection with the body unread

        # in-flight budget: pre-load the accounting to the 4x cap
        server._ingest_bytes = 4 * 1024
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("POST", "/v1/ingest", json.dumps({"events": []}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 429
        assert resp.getheader("Retry-After") is not None
        body = json.loads(resp.read())
        assert body["code"] == 429
        conn.close()
        server._ingest_bytes = 0
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("POST", "/v1/ingest", json.dumps(
            {"events": [{"type": "node_drain", "name": "n-5"}]}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200  # the budget released: normal service
        assert resp.getheader("X-Simon-Epoch") == server._ha.image.epoch
        conn.close()
    finally:
        httpd.shutdown()
        server.drain(deadline=0.1)


def test_http_whatif_shed_maps_to_429_with_retry_after(tmp_path):
    import http.client

    server = _ha_server(tmp_path, max_queue=8, tenant_rate=0.001)
    httpd = server.build_httpd(port=0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        body = json.dumps({"pods": [make_pod("sh-1", cpu="1",
                                             memory="1Gi")]})
        codes = []
        for _ in range(10):  # burst past the 8-token burst at ~0 rps
            conn.request("POST", "/v1/whatif", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = json.loads(resp.read())
            codes.append(resp.status)
            if resp.status == 429:
                assert out["reason"] == "rate_limit"
                assert out["retry_after_s"] > 0
                assert resp.getheader("Retry-After") is not None
        assert codes.count(200) == 8 and codes.count(429) == 2
        conn.close()
    finally:
        httpd.shutdown()
        server.drain(deadline=0.1)
