"""Native canon_hash extension + signature memoization."""

import os

import numpy as np
import pytest

from open_simulator_tpu.native import canon_hash_fn

from fixtures import make_deployment, make_node
from open_simulator_tpu import simulate
from open_simulator_tpu.core.types import AppResource, ResourceTypes
from open_simulator_tpu.models.workloads import pods_from_deployment
from open_simulator_tpu.simulator.encode import SIG_MEMO_KEY, scheduling_signature


@pytest.fixture(scope="module")
def canon_hash():
    fn = canon_hash_fn()
    if fn is None:
        pytest.skip("native extension unavailable (no compiler?)")
    return fn


def test_native_builds_and_hashes(canon_hash):
    h = canon_hash({"a": 1, "b": [1, 2, {"c": "x"}]})
    assert isinstance(h, int) and h > 0


def test_dict_key_order_canonical(canon_hash):
    assert canon_hash({"a": 1, "b": 2}) == canon_hash({"b": 2, "a": 1})


def test_distinct_values_distinct_hashes(canon_hash):
    samples = [
        {"a": 1}, {"a": 2}, {"a": "1"}, {"a": [1]}, {"a": {"b": 1}},
        {"a": None}, {"a": 1.5}, {"b": 1}, [1, 2], [2, 1], "x", 7, None, True, False,
    ]
    hashes = [canon_hash(s) for s in samples]
    # bool True == 1 in Python tuple equality → allowed to collide with 7? no: 7 != True
    assert len(set(hashes)) == len(samples)


def test_numeric_equality_matches_python_tuples(canon_hash):
    # (1,) == (1.0,) == (True,) in Python → the frozen-tuple form collides; the
    # native hash must too, or equal groups would split forever
    assert canon_hash(1) == canon_hash(1.0) == canon_hash(True)
    assert canon_hash(0) == canon_hash(0.0) == canon_hash(False)
    big = 2**70
    assert canon_hash(big) == canon_hash(big)
    assert canon_hash(big) != canon_hash(big + 1)


def test_nested_list_vs_flat(canon_hash):
    assert canon_hash([1, [2, 3]]) != canon_hash([1, 2, 3])
    assert canon_hash([]) != canon_hash({})


def test_unsupported_type_raises(canon_hash):
    with pytest.raises(TypeError):
        canon_hash(object())


# ------------------------------------------------------------------ pod_sig ---------


ANNO_KEYS = ("simon/gpu-mem", "simon/gpu-count", "simon/gpu-index",
             "simon/local-storage")


def _sig_tuple(pod):
    """The exact tuple scheduling_signature's native path used to build in Python
    (simulator/encode.py) — pod_sig must be hash-identical to canon_hash over it."""
    md = pod.get("metadata") or {}
    spec = pod.get("spec") or {}
    anns = md.get("annotations") or {}
    return (
        md.get("namespace") or "default",
        md.get("labels"),
        spec.get("nodeSelector"),
        spec.get("affinity"),
        spec.get("tolerations"),
        spec.get("topologySpreadConstraints"),
        spec.get("nodeName"),
        spec.get("hostNetwork"),
        spec.get("containers"),
        spec.get("initContainers"),
        spec.get("overhead"),
        sorted({r.get("kind", "") for r in md.get("ownerReferences") or []}),
        [anns.get(k) for k in ANNO_KEYS],
    )


@pytest.fixture(scope="module")
def pod_sig():
    from open_simulator_tpu.native import pod_sig_fn

    fn = pod_sig_fn()
    if fn is None:
        pytest.skip("native extension unavailable (no compiler?)")
    return fn


def test_pod_sig_matches_tuple_hash(canon_hash, pod_sig):
    pods = [
        {},
        {"metadata": {"name": "a"}},
        {"metadata": {"namespace": "", "labels": {"a": "b", "c": "d"}}},
        {"metadata": {"namespace": "x", "ownerReferences": [
            {"kind": "ReplicaSet"}, {"kind": "Job"}, {"kind": "ReplicaSet"}]}},
        {"metadata": {"annotations": {"simon/gpu-mem": "4Gi", "other": "1"}},
         "spec": {"containers": [{"image": "nginx",
                                  "resources": {"requests": {"cpu": "100m"}}}],
                  "hostNetwork": True, "nodeName": "n1",
                  "tolerations": [{"key": "k", "operator": "Exists"}]}},
        {"spec": {"affinity": {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {"labelSelector": {"matchLabels": {"app": "x"}},
                 "topologyKey": "kubernetes.io/hostname"}]}},
            "topologySpreadConstraints": [
                {"maxSkew": 2, "whenUnsatisfiable": "DoNotSchedule"}]}},
        {"metadata": None, "spec": None},
        {"metadata": {"ownerReferences": []},
         "spec": {"overhead": {"cpu": "10m"}, "initContainers": []}},
    ]
    for pod in pods:
        assert pod_sig(pod, ANNO_KEYS) == canon_hash(_sig_tuple(pod))


def test_pod_sig_distinguishes_scheduling_fields(pod_sig):
    base = {"metadata": {"namespace": "d", "labels": {"app": "x"}},
            "spec": {"containers": [{"image": "a",
                                     "resources": {"requests": {"cpu": "1"}}}]}}
    import copy

    variants = []
    for mutate in (
        lambda p: p["metadata"].__setitem__("namespace", "other"),
        lambda p: p["metadata"]["labels"].__setitem__("app", "y"),
        lambda p: p["spec"].__setitem__("nodeSelector", {"k": "v"}),
        lambda p: p["spec"].__setitem__("nodeName", "n7"),
        lambda p: p["spec"]["containers"][0].__setitem__("image", "b"),
        lambda p: p["spec"]["containers"][0]["resources"]["requests"].__setitem__("cpu", "2"),
        lambda p: p["metadata"].setdefault("annotations", {}).__setitem__(
            "simon/gpu-mem", "1Gi"),
        lambda p: p["metadata"].__setitem__("ownerReferences", [{"kind": "DaemonSet"}]),
    ):
        p = copy.deepcopy(base)
        mutate(p)
        variants.append(pod_sig(p, ANNO_KEYS))
    variants.append(pod_sig(base, ANNO_KEYS))
    assert len(set(variants)) == len(variants)
    # name/uid are NOT scheduling-relevant: same signature
    named = copy.deepcopy(base)
    named["metadata"]["name"] = "pod-123"
    assert pod_sig(named, ANNO_KEYS) == pod_sig(base, ANNO_KEYS)


# ------------------------------------------------------------------ memoization -----


def test_workload_pods_share_memo():
    deploy = make_deployment("web", replicas=5, cpu="1", memory="1Gi")
    pods = pods_from_deployment(deploy)
    sigs = {scheduling_signature(p) for p in pods}
    assert len(sigs) == 1
    assert all(SIG_MEMO_KEY in p for p in pods)


def test_memo_stripped_from_results():
    nodes = [make_node("n1")]
    deploy = make_deployment("web", replicas=3, cpu="1", memory="1Gi")
    res = simulate(ResourceTypes(nodes=nodes),
                   [AppResource(name="a", resource=ResourceTypes(deployments=[deploy]))])
    for ns in res.node_status:
        for p in ns.pods:
            assert SIG_MEMO_KEY not in p
    for up in res.unscheduled_pods:
        assert SIG_MEMO_KEY not in up.pod


# ------------------------------------------------------------------ class_sigs ------


def _class_pod(md, spec=None):
    pod = {"metadata": md,
           "spec": spec if spec is not None else {
               "containers": [{"image": "nginx", "resources": {
                   "requests": {"cpu": "10m", "memory": "10M"}}}]}}
    return pod


CLASS_CASES = {
    "labels_kept": ([_class_pod({"namespace": "a", "labels": {"x": "1"}})],
                    {"x"}, True),
    "labels_dropped": ([_class_pod({"namespace": "a", "labels": {"x": "1"}}),
                        _class_pod({"namespace": "a", "labels": {"x": "2"}})],
                       set(), True),
    "labels_partly_kept": ([_class_pod({"labels": {"x": "1", "y": "2",
                                                   "z": "3"}})],
                           {"x", "z", "absent"}, False),
    "labels_absent": ([_class_pod({"namespace": "a"}),
                       _class_pod({"labels": None}),
                       _class_pod({"labels": {}})], {"x"}, True),
    "namespace_kept": ([_class_pod({"namespace": "a"})], set(), True),
    "namespace_dropped": ([_class_pod({"namespace": "a"})], set(), False),
    "namespace_empty": ([_class_pod({"namespace": ""})], set(), True),
    "namespace_missing": ([_class_pod({"labels": {"x": "1"}})], {"x"}, True),
    "metadata_none": ([_class_pod(None), {"metadata": None, "spec": None}],
                      {"x"}, True),
    "owners_and_annotations": ([_class_pod({
        "namespace": "a", "labels": {"x": "1", "y": "2"},
        "ownerReferences": [{"kind": "ReplicaSet", "controller": True},
                            {"kind": "Job"}],
        "annotations": {"simon/gpu-mem": "4Gi", "simon/local-storage": "x",
                        "other": "1"}})], {"y"}, False),
    "non_dict_metadata": ([_class_pod("not-a-dict")], set(), True),
}


@pytest.mark.parametrize("case", sorted(CLASS_CASES))
def test_class_sigs_match_pod_sig_of_class_template(pod_sig, case):
    """class_sigs keys each template as pod_sig keys its class template,
    without building it, and refuses what pod_sig refuses."""
    from open_simulator_tpu.native import class_sigs_fn
    from open_simulator_tpu.simulator.encode import class_template

    class_sigs = class_sigs_fn()
    pods, keys, keep_ns = CLASS_CASES[case]
    keys = frozenset(keys)
    if case == "non_dict_metadata":
        with pytest.raises(TypeError):
            pod_sig(pods[0], ANNO_KEYS)
        with pytest.raises(TypeError):
            class_sigs(pods, ANNO_KEYS, keys, keep_ns)
        return
    want = [pod_sig(class_template(p, keys, keep_ns), ANNO_KEYS) for p in pods]
    assert class_sigs(pods, ANNO_KEYS, keys, keep_ns) == want
    # what the class drops is all it drops: keeping every label and the
    # namespace keys the template as pod_sig does, wherever it has labels
    every = frozenset(k for p in pods
                      for k in ((p.get("metadata") or {}).get("labels") or ()))
    for p, sig in zip(pods, class_sigs(pods, ANNO_KEYS, every, True)):
        if (p.get("metadata") or {}).get("labels"):
            assert sig == pod_sig(p, ANNO_KEYS)


# ------------------------------------------------------------------ the build -------


@pytest.mark.parametrize("rc", [0, 1])
def test_build_replaces_the_binary_whole(tmp_path, monkeypatch, rc):
    """The compiler writes a temporary file that replaces the binary in one
    rename: a failed build leaves neither the binary nor a partial file,
    and a good one leaves the binary alone."""
    from open_simulator_tpu import native

    cxx = tmp_path / "cxx"
    # a compiler that writes part of its output, then exits with `rc`
    cxx.write_text('#!/bin/sh\nwhile [ "$1" != "-o" ]; do shift; done\n'
                   f'printf partial > "$2"\nexit {rc}\n')
    cxx.chmod(0o755)
    out = tmp_path / "out"
    out.mkdir()
    so = out / "_hashobj.so"
    monkeypatch.setenv("CXX", str(cxx))
    monkeypatch.setattr(native, "_SO", str(so))
    assert native._build() is (rc == 0)
    if rc:
        assert sorted(os.listdir(out)) == []
    else:
        assert sorted(os.listdir(out)) == [so.name]
        assert so.read_text() == "partial"
