"""Helpers the per-layer readers share (a reader returns None when its
run has nothing to read; it never returns 0 for a share it did not see)."""

from __future__ import annotations


def idle_pct(layer: dict):
    red = layer.get("trace")
    if not red or red.get("idle_share") is None:
        return None
    return 100.0 * red["idle_share"]


def phase_ms(layer: dict, phase: str):
    sims = layer.get("sims") or 0
    c = layer.get("counters") or {}
    v = c.get(f"phase.{phase}")
    if not sims or not v:
        return None
    return 1e3 * v / sims


def kernel_roofline(layer: dict, kernel: str, kind: str, terms: int):
    import devtrace
    import workcount

    red = layer.get("trace")
    pods = (layer.get("counters") or {}).get(f"pods.{kind}")
    if not red or not pods:
        return None
    secs = devtrace.kernel_seconds(red, kernel)
    if secs <= 0:
        return None
    ops, nbytes = workcount.work(layer["n_nodes"], [pods], terms=terms,
                                 sims=layer["sims"])
    pct, _bound = workcount.roofline_pct(ops, nbytes, secs, layer["device_kind"])
    return pct
