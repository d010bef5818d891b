"""Device idle under the program's own spans. The reduced trace gives each
idle gap to the innermost host event covering its midpoint (devtrace.reduce,
`idle_gaps`); the program names its phases `simon.<phase>` with dotted
children, so a phase and its children are a prefix."""

from __future__ import annotations


def matches(name: str, phases=(), prefixes=()) -> bool:
    """`name` is one of `phases` or a dotted child of one, or starts with
    one of `prefixes`."""
    return (any(name == p or name.startswith(p + ".") for p in phases)
            or name.startswith(tuple(prefixes)))


def idle_ms(layer: dict, phases=(), prefixes=()):
    """Device-idle milliseconds per simulation under the matching names, or
    None when the run has no trace or no gap under them."""
    red = layer.get("trace")
    sims = layer.get("sims") or 0
    if not red or not sims:
        return None
    secs = [s for name, s in red.get("idle_gaps") or ()
            if matches(name, phases, prefixes)]
    if not secs:
        return None
    return 1e3 * sum(secs) / sims
