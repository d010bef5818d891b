"""serve_dispatch_ms: median wall of one ResidentImage.dispatch_sessions call,
from the benchmark's own span round each call (--trace 1)."""

import statistics


def read(layer: dict):
    spans = layer.get("serve_spans")
    if not spans:
        return None
    return 1e3 * statistics.median(s for _, s in spans)
