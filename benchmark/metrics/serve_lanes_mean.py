"""serve_lanes_mean: requests per ResidentImage.dispatch_sessions call, from
the benchmark's own span round each call (--trace 1)."""


def read(layer: dict):
    spans = layer.get("serve_spans")
    if not spans:
        return None
    return sum(n for n, _ in spans) / len(spans)
