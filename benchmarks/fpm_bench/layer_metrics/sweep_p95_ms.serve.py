"""95th percentile latency of the window's ``support_many`` queries alone, in
ms, each timed from when it was due until its answer."""

from harness import percentile


def read(record):
    lat = record.get("latency_ms", {}).get("support_many")
    return percentile(lat, 95) if lat else None
