"""Time from due to first token, 90th percentile over the window's requests:
recorded, not judged (over tens of requests a 90th percentile is nearly a
maximum)."""
from benchmark.lib.stats import percentile


def read(facts):
    values = facts.get("ttft_ms")
    return percentile(values, 90.0) if values else None
