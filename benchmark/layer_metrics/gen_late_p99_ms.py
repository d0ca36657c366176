"""How late the load generator submitted a request against its due time,
99th percentile over the window's requests (the benchmark's own clock). A
starved generator must not read as a fast server."""
from benchmark.lib.stats import percentile


def read(facts):
    late = facts.get("gen_late_ms")
    return percentile(late, 99.0) if late else None
