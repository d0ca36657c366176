"""Time from due to first token, median over the window's requests, where a
cell records it without being judged by it (``ttft_p50_ms.chat``, ``.sat``).
In chat a window holds 16 requests and a first token takes four or five
passes of the scheduler, so the median hops between whole passes from run to
run; under a closed loop a request is due when its client's last finished."""
from benchmark.lib.stats import median


def read(facts):
    values = facts.get("ttft_ms")
    return median(values) if values else None
