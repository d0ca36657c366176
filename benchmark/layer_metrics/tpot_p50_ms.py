"""Gap between a request's output tokens, median over the window's
requests, where a cell records it without being judged by it
(``tpot_p50_ms.long``: with six requests a window the median lies between
the requests that decode alone and those whose last tokens share passes
with the next prompt's chunks, and hops between the two from run to run)."""
from benchmark.lib.stats import median


def read(facts):
    values = facts.get("tpot_ms")
    return median(values) if values else None
