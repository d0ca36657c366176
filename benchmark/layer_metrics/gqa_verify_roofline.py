"""Share of its roofline that the stack's attention reaches in a round: the
least time for the projections' weights once, the lines the kernel fetches
by its own rule (``pages_fetched_full | window`` on the round's
``engine.step.prepare`` span, whole pages from the first page the first row
sees to the second row's last) and the two rows' lines written
(``lib/opcount_moe_mtp.gqa_verify``), the traced rounds' mean, over the
device time under ``attn.*`` in one ``_round``. Bound by HBM bytes."""
from benchmark.lib.opcount_moe_mtp import gqa_verify
from benchmark.lib.readers_moe_mtp import mean, roofline, traced_rounds


def read(facts):
    rounds = traced_rounds(facts)
    if not rounds:
        return None
    page = facts["config"]["engine"]["page_size"]
    return roofline(facts, gqa_verify(
        facts["config"], mean(rounds, "rows"),
        mean(rounds, "pages_fetched_full") * page,
        mean(rounds, "pages_fetched_window") * page), "attn.")
