"""Share of its roofline that a round's expert layers reach, the stack's
and the MTP block's together (the counters sum them): the least time for
the matrices of the experts reached, every shared expert and every router
(``lib/opcount_moe_mtp.moe_round``), the traced rounds' mean, over the
device time under ``moe.*`` and ``mtp.block.moe.*`` in one ``_round``. Bound
by HBM bytes at a round's 128 rows."""
from benchmark.lib.opcount_moe_mtp import moe_round
from benchmark.lib.readers_moe_mtp import mean, roofline, traced_counts


def read(facts):
    counts = traced_counts(facts)
    if not counts:
        return None
    return roofline(facts, moe_round(
        facts["config"], mean(counts, "rows"),
        mean(counts, "moe_experts_touched"), mean(counts, "moe_assignments")),
        "moe.", "mtp.block.moe.")
