"""Share of its roofline that a whole round reaches: every weight the round
reads once, the head's slice twice, the lines fetched and written in the
stack and in the MTP block (``lib/opcount_moe_mtp.round_cost``), the traced
rounds' mean, over the device time of one ``_round``
(``decode_step_dev_ms.tpot``)."""
from benchmark.lib.opcount_moe_mtp import round_cost
from benchmark.lib.readers_moe_mtp import (
    mean,
    roofline,
    traced_counts,
    traced_rounds,
)


def read(facts):
    rounds, counts = traced_rounds(facts), traced_counts(facts)
    if not rounds or not counts:
        return None
    page = facts["config"]["engine"]["page_size"]
    return roofline(facts, round_cost(
        facts["config"], mean(rounds, "rows"),
        mean(rounds, "pages_fetched_full") * page,
        mean(rounds, "pages_fetched_window") * page,
        mean(counts, "moe_experts_touched"), mean(counts, "moe_assignments")))
