"""Assignments served here over all the live rows made: the expert layers'
``moe_assignments`` over ``rows x layers x num_experts_per_tok`` in the
traced rounds, in % (``rows``: two a live slot in the stack, the committed
ones in the MTP block, which the layers' own ``live`` mask counts; the
denominator takes two a slot in every layer, so an MTP block that runs one
row a slot reads a little under). 12.5 for an even router where this chip
holds an eighth of the experts."""
from benchmark.lib.opcount_moe_mtp import sizes
from benchmark.lib.readers_moe_mtp import traced_counts


def read(facts):
    counts = traced_counts(facts)
    if not counts:
        return None
    cfg = facts["config"]
    s = sizes(cfg)
    stack, mtp = s["sparse_layers"], s["mtp_layers"]
    # the stack's layers see both rows of a live slot, the MTP block the
    # committed ones (``emitted``)
    made = sum(c["rows"] * stack + c["emitted"] * mtp for c in counts) \
        * cfg["num_experts_per_tok"]
    if not made:
        return None
    return 100.0 * sum(c["moe_assignments"] for c in counts) / made
