"""Share of the decode batch's slots that held a live sequence, over the
window's decode steps: the difference of two ``ServingMetrics.snapshot()``
readings (real rows over padded rows)."""


def read(facts):
    rows = facts.get("batch_rows")
    if not rows or rows[1] <= 0:
        return None
    return 100.0 * rows[0] / rows[1]
