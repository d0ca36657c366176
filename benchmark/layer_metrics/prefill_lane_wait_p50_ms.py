"""Median time a request waited in the engine's prefill lane: from
``admit_start`` (a slot is assigned, where ``queue_wait_p50_ms`` stops) to
the dispatch of its own first chunk, behind every older prompt's chunks.
Read from the ``request.lane`` spans the scheduler writes when a request
retires, over the requests whose first token (the end of their
``request.prefill`` span) falls between the first and the last entry of
``facts["decode_steps"]``, the window's decode steps."""
from benchmark.lib.program_spans import finished_requests
from benchmark.lib.stats import median


def read(facts):
    steps = facts.get("decode_steps")
    if not steps:
        return None
    first, last = steps[0][0], steps[-1][0]
    waits = []
    for tree in finished_requests():
        lane, prefill = tree.get("request.lane"), tree.get("request.prefill")
        if lane is None or prefill is None:
            continue
        if first <= prefill.start_s + prefill.dur_s <= last:
            waits.append(lane.dur_s * 1e3)
    return median(waits) if waits else None
