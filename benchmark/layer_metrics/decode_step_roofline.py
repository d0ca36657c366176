"""Share of its roofline that a decode step reaches: the least time the
chip could take for the step the algorithm asks for (``lib/opcount.py``:
weights once with the tied head, the visible keys and values of the live
sequences, the new ones written), averaged over the decode steps the proxy
saw inside the traced window, over ``decode_step_dev_ms``. Bound by HBM
bytes at these sizes (``least_seconds`` says which)."""
from benchmark.lib.opcount import least_seconds, lm_decode_step
from benchmark.lib.readers import per_execution_ms


def read(facts):
    dev_ms = per_execution_ms(facts, "decode")
    bounds, peaks = facts.get("trace_bounds"), facts.get("peaks")
    if not dev_ms or not bounds or not peaks:
        return None
    cfg = facts["config"]
    steps = [s for s in facts["decode_steps"] if bounds[0] <= s[0] <= bounds[1]]
    if not steps:
        return None
    least = [least_seconds(lm_decode_step(
        cfg["hidden_size"], cfg["num_hidden_layers"], cfg["ffn_dim"],
        cfg["vocab_size"], active, context), peaks)[0]
        for _, active, context, _ in steps]
    return 100.0 * (sum(least) / len(least)) / (dev_ms * 1e-3)
