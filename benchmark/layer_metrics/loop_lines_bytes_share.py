"""Of the least bytes of a decode step, the share that is cache lines and
not weights: every visible token's lines read and every live token's
written, in every pass-layer, over the whole step's bytes
(``lib/opcount_looped``), averaged over the traced decode steps, in %. The
weights' part is fixed (the stack once a pass, the head once); the lines'
grows with the batch and the contexts, so this says which of the two sets
the pace as traffic changes. Neither better nor worse by itself. A
configuration without a loop's keys leaves nothing to read."""
from benchmark.lib.opcount_looped import lines_bytes, step
from benchmark.lib.readers_ssm import traced_steps


def read(facts):
    cfg, steps = facts["config"], traced_steps(facts)
    if not steps or "total_ut_steps" not in cfg:
        return None
    shares = [lines_bytes(cfg, active, context)
              / step(cfg, active, context)["bytes"]
              for active, context in steps]
    return 100.0 * sum(shares) / len(shares)
