"""Share of its roofline that a whole decode step reaches: the stack's
weights once a pass, the head once, the visible lines of every pass-layer
read and the live tokens' written (``lib/opcount_looped.step``), averaged
over the traced decode steps, over the device time of one ``_step``."""
from benchmark.lib.opcount_looped import step
from benchmark.lib.readers_ssm import step_roofline


def read(facts):
    cfg = facts["config"]
    if "total_ut_steps" not in cfg:
        return None
    return step_roofline(facts, lambda active, context: step(
        cfg, active, context))
