"""Share of its roofline that a whole decode step reaches: every weight
once with the tied embedding as the head, the live states read and written,
the visible lines of the attention layers (``lib/opcount_ssm_mqa.step``),
averaged over the traced decode steps, over the device time of one
``_step``."""
from benchmark.lib.opcount_ssm_mqa import step
from benchmark.lib.readers_ssm import step_roofline


def read(facts):
    cfg = facts["config"]
    return step_roofline(facts, lambda active, context: step(
        cfg, active, context))
