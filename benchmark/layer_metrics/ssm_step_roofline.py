"""Share of its roofline that the state-space layers of a decode step
reach: every mixer's weights once and every live state read and written
(``lib/opcount_ssm_mqa.ssm_step``), averaged over the traced decode steps,
over the device time under ``ssm.*`` in one ``_step``."""
from benchmark.lib.opcount_ssm_mqa import ssm_step
from benchmark.lib.readers_ssm import step_roofline


def read(facts):
    cfg = facts["config"]
    return step_roofline(facts, lambda active, context: ssm_step(cfg, active),
                         prefix="ssm.")
