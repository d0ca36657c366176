"""Share of its roofline that a prefill launch's recurrence reaches: per
state layer the scan over the launch's real rows
(``lib/opcount_ssm_mqa.chunk_scan``, rows = ``n_valid`` of the launch's
``engine.chunk.prepare`` span), summed over the state layers and the traced
launches, over the device time of ``programs.prefill`` under ``ssm.scan``
in the traced window. A window whose traced part holds no launch, or a
program without the scope, leaves nothing to read."""
from benchmark.lib.opcount import least_seconds
from benchmark.lib.opcount_ssm_mqa import chunk_scan, sizes
from benchmark.lib.readers_moe_mla import scope_seconds
from benchmark.lib.readers_ssm import span_attrs


def read(facts):
    launches = span_attrs(facts, "engine.chunk.prepare", "state_reset")
    got = scope_seconds(facts, "ssm.scan", keys=("prefill",))
    peaks = facts.get("peaks")
    if not launches or not got or got[0] <= 0 or not peaks:
        return None
    cfg = facts["config"]
    least = sum(least_seconds(chunk_scan(cfg, a["n_valid"]), peaks)[0]
                for a in launches) * sizes(cfg)["state_layers"]
    return 100.0 * least / got[0]
