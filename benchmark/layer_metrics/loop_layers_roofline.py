"""Share of their roofline that the layers of a decode step reach, every
pass-layer's attention and MLP together: their weights once a pass, the
visible lines read, the live tokens' written
(``lib/opcount_looped.layers_step``), averaged over the traced decode steps,
over the device time under ``attn.full`` and ``mlp`` in one ``_step``.

The two scopes are read together and not one by one: the compiler streams a
layer's MLP weights into fast memory while the attention before it still
computes, so the time under ``mlp`` alone leaves out part of its work (it
would read 137% of its roofline on a v5e, and ``attn.full`` alone 64%,
PERF.md section 6, PR 43) while their sum holds all of both."""
from benchmark.lib.opcount import least_seconds
from benchmark.lib.opcount_looped import layers_step
from benchmark.lib.readers_moe_mla import scope_seconds
from benchmark.lib.readers_ssm import traced_steps


def read(facts):
    cfg, steps, peaks = facts["config"], traced_steps(facts), facts.get(
        "peaks")
    if not steps or not peaks or "total_ut_steps" not in cfg:
        return None
    seconds = calls = 0.0
    for scope in ("attn.full", "mlp"):
        got = scope_seconds(facts, scope, keys=("decode",))
        if not got:
            return None
        seconds, calls = seconds + got[0], got[2]
    if not calls or seconds <= 0:
        return None
    least = [least_seconds(layers_step(cfg, *s), peaks)[0] for s in steps]
    return 100.0 * (sum(least) / len(least)) / (seconds / calls)
