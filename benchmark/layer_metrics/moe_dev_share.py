"""Share of the device time of the two serving programs spent in the
expert layers: the operations traced under ``moe.route``, ``moe.experts``
and ``moe.shared`` over ``_step`` + ``_prefill_chunk`` in the traced window
(the driver's map from operation to ``jax.named_scope`` region)."""
from benchmark.lib.readers_moe_mla import share_under


def read(facts):
    return share_under(facts, "moe.")
