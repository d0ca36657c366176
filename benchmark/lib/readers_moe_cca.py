"""What the readers of the ZAYA-shaped cell share (``cca_*``, ``moe_router |
moe_top1_*``, ``head_step_dev_share``, ``moe_cca_step_roofline``): the decode
program's device time by the label the driver gave each operation
(``drivers/lm_serving_moe_cca.py`` ``scope_of``: ``attn.full`` and under it
``.cca.in | .cca.mix | .cca.out | .merge | .kernel``; ``moe.router``;
``moe.experts`` and ``moe.experts.kernel``; ``merge``; ``head``), and the
mean least time of a cost over the traced decode steps. Both are the elder
expert cells' own: ``decode_share_under(facts, prefix)`` (% of the decode
program's device time under the labels that start with ``prefix``) and
``roofline(facts, cost_of, prefix=None)`` (``cost_of(active, context tokens,
counts)`` over the traced steps, over the time of one step under ``prefix``,
the whole program with none). A program without the scopes or the counters
leaves nothing to read and every reader returns ``None``."""
from benchmark.lib.readers_moe_mla import roofline  # noqa: F401
from benchmark.lib.readers_moe_mtp import decode_share_under  # noqa: F401
