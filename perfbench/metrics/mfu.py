"""mfu (%): model FLOP/s of the whole train step over the chips' bf16 peak.

Model FLOPs per token (``lib/flops.per_token``, recomputation not counted)
times the tokens per second of the untraced window, over chips times the
published peak of ``device_kind``.  Layer: the train step as a whole.
"""

from perfbench.lib.peaks import peaks


def read(ctx):
    return (100.0 * ctx.flops_per_token * ctx.tokens_per_s
            / (ctx.chips * peaks(ctx.device_kind)[0]))
