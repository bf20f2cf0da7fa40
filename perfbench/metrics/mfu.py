"""Model FLOP/s utilisation of the whole train step, in %: the operations
one trained token requires (the configuration's reference module counts
them; recomputation not counted) times the untraced window's tokens/s,
over chips times the bf16 peak."""


def read(ctx):
    return (100.0 * ctx.flops_per_token * ctx.tokens_per_s
            / (ctx.chips * ctx.peaks["bf16_flops_per_s"]))
