"""Share of its roofline causal attention's flash kernels reach, in %: the
larger of (bytes they must move / HBM bandwidth) and (FLOPs / bf16 peak),
per step and chip, over their measured time per step and chip. The work is
the minimum causal attention needs, from shapes
(``harness.attn_work.flash_work``): recomputation not counted, so the
share cannot pass 100 %."""

from harness.attn_work import flash_work
from harness.spec import metric_reader


def read(ctx):
    ms = metric_reader("flash_attention.ms")(ctx)
    work = flash_work(ctx.cell.model, ctx.cell.traffic, ctx.chips)
    if not ms or work is None:
        return None
    flops, nbytes = work
    bound_s = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                  flops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * bound_s / (ms * 1e-3)
