"""Share of its roofline the expert layer's grouped matmuls reach, in %:
the larger of (bytes they must move / HBM bandwidth) and (FLOPs / bf16
peak), per step and chip, over their measured time per step and chip. The
work is counted from shapes and the expected held share
(``harness.moe_work.gmm_work``), recomputation not counted."""

from harness.moe_work import gmm_work
from harness.spec import metric_reader


def read(ctx):
    ms = metric_reader("moe_gmm.ms")(ctx)
    work = gmm_work(ctx.cell.model, ctx.cell.traffic, ctx.chips)
    if not ms or work is None:
        return None
    flops, nbytes = work
    bound_s = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                  flops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * bound_s / (ms * 1e-3)
