"""Share of its roofline the gossip_mix kernel reaches, in %: the larger of
(bytes the mix must move / HBM bandwidth) and (FLOPs / bf16 peak), per
step and chip, over the kernel's measured time per step and chip. The work
is counted from the bus's padded shapes and the topology's degree
(harness.work.gossip_mix_work), never from cost_analysis."""

from harness.spec import metric_reader


def read(ctx):
    ms = metric_reader("gossip_mix.ms")(ctx)
    if not ms or ctx.mix_work is None:
        return None
    flops, nbytes = ctx.mix_work
    bound_s = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                  flops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * bound_s / (ms * 1e-3)
