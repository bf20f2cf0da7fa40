"""Device time of the expert layer's grouped matmuls per step and chip, in
ms, forward and backward.

The program runs them as ``lax.ragged_dot``, which the TPU compiler turns
into Mosaic kernels: in the trace, custom calls with target
``tpu_custom_call`` (instructions ``ragged-dot-none``) whose result is the
pair buffer through a projection, ``[tokens x min(top_k, held), d_ff_expert]``
or ``[..., d_model]`` (forward, and the input's gradient), or a held
expert's weight gradient, ``[held, d_model, d_ff_expert]`` or
``[held, d_ff_expert, d_model]`` (``harness.moe_work.gmm_result_shapes``).
The small ``ragged-dot-metadata`` kernel (a tuple result) is left out.
Summed over the chips, over traced steps times chips; None in a cell
without experts, or where no such op ran.
"""
import re

from harness.moe_work import gmm_result_shapes

_SHAPE = re.compile(r" [a-z0-9]+\[(\d+(?:,\d+)*)\]$")


def read(ctx):
    shapes = gmm_result_shapes(ctx.cell.model, ctx.cell.traffic)
    if not shapes:
        return None
    ns = 0.0
    for o in ctx.ops_in_window():
        if o.category != "custom-call:tpu_custom_call":
            continue
        m = _SHAPE.search(o.name)
        if m and tuple(int(d) for d in m.group(1).split(",")) in shapes:
            ns += o.dur_ns
    if ns == 0:
        return None
    return ns * 1e-6 / (ctx.steps * ctx.chips)
