"""Device time of causal attention's Pallas flash kernels per step and
chip, in ms: the forward, and the dQ and dK/dV kernels of the backward
(the layers' remat keeps the forward's output, so it runs once).

The trace names no kernel: a Pallas call shows as a custom call with
target ``tpu_custom_call``, named by its result shape, a tuple by its first
element. The flash kernels' results are 4-D ``(rows, heads, L, d)``: the
forward's output ``(B, H, L, d_v)``, dQ ``(B, H, L, d_qk)`` and dK
``(B, Kh, L, d_qk)`` (``harness.attn_work.flash_result_shapes``), which no
other kernel of the step writes. Summed over the chips, over traced steps
times chips; None in a cell without such attention, or where no such op
ran.
"""
import re

from harness.attn_work import flash_result_shapes

_SHAPE = re.compile(r" \(?[a-z0-9]+\[(\d+(?:,\d+)*)\]")


def read(ctx):
    shapes = flash_result_shapes(ctx.cell.model, ctx.cell.traffic, ctx.chips)
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
