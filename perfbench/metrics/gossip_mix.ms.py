"""Device time of the gossip_mix Pallas kernel per step and chip, in ms.

The trace names no kernel: the Pallas call shows as a custom call with
target ``tpu_custom_call`` (the instruction takes the jitted function's
name). In the timed steps the only one is gossip_mix, which writes the
flat bus of 128-lane rows, so its events are the tpu_custom_call ops with
a 2-D ``[rows,128]`` result. Summed over the chips, over traced steps
times chips.
"""
import re

_BUS = re.compile(r"\[\d+,128\]$")


def read(ctx):
    ns = sum(o.dur_ns for o in ctx.ops_in_window()
             if o.category == "custom-call:tpu_custom_call"
             and _BUS.search(o.name))
    if ns == 0:
        return None
    return ns * 1e-6 / (ctx.steps * ctx.chips)
