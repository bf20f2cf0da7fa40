"""Pallas TPU kernel: fused gossip-mix + SGD update.

One VMEM pass computes  out = a₀·w + Σ_d a_{d+1}·nbr_d − η·u  over 2-D tiles
(the update term is optional: the pure-consensus variant skips reading u).

Memory traffic per element: (k + 2) reads + 1 write in a single pass, versus
2(k + 2) reads + (k + 2) writes for the unfused chain of axpys — the gossip
step is purely memory-bound (arithmetic intensity ≈ (k+2) FLOPs per (k+2)·4
bytes), so the fusion is worth ~2× HBM traffic on the full parameter set
*every iteration*.

Tiling: inputs are reshaped to (R, C) with C a multiple of 128 (lane width)
and R tiled by BLOCK_R sublanes; each neighbor buffer is its own operand and
each tile of every buffer is resident in VMEM simultaneously —
VMEM footprint = (k + 2) · BLOCK_R · BLOCK_C · 4 B, sized ≤ ~4 MiB.

``donate=True`` aliases the self buffer to the output
(``input_output_aliases``), making the pass in-place on HBM — used by the
flat-buffer gossip bus (`repro.core.bus`) whose packed buffer is a temporary.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_R = 256
DEFAULT_BLOCK_C = 512


def _kernel(*refs, k: int, has_update: bool, prefetch: bool):
    if prefetch:
        refs = refs[1:]         # the source table only steers the index maps
    w_ref, nbr_refs, wts_ref, rest = refs[0], refs[1:k + 1], refs[k + 1], \
        refs[k + 2:]
    acc = w_ref[...].astype(jnp.float32) * wts_ref[0]
    for d in range(k):  # k is static — unrolled adds, single pass
        acc += nbr_refs[d][...].astype(jnp.float32) * wts_ref[d + 1]
    if has_update:
        upd_ref, eta_ref, out_ref = rest
        acc -= eta_ref[0] * upd_ref[...].astype(jnp.float32)
    else:
        (out_ref,) = rest
    out_ref[...] = acc.astype(out_ref.dtype)


def gossip_mix_2d(
    w: jax.Array,                 # (R, C)
    neighbors: Sequence[jax.Array],   # k arrays, each (R, C)
    weights: jax.Array,           # (k + 1,) float32
    update: jax.Array | None = None,  # (R, C), optional
    eta: jax.Array | None = None,     # (1,) float32, required with update
    *,
    block_r: int = DEFAULT_BLOCK_R,
    block_c: int = DEFAULT_BLOCK_C,
    interpret: bool = False,
    donate: bool = False,
    sources: np.ndarray | None = None,   # (k, M) ints, see below
) -> jax.Array:
    """``a₀·w + Σ_d a_{d+1}·neighbors[d] − η·update`` in one VMEM pass.

    ``sources`` reads the neighbors in place, for the single-process bus
    where ``w`` stacks M workers' equal row ranges: neighbor d of worker m
    is then worker ``sources[d][m]``'s rows of ``neighbors[d]`` (usually
    ``w`` itself), so no permuted copy of the bus is ever written. The
    table rides in SMEM as a scalar prefetch that steers the index maps.
    """
    k = len(neighbors)
    R, C = w.shape
    block_r = min(block_r, R)
    block_c = min(block_c, C)
    assert R % block_r == 0 and C % block_c == 0, (R, C, block_r, block_c)
    has_update = update is not None
    grid = (R // block_r, C // block_c)
    prefetch = sources is not None
    tile = lambda i, j, *_: (i, j)
    if prefetch:
        assert not donate, "in-place neighbor reads need the input intact"
        M = len(sources[0])
        assert R % (M * block_r) == 0, (R, M, block_r)
        nbw = R // (M * block_r)        # row blocks per worker
        table = jnp.asarray(np.asarray(sources, np.int32).reshape(-1))

        def nbr_map(d):
            return lambda i, j, src: (src[d * M + i // nbw] * nbw + i % nbw, j)
    else:
        nbr_map = lambda d: tile
    in_specs = [pl.BlockSpec((block_r, block_c), tile)]
    in_specs += [pl.BlockSpec((block_r, block_c), nbr_map(d)) for d in range(k)]
    in_specs += [pl.BlockSpec((k + 1,), lambda *_: (0,))]
    args = [w, *neighbors, weights]
    if has_update:
        assert eta is not None, "update without eta"
        in_specs += [
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((1,), lambda *_: (0,)),
        ]
        args += [update, eta]
    out_specs = pl.BlockSpec((block_r, block_c), tile)
    if prefetch:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs)
        args = [table] + args
    else:
        grid_spec = pl.GridSpec(grid=grid, in_specs=in_specs,
                                out_specs=out_specs)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, has_update=has_update,
                          prefetch=prefetch),
        grid_spec=grid_spec,
        # inside jax.shard_map the output varies over every manual axis an
        # input varies over; the vma checker needs that stated
        out_shape=jax.ShapeDtypeStruct(
            (R, C), w.dtype,
            vma=frozenset().union(*(jax.typeof(a).vma for a in args))),
        input_output_aliases={0: 0} if donate else {},
        interpret=interpret,
        name="gossip_mix",
    )(*args)
