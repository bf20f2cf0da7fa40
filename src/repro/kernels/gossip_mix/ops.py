"""jit'd wrappers: fused gossip-mix + update over arbitrary parameter pytrees.

Two entry points:

* :func:`gossip_mix_leaf` — one leaf of any shape, padded to the 2-D tile
  grid and run through the Pallas kernel (kept for tests / ad-hoc use).
* :func:`gossip_mix_pytree` — the whole pytree packs ONCE into the flat bus
  layout (`repro.core.bus.BusLayout` — the layout-v2 two-pass plan: cached
  pack/unpack with per-leaf row-range slots, each slot whole sublane tiles
  of 128-lane rows) and runs ONE kernel call per dtype group,
  instead of the old per-leaf Python loop of pad/stack/kernel dispatches.

``interpret=None`` (default) compiles the kernel on TPU and runs it in
Pallas interpret mode elsewhere, as the bus does.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gossip_mix.kernel import DEFAULT_BLOCK_C, DEFAULT_BLOCK_R, gossip_mix_2d

PyTree = Any


def _pad_to_2d(x: jax.Array, block_r: int, block_c: int):
    n = x.size
    c = block_c
    r = int(np.ceil(n / c / block_r)) * block_r
    pad = r * c - n
    flat = jnp.pad(x.reshape(-1), (0, pad))
    return flat.reshape(r, c), n


@functools.partial(jax.jit, static_argnames=("interpret", "block_r", "block_c"))
def gossip_mix_leaf(
    w: jax.Array, neighbors: jax.Array, weights: jax.Array, update: jax.Array,
    eta, *, interpret: bool | None = None,
    block_r: int = DEFAULT_BLOCK_R, block_c: int = DEFAULT_BLOCK_C,
) -> jax.Array:
    """Fused mix+update for one leaf of any shape. neighbors: (k, *w.shape)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k = neighbors.shape[0]
    w2, n = _pad_to_2d(w, block_r, block_c)
    nb2 = [_pad_to_2d(neighbors[d], block_r, block_c)[0] for d in range(k)]
    up2, _ = _pad_to_2d(update, block_r, block_c)
    out = gossip_mix_2d(
        w2, nb2, weights.astype(jnp.float32),
        up2, jnp.asarray([eta], jnp.float32),
        block_r=min(block_r, w2.shape[0]), block_c=block_c, interpret=interpret)
    return out.reshape(-1)[:n].reshape(w.shape)


def gossip_mix_pytree(params: PyTree, neighbor_params: list[PyTree],
                      weights: jax.Array, updates: PyTree, eta,
                      *, interpret: bool | None = None,
                      block_r: int = DEFAULT_BLOCK_R,
                      block_c: int = DEFAULT_BLOCK_C) -> PyTree:
    """Fused kernel over a pytree via the flat bus layout (one pack, one
    kernel dispatch per dtype group — not one per leaf). Uses the cached
    layout-v2 plan with a single shard (shards=1: every leaf packs whole)."""
    from repro.core import bus

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    layout = bus.plan_layout(params, lead_ndim=0, block_r=block_r)
    self_bufs = bus.pack(params, layout, lead_ndim=0)
    nbr_bufs = [bus.pack(nb, layout, lead_ndim=0) for nb in neighbor_params]
    upd_bufs = bus.pack(updates, layout, lead_ndim=0)
    weights = weights.astype(jnp.float32)
    eta_arr = jnp.asarray([eta], jnp.float32)
    outs = []
    for gi, g in enumerate(layout.groups):
        nbrs = [nb[gi] for nb in nbr_bufs]
        outs.append(gossip_mix_2d(
            self_bufs[gi], nbrs, weights, upd_bufs[gi], eta_arr,
            block_r=g.block_r, block_c=block_c, interpret=interpret))
    return bus.unpack(outs, layout, lead_ndim=0)
