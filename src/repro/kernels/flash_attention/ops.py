"""The flash-attention op in the model's ``(B, L, heads, d)`` layout:
differentiable through its own backward kernels, and run per device.

The TPU compiler partitions no Pallas call: on a mesh it refuses one
whose operands are sharded. So each kernel call runs through
:func:`repro.launch.mesh.per_device`, inside a ``shard_map`` that splits
the rows over the mesh's worker axes and the heads over its model axis
where they divide (the all-reduce step's batch, or the rows of workers
folded together).

The op is a ``jax.custom_vjp`` whose forward and backward are each a
``jax.custom_batching.custom_vmap``. Under the train step's vmap over
workers the batched rule runs them through
:func:`repro.launch.mesh.per_worker`: in a ``shard_map`` over the mesh's
worker axes where there is one worker per device group, else with the
workers folded into the kernel's batch grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro.kernels.flash_attention import kernel as K
from repro.launch.mesh import batch_all, per_device, per_worker

BLOCKS = (1024, 512, 256, 128)
# the name the forward's output and logsumexp carry as residuals: a remat
# policy that saves them keeps the backward from running the forward again
RESIDUAL_NAME = "flash_attention_out"


def on_tpu() -> bool:
    """Whether the kernel compiles (TPU) or is interpreted (elsewhere)."""
    return jax.default_backend() == "tpu"


def pick_block(L: int) -> int | None:
    """The q and kv block for a sequence of ``L``: the largest of
    :data:`BLOCKS` that divides it, None where none does."""
    return next((b for b in BLOCKS if L % b == 0), None)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_kv", "interpret"))
def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              scale: float | None = None, block_q: int | None = None,
              block_kv: int | None = None, interpret: bool | None = None):
    """q ``(B, Lq, H, d_qk)``; k ``(B, Lkv, Kh, d_qk)``; v ``(B, Lkv, Kh,
    d_v)`` -> ``(B, Lq, H, d_v)``: ``softmax(scale q k^T + mask) v``.

    ``scale`` defaults to ``d_qk ** -0.5``; blocks default to
    :func:`pick_block` and are cut to the sequence. ``interpret=None``
    compiles the kernel on TPU and interprets it elsewhere. The gradient
    is there for causal attention with no window.
    """
    if interpret is None:
        interpret = not on_tpu()
    Lq, Lkv = q.shape[1], k.shape[1]
    block_q = min(block_q or pick_block(Lq) or Lq, Lq)
    block_kv = min(block_kv or pick_block(Lkv) or Lkv, Lkv)
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    op = _op(causal, window, float(scale), block_q, block_kv, interpret)
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(op(t(q), t(k), t(v)))


@functools.lru_cache(maxsize=None)
def _op(causal, window, scale, block_q, block_kv, interpret):
    """The custom-VJP op over ``(B, heads, L, d)`` for one static setting."""
    kw = dict(scale=scale, causal=causal, window=window, block_q=block_q,
              block_kv=block_kv, interpret=interpret)

    def backward(q, k, v, o, lse, do):
        if not causal or window is not None:
            raise NotImplementedError(
                "the flash kernel's gradient is for causal attention with "
                "no window")
        di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), -1)
        di = jnp.broadcast_to(di[:, :, None], lse.shape)
        return K.flash_backward(q, k, v, do, lse, di, scale=scale,
                                block_q=block_q, block_kv=block_kv,
                                interpret=interpret)

    forward = _sharded(functools.partial(K.flash_forward, **kw))
    backward = _sharded(backward)

    @jax.custom_vjp
    def attn(q, k, v):
        return forward(q, k, v)[0]

    def attn_fwd(q, k, v):
        o, lse = forward(q, k, v)
        o, lse = (checkpoint_name(x, RESIDUAL_NAME) for x in (o, lse))
        return o, (q, k, v, o, lse)

    def attn_bwd(res, do):
        return backward(*res, do)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn


def _sharded(f):
    """``f`` over ``(B, heads, ...)`` operands, run per device, as a
    custom_vmap whose batched rule runs it per worker."""
    def local(*args):
        return per_device(f, args, model_dim=1)

    g = jax.custom_batching.custom_vmap(local)

    @g.def_vmap
    def _batched(axis_size, in_batched, *args):
        args = batch_all(axis_size, in_batched, args)
        out = per_worker(local, args, fold=True)
        return out, jax.tree.map(lambda _: True, out)

    return g
