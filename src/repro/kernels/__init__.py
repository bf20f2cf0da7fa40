"""Pallas TPU kernels for the hot spots of the train step:
  * gossip_mix       — fused consensus-mix + SGD update (memory-bound)
  * flash_attention  — causal self-attention, forward and backward
                       (FlashAttention-2), which the model trains through
                       on TPU (``repro.models.attention.attention_any``)

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit
wrapper), ref.py (pure-jnp oracle); validated in interpret=True on CPU.
"""
