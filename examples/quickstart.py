"""Quickstart: decentralized training of a tiny LM on a worker ring.

    PYTHONPATH=src python examples/quickstart.py

Demonstrates the public API end-to-end in under a minute on CPU:
topology → GossipSpec → DSM train step → loss curve + gradient statistics
(the paper's E, E_sp, H per step).
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import topology as T
from repro.core.decentralized import replicate_for_workers
from repro.core.gossip import GossipSpec
from repro.data import WorkerBatcher, pad_to_equal, random_split, token_stream
from repro.models import model as M
from repro.optim import momentum_sgd
from repro.train import train


def main():
    M_WORKERS = 4
    cfg = dataclasses.replace(
        get_config("granite-3-2b", reduced=True),
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=256, vocab_size=512)
    toks, _ = token_stream(S=512, seq_len=32, vocab=cfg.vocab_size, seed=0)
    parts = pad_to_equal(random_split(len(toks), M_WORKERS))
    batcher = WorkerBatcher((toks,), parts, batch_size=8, seed=0)

    def batches():
        while True:
            (t,) = batcher.next()
            yield {"tokens": jnp.asarray(t)}

    topo = T.undirected_ring(M_WORKERS)
    print(f"topology: {topo.name}  spectral gap: {topo.spectral_gap:.3f}")
    params0 = replicate_for_workers(M.init(jax.random.PRNGKey(0), cfg), M_WORKERS)
    state, hist = train(
        lambda p, b: M.loss_fn(p, cfg, b),
        params0,
        momentum_sgd(0.1, 0.9),           # the paper's optimizer
        batches(),
        steps=60,
        gossip=GossipSpec(topology=topo, backend="einsum"),
        mode="gossip",
        log_every=10,
    )
    print(f"\nloss: {hist.loss[0]:.3f} -> {hist.loss[-1]:.3f}")
    print(f"final sqrt(E/E_sp): "
          f"{np.sqrt(hist.grad_energy[-1] / max(hist.grad_spread[-1], 1e-9)):.2f} "
          f"(paper Table 1 statistic)")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
