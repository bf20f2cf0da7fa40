"""End-to-end driver: decentralized training of a ~100M-param LM for a few
hundred steps (paper technique, synthetic corpus, checkpointing).

    PYTHONPATH=src python examples/train_100m.py --steps 300           # full
    PYTHONPATH=src python examples/train_100m.py --preset small        # quick

Model: granite-family decoder, d_model=512, 12 layers, vocab 8192 ≈ 100M
params (60M non-embedding). Four DSM workers on a ring; classical momentum
0.9 and the Smith LR rule, exactly the paper's §4 recipe.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import topology as T
from repro.core.decentralized import init_state, make_train_step, replicate_for_workers
from repro.core.gossip import GossipSpec
from repro.data import WorkerBatcher, pad_to_equal, random_split, token_stream
from repro.models import model as M
from repro.optim import momentum_sgd, smith_lr_range_test
from repro.train import train

PRESETS = {
    # name: (d_model, layers, heads, d_ff, vocab, seq, batch/worker, steps)
    "full": (512, 12, 8, 2048, 8192, 128, 8, 300),
    "small": (256, 4, 4, 1024, 2048, 64, 8, 60),
}


def make_config(preset: str):
    """(cfg, preset tuple) for a train_100m run — shared with serve_demo so a
    gossip checkpoint trained here can be decoded there."""
    d, L, H, F, V, seq, B, steps = PRESETS[preset]
    cfg = dataclasses.replace(
        get_config("granite-3-2b", reduced=True),
        n_layers=L, d_model=d, n_heads=H, n_kv_heads=max(H // 4, 1),
        head_dim=d // H, d_ff=F, vocab_size=V, scan_layers=True, remat=False,
        tie_embeddings=True)
    return cfg, PRESETS[preset]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="small", choices=PRESETS)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--topology", default="ring", choices=("ring", "clique"))
    ap.add_argument("--ckpt", default="results/train_100m.npz")
    ap.add_argument("--mesh", action="store_true",
                    help="run on a WorkerMesh over the local devices "
                         "(workers × model groups) instead of meshless vmap")
    args = ap.parse_args()

    if args.mesh and len(jax.devices()) < args.workers:
        raise SystemExit(
            f"--mesh needs one device per worker (≥{args.workers}); this "
            f"host has {len(jax.devices())}. Force host devices first, e.g."
            f"\n  XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{2 * args.workers} PYTHONPATH=src python "
            f"examples/train_100m.py --mesh ...")

    d, L, H, F, V, seq, B, steps = PRESETS[args.preset]
    steps = args.steps or steps
    cfg, _ = make_config(args.preset)
    from repro.models.params import count_params
    n_params = count_params(M.model_defs(cfg))
    print(f"model: {n_params/1e6:.1f}M params  d={d} L={L} vocab={V} seq={seq}")

    Mw = args.workers
    toks, _ = token_stream(S=4096, seq_len=seq, vocab=V, seed=0)
    parts = pad_to_equal(random_split(len(toks), Mw))
    batcher = WorkerBatcher((toks,), parts, batch_size=B, seed=0)

    def batches():
        while True:
            (t,) = batcher.next()
            yield {"tokens": jnp.asarray(t)}

    # Smith (2017) LR range test — the paper's configuration rule
    params0 = M.init(jax.random.PRNGKey(0), cfg)

    def one_step_loss(lr):
        p = replicate_for_workers(params0, Mw)
        opt = momentum_sgd(lr, 0.9)
        spec = GossipSpec(topology=T.undirected_ring(Mw), backend="einsum")
        step = jax.jit(make_train_step(
            lambda q, b: M.loss_fn(q, cfg, b), opt, gossip=spec, mode="gossip"))
        st = init_state(p, opt)
        (t,) = batcher.next()
        st, m = step(st, {"tokens": jnp.asarray(t)})
        return float(m.loss)

    lr, _, _ = smith_lr_range_test(one_step_loss, 1e-4, 3.0, n_points=10)
    lr *= 0.3  # safety margin below the divergence knee (momentum 0.9)
    print(f"Smith LR rule selected lr = {lr:.4f}")

    topo = T.undirected_ring(Mw) if args.topology == "ring" else T.clique(Mw)
    mesh = param_specs = None
    gspec = GossipSpec(topology=topo, backend="einsum")
    if args.mesh:
        # WorkerMesh over local devices: Mw workers × whatever model-group
        # factor the device count affords (k=1 on a CPU host is fine — the
        # point is that the SAME code path drives the 512-chip mesh).
        from repro.launch.mesh import WorkerMesh, make_host_mesh
        from repro.launch import shardings as shard_lib
        k = max(len(jax.devices()) // Mw, 1)   # device floor checked in main
        wm = WorkerMesh.from_mesh(make_host_mesh(data=Mw, model=k))
        mesh = wm
        gspec = GossipSpec.for_mesh(topo, wm, backend="fused")
        param_specs = shard_lib.param_pspecs(cfg, wm, "gossip")
        print(f"WorkerMesh: {wm.describe()}")
    state, hist = train(
        lambda p, b: M.loss_fn(p, cfg, b),
        replicate_for_workers(params0, Mw),
        momentum_sgd(lr, 0.9),
        batches(), steps=steps,
        gossip=gspec,
        mode="gossip", mesh=mesh, param_specs=param_specs,
        log_every=max(steps // 10, 1),
        ckpt_path=args.ckpt, ckpt_every=max(steps // 3, 1))
    print(f"\nloss {hist.loss[0]:.4f} -> {hist.loss[-1]:.4f} over {steps} steps "
          f"on {topo.name}; checkpoint at {args.ckpt}")
    print("decode from it:  PYTHONPATH=src python examples/serve_demo.py "
          f"--gossip-ckpt {args.ckpt} --preset {args.preset}")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
