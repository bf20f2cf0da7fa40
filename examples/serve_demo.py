"""Serving demo: continuous batching over a paged KV cache (default), or
the lock-step wave baseline with ``--batcher wave``.

    PYTHONPATH=src python examples/serve_demo.py [--arch gemma-2b]
    PYTHONPATH=src python examples/serve_demo.py --batcher wave
    PYTHONPATH=src python examples/serve_demo.py \
        --gossip-ckpt results/train_100m.npz --preset small

Uses the reduced config of any assigned architecture; exercises the same
serve_step the decode dry-run shapes lower. With ``--gossip-ckpt`` the
demo decodes from a decentralized-training checkpoint: the worker-stacked
estimates are consensus-averaged (w̄ = (1/M)Σ w_j) into one serving replica
via ``serving.engine.load_consensus_params``.

Archs the paged cache can't serve (ssm/rglru/sliding-window/enc-dec)
automatically fall back to the wave baseline.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.models import model as M
from repro.serving import ContinuousBatcher, WaveBatcher, generate
from repro.serving.engine import load_consensus_params
from repro.serving.kvcache import paged_unsupported_reason


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=ARCH_NAMES)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batcher", default="continuous",
                    choices=("continuous", "wave"),
                    help="continuous = paged-KV slots refilled per request "
                         "(production path); wave = lock-step baseline")
    ap.add_argument("--gossip-ckpt", default=None,
                    help="decode from a gossip-trained checkpoint "
                         "(train_100m.py output); implies --preset's config")
    ap.add_argument("--preset", default="small",
                    help="train_100m preset the checkpoint was trained with")
    args = ap.parse_args()

    if args.gossip_ckpt:
        from train_100m import PRESETS, make_config  # same examples/ dir
        if args.preset not in PRESETS:
            ap.error(f"--preset must be one of {sorted(PRESETS)}")
        cfg, _ = make_config(args.preset)
        params = load_consensus_params(args.gossip_ckpt, cfg)
        print(f"serving consensus average of gossip checkpoint "
              f"{args.gossip_ckpt} ({cfg.name})")
    else:
        cfg = get_config(args.arch, reduced=True)
        params = M.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    print(f"serving {cfg.name}: d_model={cfg.d_model} layers={cfg.n_layers}")

    batcher = args.batcher
    reason = paged_unsupported_reason(cfg)
    if batcher == "continuous" and reason is not None:
        print(f"paged cache unsupported for {cfg.name} ({reason}); "
              f"falling back to the wave baseline")
        batcher = "wave"

    if batcher == "continuous":
        cb = ContinuousBatcher(params, cfg, batch_slots=3, max_len=64,
                               page_size=8, max_new=8)
        cb.warmup()
        rids = []
        for i in range(args.requests):
            prompt = rng.integers(0, cfg.vocab_size, size=rng.integers(4, 12))
            rids.append(cb.submit(prompt, n_new=8))
        done = cb.run_until_done()
        st = cb.stats()
        print(f"continuous: occupancy={st['mean_occupancy']:.2f} "
              f"decode_traces={st['decode_traces']} "
              f"bucket_misses={st['bucket_misses']}")
    else:
        wb = WaveBatcher(params, cfg, batch_slots=3, max_len=64)
        # recurrent kinds (ssm/rglru) can't take ragged waves: pad tokens
        # would pollute the per-slot recurrent state, so batch equal lengths
        recurrent = set(cfg.layer_kinds) - {"attn", "local"}
        rids = []
        for i in range(args.requests):
            size = 8 if recurrent else int(rng.integers(4, 12))
            prompt = rng.integers(0, cfg.vocab_size, size=size)
            rids.append(wb.submit(prompt, n_new=8))
        done = wb.run_until_done()
    for rid in rids:
        print(f"request {rid}: generated tokens {done[rid].tolist()}")

    # temperature sampling through the same KV-cache path
    out = generate(params, cfg,
                   jax.numpy.asarray(rng.integers(0, cfg.vocab_size, (2, 6))),
                   n_new=6, temperature=0.8)
    print("sampled:", out.tokens.tolist())


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
