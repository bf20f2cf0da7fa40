"""Smoke run of decentralized (gossip) training on TPU, through the normal
entry points, at the published widths of granite-3-2b.

    python chip_smoke.py                 # one chip: 4 ring workers vmapped
    python chip_smoke.py --four-chips    # four chips: one ring worker each,
                                         # against the all-reduce baseline
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny [--four-chips]
        # rehearsal at toy width; it runs every phase and then refuses to
        # report success, because the platform is not a TPU

One chip: ``repro.train.train()`` runs a few steps of M=4 workers on an
undirected ring with the fused gossip bus (the compiled ``gossip_mix``
kernel), then one step from a fixed state is checked against the
``backend="einsum"`` step, and the consensus model answers requests
through ``ContinuousBatcher``. Four chips: the same workers, one per chip,
gossip over ICI with bulk collective-permutes; the step is timed against
``mode="allreduce"`` and checked against the meshless einsum step.

Every check prints a PASS or FAIL line. The last line of standard output is
``{"ok": true, "device": {...}}``, printed only on a TPU when every check
passed; otherwise the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SEED = 0
WORKERS = 4
# granite-3-2b at published widths (d_model 2048, 32/8 heads, d_ff 8192,
# vocab 49155, bf16). One chip holds 4 replicas with momentum, grads and the
# packed bus: at 2 layers the compiled step needs 13.2 GB of the 16 GiB
# (compile rehearsal for a described v5e), so depth is cut to 2.
LAYERS = 2
SEQ = 2048
STEPS = 5
# Fused vs einsum step, as max|diff| over the leaf's largest magnitude m.
# The einsum step rounds the topology weights to bf16 (≤ 2^-9·m) and rounds
# its bf16 partial sums and the update add (≤ 2^-8·m each, up to four
# times); the fused kernel rounds once, in fp32. Eight half-ulps, 2^-5·m,
# bound both; a wrong neighbor or weight errs at the scale of m itself.
BF16_RTOL = 2.0 ** -5


def _log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """PASS/FAIL record; any FAIL makes the run fail."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        _log(f"{'PASS' if ok else 'FAIL'} {name}{': ' + detail if detail else ''}")
        if not ok:
            self.failed.append(name)
        return ok


def _setup(tiny: bool):
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.data import WorkerBatcher, pad_to_equal, random_split, token_stream

    if tiny:
        cfg = get_config("granite-3-2b", reduced=True, n_layers=2)
        seq = 64
    else:
        cfg = get_config("granite-3-2b", n_layers=LAYERS)
        seq = SEQ
    toks, _ = token_stream(S=8 * WORKERS, seq_len=seq, vocab=cfg.vocab_size,
                           seed=SEED)
    parts = pad_to_equal(random_split(len(toks), WORKERS, seed=SEED))
    batcher = WorkerBatcher((toks,), parts, batch_size=1, seed=SEED)

    def next_batch():
        (t,) = batcher.next()                     # (M, 1, seq + 1)
        return {"tokens": jnp.asarray(t)}

    _log(f"model {cfg.name}: d_model={cfg.d_model} heads={cfg.n_heads}/"
         f"{cfg.n_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
         f"vocab={cfg.vocab_size} layers={cfg.n_layers} {cfg.param_dtype}; "
         f"workers={WORKERS} seq={seq} batch/worker=1")
    return cfg, next_batch, np


def _init_params(cfg, workers: int | None = WORKERS):
    """Seeded params, replicated over ``workers`` (None: one copy)."""
    import jax

    from repro.core.decentralized import replicate_for_workers
    from repro.models import model as M

    p = M.init(jax.random.PRNGKey(SEED), cfg)
    return p if workers is None else replicate_for_workers(p, workers)


def _distinct_params(cfg):
    """Each worker from its own seed. A step from replicated params mixes
    equal values, so it cannot tell a wrong neighbor or weight; from
    distinct ones every mixing error shows at the scale of the params."""
    import jax

    from repro.models import model as M

    keys = jax.random.split(jax.random.PRNGKey(SEED), WORKERS)
    return jax.jit(jax.vmap(lambda k: M.init(k, cfg)))(keys)


def _to_host(tree):
    import jax
    import numpy as np

    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]


def _max_rel_err(got: list, ref: list) -> float:
    """max over leaves of max|got − ref| / max|ref|."""
    import numpy as np

    worst = 0.0
    for a, b in zip(got, ref):
        scale = float(np.max(np.abs(b))) or 1.0
        worst = max(worst, float(np.max(np.abs(a - b))) / scale)
    return worst


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


def _einsum_step(cfg, state, batch):
    """One meshless ``backend="einsum"`` step: the numerics reference."""
    import jax

    from repro.core import topology as T
    from repro.core.decentralized import make_train_step
    from repro.core.gossip import GossipSpec
    from repro.models import model as M
    from repro.optim import momentum_sgd

    spec = GossipSpec(topology=T.undirected_ring(WORKERS), backend="einsum")
    step = make_train_step(lambda p, b: M.loss_fn(p, cfg, b),
                           momentum_sgd(1e-2, 0.9), gossip=spec)
    new, _ = jax.jit(step, donate_argnums=(0,))(state, batch)
    return _to_host(new.params)


def one_chip(tiny: bool, check: Checks) -> None:
    import jax

    from repro.core import topology as T
    from repro.core.decentralized import init_state, make_train_step
    from repro.core.gossip import GossipSpec
    from repro.models import model as M
    from repro.optim import momentum_sgd
    from repro.serving import ContinuousBatcher
    from repro.train import train

    cfg, next_batch, np = _setup(tiny)
    dev = jax.devices()[0]
    loss = lambda p, b: M.loss_fn(p, cfg, b)
    opt = momentum_sgd(1e-2, 0.9)
    spec = GossipSpec(topology=T.undirected_ring(WORKERS), backend="fused")

    # -- compile: the fused step, from shapes --------------------------------
    _log("== phase compile")
    step = make_train_step(loss, opt, gossip=spec)
    state_abs = jax.eval_shape(lambda: init_state(_init_params(cfg), opt))
    batch = next_batch()
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0,)).lower(state_abs, batch).compile()
    _log(f"compile_s {time.perf_counter() - t0:.2f}")
    mem = compiled.memory_analysis()
    if mem is not None:
        _log(f"compiled memory: arguments {mem.argument_size_in_bytes / 1e9:.3f} GB"
             f" temp {mem.temp_size_in_bytes / 1e9:.3f} GB")
    n_kernels = compiled.as_text().count("tpu_custom_call")
    check("step HLO holds the compiled gossip_mix kernel (tpu_custom_call)",
          n_kernels > 0, f"{n_kernels} occurrences")

    # -- train: the main path, a few steps ------------------------------------
    _log("== phase train")
    state, hist = train(loss, _init_params(cfg), opt,
                        iter(next_batch, None), steps=STEPS, gossip=spec,
                        log_every=1, verbose=False)
    for k, (l, dt) in enumerate(zip(hist.loss, hist.step_time)):
        _log(f"step {k} loss {l:.5f} step_s {dt:.4f}")
    check("loss finite at every step", bool(np.all(np.isfinite(hist.loss))),
          f"{hist.loss[0]:.4f} -> {hist.loss[-1]:.4f}")
    consensus = jax.tree.map(lambda x: x.mean(0).astype(x.dtype), state.params)
    del state
    _log(f"peak_bytes_in_use after train: {_peak_bytes(dev)}")

    # -- reference: fused vs einsum, one step from the same distinct workers --
    _log("== phase reference")
    batch = next_batch()
    state = jax.block_until_ready(init_state(_distinct_params(cfg), opt))
    t0 = time.perf_counter()
    new, _ = compiled(state, batch)
    jax.block_until_ready(new)
    _log(f"fused step_s {time.perf_counter() - t0:.4f} (compiled step, "
         "block_until_ready)")
    fused = _to_host(new.params)
    del new
    ref = _einsum_step(cfg, init_state(_distinct_params(cfg), opt), batch)
    err = _max_rel_err(fused, ref)
    check("fused step equals einsum step", err <= BF16_RTOL,
          f"max|fused-einsum|/max|einsum| = {err:.3e} <= {BF16_RTOL:.3e}")

    # -- serve: the consensus model through ContinuousBatcher -----------------
    _log("== phase serve")
    max_len, (lo, hi), max_new = (128, (20, 60), 16) if tiny else \
        (2048, (260, 500), 64)
    rng = np.random.default_rng(SEED)
    srv = ContinuousBatcher(consensus, cfg, batch_slots=8, max_len=max_len,
                            max_new=max_new)
    want = {}
    for _ in range(6):
        prompt = rng.integers(1, cfg.vocab_size, size=int(rng.integers(lo, hi)))
        n_new = int(rng.integers(max_new // 4, max_new + 1))
        want[srv.submit(prompt, n_new)] = n_new
    t0 = time.perf_counter()
    done = srv.run_until_done()
    wall = time.perf_counter() - t0
    got = {rid: len(done.get(rid, ())) for rid in want}
    in_vocab = all(int(np.max(done[r])) < cfg.vocab_size for r in done)
    check("every request returns its n_new tokens",
          got == want and in_vocab,
          f"{len(done)} requests, {sum(got.values())} tokens in {wall:.2f} s "
          "(compiles included)")
    _log(f"peak_bytes_in_use: {_peak_bytes(dev)}")


def four_chips(tiny: bool, check: Checks) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import bus
    from repro.core import topology as T
    from repro.core.decentralized import TrainState, init_state, make_train_step
    from repro.core.gossip import GossipSpec
    from repro.launch.hlo_cost import analyze_hlo
    from repro.launch.mesh import WorkerMesh
    from repro.models import model as M
    from repro.optim import momentum_sgd

    devices = jax.devices()
    if not check("four devices", len(devices) >= WORKERS,
                 f"found {len(devices)}"):
        return
    cfg, next_batch, np = _setup(tiny)
    mesh = jax.make_mesh((WORKERS,), ("data",), devices=devices[:WORKERS],
                         axis_types=(jax.sharding.AxisType.Auto,))
    wm = WorkerMesh.from_mesh(mesh)
    loss = lambda p, b: M.loss_fn(p, cfg, b)
    opt = momentum_sgd(1e-2, 0.9)
    spec = GossipSpec.for_mesh(T.undirected_ring(WORKERS), wm, backend="fused")
    workers = NamedSharding(mesh, P("data"))
    replicated = NamedSharding(mesh, P())

    def timed(fn, state, batch, n=5):
        state, _ = fn(state, batch)                   # warm
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for _ in range(n):
            state, m = fn(state, batch)
        jax.block_until_ready((state, m))
        return (time.perf_counter() - t0) / n

    # -- ring: one worker per chip, sharded fused bus --------------------------
    _log("== phase ring")
    batch = next_batch()
    with jax.set_mesh(mesh):
        s0 = init_state(_distinct_params(cfg), opt)
        state = TrainState(jax.device_put(s0.step, replicated),
                           jax.device_put(s0.params, workers),
                           jax.device_put(s0.opt_state, workers))
        del s0
        b = jax.device_put(batch, workers)
        # no gradient statistics: their worker means are full-size
        # all-reduces, which would time the statistics, not the exchange
        fn = jax.jit(make_train_step(loss, opt, gossip=spec, mesh=wm,
                                     compute_stats=False),
                     donate_argnums=(0,))
        t0 = time.perf_counter()
        compiled = fn.lower(state, b).compile()
        _log(f"ring compile_s {time.perf_counter() - t0:.2f}")
        counts = analyze_hlo(compiled.as_text()).coll_counts
        _log(f"ring step collectives: {counts}")
        n_cp = counts["collective-permute"]
        want = bus.bulk_collectives_per_step(spec)
        check("collective-permutes per step == bulk_collectives_per_step",
              n_cp == want, f"{n_cp} in HLO, {want} expected")
        state, _ = compiled(state, b)
        leaf = jax.tree.leaves(state.params)[0]
        owners = {s.device for s in leaf.addressable_shards}
        check("one worker per device",
              len(owners) == WORKERS and leaf.sharding.device_set == set(
                  devices[:WORKERS])
              and all(s.data.shape[0] == 1 for s in leaf.addressable_shards),
              f"{len(owners)} devices hold shards")
        ring = _to_host(state.params)
        ring_s = timed(compiled, state, b)
        del state
        _log(f"ring step_s {ring_s:.4f}")

        # -- all-reduce baseline: one replicated copy, batch over the chips ----
        _log("== phase allreduce")
        ar_state = jax.device_put(init_state(_init_params(cfg, None), opt),
                                  replicated)
        ar_batch = jax.device_put(
            {"tokens": batch["tokens"].reshape(-1, batch["tokens"].shape[-1])},
            workers)
        ar = jax.jit(make_train_step(loss, opt, mode="allreduce", mesh=wm,
                                     compute_stats=False),
                     donate_argnums=(0,))
        ar_s = timed(ar, ar_state, ar_batch)
        del ar_state
        _log(f"allreduce step_s {ar_s:.4f}")
    _log(f"ring/allreduce step time {ring_s / ar_s:.3f}")

    # -- reference: the meshless einsum step from the same state --------------
    _log("== phase reference")
    ref = _einsum_step(cfg, init_state(_distinct_params(cfg), opt), batch)
    err = _max_rel_err(ring, ref)
    check("ring step equals meshless einsum step", err <= BF16_RTOL,
          f"max|ring-einsum|/max|einsum| = {err:.3e} <= {BF16_RTOL:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="one ring worker per chip on four chips, against "
                         "the all-reduce baseline; runs no other phase")
    ap.add_argument("--tiny", action="store_true",
                    help="toy width for a rehearsal off the chip")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    try:
        from repro.launch.cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing ({e})", file=sys.stderr)
        return 2
    import jax

    use_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _log(f"device: {device}")
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}); run with --tiny for a "
              "rehearsal", file=sys.stderr)
        return 1
    check = Checks()
    (four_chips if args.four_chips else one_chip)(args.tiny, check)
    check("platform is tpu", on_tpu, f"JAX found {dev.platform!r}")
    if check.failed:
        print(f"chip_smoke: FAILED {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
