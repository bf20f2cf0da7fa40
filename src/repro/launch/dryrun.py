"""Multi-pod dry-run: AOT lower + compile every (architecture × input shape)
on the production mesh, proving the distribution config is coherent without
hardware, and extracting the roofline terms from the compiled artifact.

Usage:
    python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
    python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all            # every combo, subprocesses

Writes JSON artifacts to results/dryrun/.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# ^ MUST precede any jax import: jax locks the device count on first init.
#   (setdefault so tests can pre-set a smaller count before importing us.)
#   The dry-run models the production mesh on forced host devices, so it
#   and its --all children stay on the CPU and never ask for a chip.

import argparse
import dataclasses
import functools
import json
import sys
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_NAMES, INPUT_SHAPES, get_config
from repro.configs.base import ModelConfig
from repro.core import topology as topo_lib
from repro.core.decentralized import TrainState, make_train_step
from repro.core.gossip import GossipSpec
from repro.launch import roofline as roof_lib
from repro.launch import shardings as shard_lib
from repro.launch.mesh import WorkerMesh, make_worker_mesh, n_workers
from repro.models import model as M
from repro.models.params import abstract_tree
from repro.optim import momentum_sgd
from repro.serving.engine import make_serve_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

def kind_of(shape_name: str) -> str:
    return INPUT_SHAPES[shape_name]["kind"]


# long_500k is only lowered for sub-quadratic archs (DESIGN.md §decode-shapes)
def shape_applicable(cfg: ModelConfig, shape_name: str) -> bool:
    if shape_name == "long_500k":
        return cfg.subquadratic
    return True


def make_topology(name: str, M_: int, degree: int = 2, pod_size: int = 16):
    if name == "ring":
        return topo_lib.undirected_ring(M_)
    if name == "clique":
        return topo_lib.clique(M_)
    if name == "expander":
        return topo_lib.expander(M_, degree, n_candidates=10)
    if name == "dirring":
        return topo_lib.directed_ring_lattice(M_, degree)
    if name == "hypercube":
        return topo_lib.hypercube(int(np.log2(M_)))
    if name == "hier":
        # hierarchical multi-pod: inter-pod pairing ⊗ intra-pod ring —
        # cross-pod gossip collapses to one permutation class instead of the
        # flat ring's pod-spanning edges (beyond-paper §Perf). pod_size
        # follows the mesh's workers-per-pod so node index = pod-major
        # worker index (matches WorkerMesh coordinate order).
        assert M_ % pod_size == 0
        pods = M_ // pod_size
        outer = topo_lib.clique(max(pods, 1))
        return topo_lib.kronecker(outer, topo_lib.undirected_ring(pod_size))
    raise ValueError(name)


def _abstract(tree, dtype=None):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype or s.dtype), tree)


def _prepend_workers(abs_tree, Mw: int):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((Mw,) + s.shape, s.dtype), abs_tree)


def input_specs(cfg: ModelConfig, shape_name: str, mesh, mode: str):
    """ShapeDtypeStruct stand-ins for every model input of this shape."""
    spec = INPUT_SHAPES[shape_name]
    seq, gb, kind = spec["seq_len"], spec["global_batch"], spec["kind"]
    dt_tok = jnp.int32
    dt_act = jnp.dtype(cfg.compute_dtype)
    out: dict[str, Any] = {}
    if kind == "train":
        if mode == "gossip":
            Mw = n_workers(mesh)
            per = gb // Mw
            out["tokens"] = jax.ShapeDtypeStruct((Mw, per, seq), dt_tok)
            out["labels"] = jax.ShapeDtypeStruct((Mw, per, seq), dt_tok)
            if cfg.encoder_layers:
                out["enc_embeds"] = jax.ShapeDtypeStruct(
                    (Mw, per, cfg.encoder_seq, cfg.d_model), dt_act)
        else:
            out["tokens"] = jax.ShapeDtypeStruct((gb, seq), dt_tok)
            out["labels"] = jax.ShapeDtypeStruct((gb, seq), dt_tok)
            if cfg.encoder_layers:
                out["enc_embeds"] = jax.ShapeDtypeStruct(
                    (gb, cfg.encoder_seq, cfg.d_model), dt_act)
    elif kind == "prefill":
        out["tokens"] = jax.ShapeDtypeStruct((gb, seq), dt_tok)
        if cfg.encoder_layers:
            out["enc_embeds"] = jax.ShapeDtypeStruct(
                (gb, cfg.encoder_seq, cfg.d_model), dt_act)
    elif kind == "decode":
        out["tokens"] = jax.ShapeDtypeStruct((gb, 1), dt_tok)
        out["caches"] = jax.eval_shape(
            functools.partial(M.init_cache, None, cfg, gb, seq))
        if cfg.encoder_layers:
            out["memory"] = jax.ShapeDtypeStruct(
                (gb, cfg.encoder_seq, cfg.d_model), dt_act)
            out["cross_kvs"] = _cross_kv_abstract(cfg, gb)
    return out


def _cross_kv_abstract(cfg: ModelConfig, batch: int):
    dt = jnp.dtype(cfg.compute_dtype)
    segs = M.plan_segments(cfg)
    shape = (batch, cfg.encoder_seq, cfg.n_kv_heads, cfg.head_dim)
    out = []
    for seg in segs:
        pair = (jax.ShapeDtypeStruct(shape, dt), jax.ShapeDtypeStruct(shape, dt))
        if seg.scanned:
            pair = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((seg.length,) + s.shape, s.dtype), pair)
            out.append(pair)
        else:
            out.append([pair for _ in range(seg.length)])
    return out


@dataclasses.dataclass
class DryrunResult:
    arch: str
    shape: str
    mesh: str
    mode: str
    topology: str
    ok: bool
    compile_s: float
    roofline: dict | None
    collectives: dict | None
    coll_counts: dict | None
    memory_analysis: str | None
    error: str | None = None


def build_and_compile(arch: str, shape_name: str, *, multi_pod: bool = False,
                      topology: str = "ring", gossip_backend: str = "ppermute",
                      mode: str | None = None, gossip_period: int = 1,
                      microbatch: int | None = None,
                      worker_internal: str = "tp",
                      shard_activations: str | None = None,
                      parallel_block: bool = False,
                      save_hlo: str | None = None,
                      donate: bool = True,
                      reduced: bool = False,
                      hierarchical: bool = False) -> DryrunResult:
    cfg = get_config(arch, reduced=True) if reduced else get_config(arch)
    overrides = {}
    if shard_activations:
        overrides["shard_activations"] = shard_activations
    if parallel_block:
        overrides["parallel_block"] = True
    if overrides:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **overrides)
    wm = make_worker_mesh(multi_pod=multi_pod)
    mesh = wm.mesh
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    spec = INPUT_SHAPES[shape_name]
    kind = spec["kind"]
    mode = mode or (cfg.dp_mode if kind == "train" else
                    ("fsdp" if cfg.serve_sharding == "fsdp" else "serve"))
    chips = int(np.prod(list(mesh.shape.values())))
    if microbatch is None:
        # default: keep per-microbatch sequences-per-worker small enough that
        # remat carries fit HBM (found via memory_analysis bisection)
        Mw = wm.n_workers
        per = INPUT_SHAPES[shape_name]["global_batch"] // Mw if kind_of(shape_name) == "train" else 1
        microbatch = max(per // 2, 1) if kind_of(shape_name) == "train" else 1
    wa = wm.worker_axes
    t0 = time.time()

    with jax.set_mesh(mesh):
        defs = M.model_defs(cfg)
        params_abs = abstract_tree(defs, jnp.dtype(cfg.param_dtype))
        ins = input_specs(cfg, shape_name, wm, mode)

        if kind == "train":
            # hier pod_size follows the mesh: workers-per-pod, so the
            # kronecker node order == pod-major worker index order
            pod_size = (wm.n_workers // mesh.shape["pod"]
                        if multi_pod and topology == "hier" else 16)
            topo = make_topology(topology, wm.n_workers, pod_size=pod_size)
            gspec = GossipSpec.for_mesh(topo, wm, backend=gossip_backend,
                                        period=gossip_period,
                                        hierarchical=hierarchical)
            if mode == "gossip":
                params_abs = _prepend_workers(params_abs, wm.n_workers)
            pspec = shard_lib.param_pspecs(cfg, wm, mode,
                                           worker_internal=worker_internal)
            opt = momentum_sgd(1e-2, 0.9)
            loss = lambda p, b: M.loss_fn(p, cfg, b)
            step = make_train_step(loss, opt, gossip=gspec,
                                   mode=mode if mode != "serve" else "allreduce",
                                   mesh=wm, compute_stats=False,
                                   microbatch=microbatch,
                                   param_specs=pspec if mode == "gossip" else None)
            state_abs = TrainState(jax.ShapeDtypeStruct((), jnp.int32),
                                   params_abs, params_abs)  # momentum mirrors
            state_spec = shard_lib.state_pspecs(cfg, wm, params_abs, pspec)
            batch_spec = shard_lib.batch_pspecs(cfg, wm, "train", mode,
                                                worker_internal=worker_internal)
            batch_spec = {k: batch_spec[k] for k in ins}
            fn = jax.jit(
                step,
                in_shardings=(state_spec, batch_spec),
                out_shardings=(state_spec, None),
                donate_argnums=(0,) if donate else ())
            lowered = fn.lower(state_abs, ins)
            n_tokens = spec["global_batch"] * spec["seq_len"]
        elif kind == "prefill":
            pspec = shard_lib.param_pspecs(
                cfg, mesh, "fsdp" if mode == "fsdp" else "allreduce")
            gb = spec["global_batch"]
            b_ax = shard_lib._div(gb, mesh, wa[0] if len(wa) == 1 else wa)

            if cfg.encoder_layers:
                def fn_prefill(p, tokens, enc_embeds):
                    logits, caches, ckv, mem = M.prefill(
                        p, cfg, tokens, max_len=spec["seq_len"], enc_embeds=enc_embeds)
                    return logits, caches
                args = (params_abs, ins["tokens"], ins["enc_embeds"])
                in_sh = (pspec, P(b_ax, None), P(b_ax, None, None))
            else:
                def fn_prefill(p, tokens):
                    logits, caches, _, _ = M.prefill(p, cfg, tokens,
                                                     max_len=spec["seq_len"])
                    return logits, caches
                args = (params_abs, ins["tokens"])
                in_sh = (pspec, P(b_ax, None))
            fn = jax.jit(fn_prefill,
                         in_shardings=in_sh)
            lowered = fn.lower(*args)
            n_tokens = spec["global_batch"] * spec["seq_len"]
        else:  # decode
            pspec = shard_lib.param_pspecs(
                cfg, mesh, "fsdp" if mode == "fsdp" else "allreduce")
            gb = spec["global_batch"]
            serve = make_serve_step(cfg)
            cache_spec = shard_lib.cache_pspecs(cfg, mesh, gb)
            b_ax = shard_lib._div(gb, mesh, wa[0] if len(wa) == 1 else wa)
            if cfg.encoder_layers:
                ckv_spec = shard_lib.cross_kv_pspecs(cfg, mesh, gb)
                fn = jax.jit(serve, in_shardings=(
                    pspec, cache_spec, P(b_ax, None), P(b_ax, None, None), ckv_spec),
                    out_shardings=(None, cache_spec),
                    donate_argnums=(1,) if donate else ())
                lowered = fn.lower(params_abs, ins["caches"], ins["tokens"],
                                   ins["memory"], ins["cross_kvs"])
            else:
                fn = jax.jit(serve, in_shardings=(
                    pspec, cache_spec, P(b_ax, None)),
                    out_shardings=(None, cache_spec),
                    donate_argnums=(1,) if donate else ())
                lowered = fn.lower(params_abs, ins["caches"], ins["tokens"])
            n_tokens = spec["global_batch"]  # one token per sequence

        compiled = lowered.compile()
        compile_s = time.time() - t0
        try:
            mem = compiled.memory_analysis()
            mem_str = str(mem)
        except Exception as e:  # pragma: no cover
            mem_str = f"unavailable: {e}"
        # the production mesh is a v5e pod, modelled on host devices
        terms = roof_lib.analyze(compiled, cfg, chips=chips, n_tokens=n_tokens,
                                 device_kind=roof_lib.V5E,
                                 kind="train" if kind == "train" else "serve")
        from repro.launch import hlo_cost as hc_lib
        hlo = compiled.as_text()
        hc = hc_lib.analyze_hlo(hlo)
        coll = hc.coll_bytes
        counts = hc.coll_counts
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(hlo)

    return DryrunResult(
        arch=arch, shape=shape_name, mesh=mesh_name, mode=mode,
        topology=topology if kind == "train" else "-", ok=True,
        compile_s=compile_s, roofline=terms.as_dict(), collectives=coll,
        coll_counts=counts, memory_analysis=mem_str)


def run_one(arch: str, shape_name: str, **kw) -> DryrunResult:
    try:
        return build_and_compile(arch, shape_name, **kw)
    except Exception:
        return DryrunResult(
            arch=arch, shape=shape_name,
            mesh="multipod_2x16x16" if kw.get("multi_pod") else "pod_16x16",
            mode=kw.get("mode") or "?", topology=kw.get("topology", "ring"),
            ok=False, compile_s=0.0, roofline=None, collectives=None,
            coll_counts=None, memory_analysis=None,
            error=traceback.format_exc())


def save_result(res: DryrunResult, tag: str = "") -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = f"{res.arch}__{res.shape}__{res.mesh}{('__' + tag) if tag else ''}.json"
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(dataclasses.asdict(res), f, indent=1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--gossip-backend", default="ppermute")
    ap.add_argument("--gossip-period", type=int, default=1)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--worker-internal", default="tp", choices=("tp", "dp", "fsdp"))
    ap.add_argument("--shard-activations", default=None, nargs="?", const="model")
    ap.add_argument("--parallel-block", action="store_true")
    ap.add_argument("--mode", default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--hierarchical", action="store_true",
                    help="stage the gossip mix as intra-pod (ICI) then "
                         "inter-pod (DCI) rounds (GossipSpec.hierarchical)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI lane: host-forced multi-pod WorkerMesh, reduced "
                         "nemotron, gossip mode (technique ON) must lower")
    ap.add_argument("--hier-smoke", action="store_true",
                    help="CI lane: hier topology × model sharding on a "
                         "host-forced multi-pod mesh; HLO-assert cross-pod "
                         "permutes ride only the pod (DCI) axis while "
                         "intra-pod stages stay ICI")
    args = ap.parse_args(argv)

    if args.smoke:
        # Shrink the production mesh to whatever the forced host device count
        # allows (set XLA_FLAGS=--xla_force_host_platform_device_count=8).
        import repro.launch.mesh as mesh_lib
        n = len(jax.devices())
        assert n >= 8, f"smoke lane needs ≥8 forced host devices, got {n}"
        mesh_lib.MULTI_POD = (2, 2, 2)
        INPUT_SHAPES.setdefault(
            "train_smoke", dict(seq_len=64, global_batch=8, kind="train"))
        res = run_one(args.arch or "nemotron-4-340b", "train_smoke",
                      multi_pod=True, topology=args.topology,
                      gossip_backend="fused", mode="gossip", reduced=True)
        if not res.ok:
            print(res.error)
            return 2
        counts = res.coll_counts or {}
        wm = make_worker_mesh(multi_pod=True)  # same factorization run_one used
        print(f"SMOKE OK {res.arch} gossip lowering on multipod "
              f"{mesh_lib.MULTI_POD}: {wm.describe()}; "
              f"collective-permutes={counts.get('collective-permute', 0)} "
              f"cp_bytes={int((res.collectives or {}).get('collective-permute', 0))}")
        assert counts.get("collective-permute", 0) > 0, \
            "gossip mode must lower to collective-permutes"
        return 0

    if args.hier_smoke:
        # ROADMAP "hier × model sharding": the staged hierarchical mix on the
        # multi-pod mesh must produce ONLY pure link classes — intra-pod
        # stages ride ICI, the inter-pod stage rides the pod (DCI) axis —
        # and no permute may mix the two.
        import tempfile

        import repro.launch.mesh as mesh_lib
        from repro.launch import hlo_cost as hc_lib
        n = len(jax.devices())
        assert n >= 8, f"hier-smoke lane needs ≥8 forced host devices, got {n}"
        mesh_lib.MULTI_POD = (2, 2, 2)
        INPUT_SHAPES.setdefault(
            "train_smoke", dict(seq_len=64, global_batch=8, kind="train"))
        hlo_path = os.path.join(tempfile.mkdtemp(), "hier_smoke.hlo")
        res = run_one(args.arch or "nemotron-4-340b", "train_smoke",
                      multi_pod=True, topology="hier",
                      gossip_backend=args.gossip_backend, mode="gossip",
                      reduced=True, hierarchical=True, save_hlo=hlo_path)
        if not res.ok:
            print(res.error)
            return 2
        with open(hlo_path) as f:
            hlo = f.read()
        wm = make_worker_mesh(multi_pod=True)
        classes = hc_lib.permute_link_classes(hlo, wm)
        print(f"HIER SMOKE {res.arch} on multipod {mesh_lib.MULTI_POD}: "
              f"{wm.describe()}; permute classes ici={classes['ici']} "
              f"dci={classes['dci']} mixed={classes['mixed']}")
        assert classes["ici"] > 0, "intra-pod gossip stage must lower to ICI permutes"
        assert classes["dci"] > 0, "inter-pod gossip stage must lower to DCI permutes"
        assert classes["mixed"] == 0, (
            "hierarchical gossip must not emit pod-crossing permutes that also "
            f"move along non-pod axes: {classes['ops']}")
        return 0

    if args.all:
        import subprocess
        fails = []
        for multi in (False, True):
            for arch in ARCH_NAMES:
                cfg = get_config(arch)
                for shape in INPUT_SHAPES:
                    if not shape_applicable(cfg, shape):
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape]
                    if multi:
                        cmd.append("--multi-pod")
                    print(">>", " ".join(cmd), flush=True)
                    rc = subprocess.call(cmd)
                    if rc:
                        fails.append((arch, shape, multi))
        print("FAILURES:", fails if fails else "none")
        return 1 if fails else 0

    assert args.arch and args.shape
    cfg = get_config(args.arch)
    if not shape_applicable(cfg, args.shape):
        print(f"SKIP {args.arch} × {args.shape}: full attention at 500k "
              f"(documented in DESIGN.md)")
        return 0
    res = run_one(args.arch, args.shape, multi_pod=args.multi_pod,
                  topology=args.topology, gossip_backend=args.gossip_backend,
                  gossip_period=args.gossip_period, microbatch=args.microbatch,
                  worker_internal=args.worker_internal,
                  shard_activations=args.shard_activations,
                  parallel_block=args.parallel_block,
                  mode=args.mode, save_hlo=args.save_hlo,
                  hierarchical=args.hierarchical)
    path = save_result(res, args.tag)
    if res.ok:
        r = res.roofline
        print(f"OK {res.arch} × {res.shape} × {res.mesh} [{res.mode}] "
              f"compile={res.compile_s:.1f}s  "
              f"t_comp={r['t_compute_s']:.4f}s t_mem={r['t_memory_s']:.4f}s "
              f"t_coll={r['t_collective_s']:.4f}s -> {r['bottleneck']}")
        print("memory_analysis:", (res.memory_analysis or "")[:400])
        print("saved:", path)
        return 0
    print(f"FAIL {res.arch} × {res.shape} × {res.mesh}")
    print(res.error)
    print("saved:", path)
    return 2


if __name__ == "__main__":
    sys.exit(main())
