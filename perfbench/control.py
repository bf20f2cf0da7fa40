"""Readings that the limits of ``correct`` are set from: the program's
own, the control's and the planted faults', at the cell's own size.

    python3 perfbench/control.py --workload <cell> --seeds 1 2 3 \
        [--program-seeds 4 5 ...] [--dump DIR]

For every seed of ``--program-seeds`` it drives the program's compiled step
through the checked steps, as a run's set-up does, and keeps the readings;
the program's state is then freed. For every seed of either list it runs
the float32 reference and prints the program's gaps against it; for every
seed of ``--seeds`` also the control's (the same reference with every
matmul operand rounded to fp8, the precision below the configuration's
bfloat16) and those of the reference with each fault planted
(``drop_half``: half of every row left out of the loss; ``no_mix``: the
exchange left out; ``no_momentum``: the update without its momentum). One
JSON line per seed. ``--dump`` keeps every (workers, leaves) array read.
The benchmark's own runs never run this; it is for the chip.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def program_found(cell, seeds) -> dict:
    """{seed: the program's readings of its checked steps}."""
    import jax

    from harness import check, tokens
    from harness.program import build, seed_key

    tr = cell.traffic
    prog = build(cell, jax.devices()[:cell.chips])
    read = check.program_readings(prog.init_replica)
    out, step = {}, None
    with prog.mesh_ctx():
        for seed in seeds:
            key = seed_key(seed)
            state = prog.init_state(prog.replicate(prog.init_replica(key)))
            batches = [prog.batch(b) for b in tokens.batch_pool(
                seed, tr, cell.model["vocab_size"])[:tr["checked_steps"]]]
            if step is None:
                step = prog.step.lower(state, batches[0]).compile()
            state, out[seed] = read(step, state, batches, key)
            del state, batches
    del step
    jax.clear_caches()
    return out


def readings(cell, seed: int, variants, found=None, dump=None) -> dict:
    import jax
    import numpy as np

    from harness import check, tokens
    from harness.program import build, seed_key
    from references.train_step import reference_readings

    tr, m = cell.traffic, cell.model
    # the reference is one process on one device wherever the program runs;
    # only the parameters' layout is taken from the program
    one = dataclasses.replace(cell, traffic=dict(tr, placement="vmap"))
    prog = build(one, jax.devices()[:1])
    replica = prog.init_replica(seed_key(seed))
    batches = tokens.batch_pool(seed, tr, m["vocab_size"])[
        :tr["checked_steps"]]
    run = lambda **kw: reference_readings(
        cell.reference(), m, tr, replica, batches, **kw)
    ref = run()
    out, arrays = {"seed": seed}, {"reference": ref}
    if found is not None:
        out["program"] = check.gaps(found, ref)
        arrays["program"] = found
    for name, kw in variants:
        t = time.perf_counter()
        arrays[name] = run(**kw)
        out[name] = check.gaps(arrays[name], ref)
        out[name]["seconds"] = time.perf_counter() - t
    if dump:
        np.savez(os.path.join(dump, f"{cell.name}-{seed}.npz"), **{
            f"{k}.{part}": v[part] for k, v in arrays.items() for part in v})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*",
                    default=["drop_half", "no_mix", "no_momentum"])
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".perfbench_cache", "jax"))
    from harness import spec

    cell = spec.load(args.workload, ROOT)
    variants = [("control_fp8", {"prec": "fp8"})]
    variants += [(f, {"fault": f}) for f in args.faults]
    found = program_found(cell, args.program_seeds)
    if args.dump:
        os.makedirs(args.dump, exist_ok=True)
    for seed in dict.fromkeys(args.program_seeds + args.seeds):
        print(json.dumps(readings(
            cell, seed, variants if seed in args.seeds else [],
            found.get(seed), args.dump)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
