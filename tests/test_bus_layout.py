"""Bus layout v2: tile-aligned row planning + row-split of indivisible leaves.

Property layer (hypothesis when installed, deterministic adversarial cases
always): pack → unpack round-trips BIT-exactly for every shard factor k,
dtype mix, and awkward row count — prime rows, single-row leaves, zero-size
leaves forming an empty dtype group. The HLO layer (slow lane) pins the
byte contract on a GQA-shaped tree: replicated-leaf collective bytes == 0
and per-device cp bytes within 2% of the ideal 1/k at k ∈ {4, 16}.
"""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_in_subprocess
from repro.core import bus
from repro.core import topology as T
from repro.core.gossip import GossipSpec

BLK = dict(block_r=32)   # plan_layout tile-height cap; cols are fixed to LANE

KS = [1, 2, 4, 16]


def _bits(x):
    return np.asarray(x).view(np.uint8)


def _assert_tree_bit_equal(a, b):
    for (pa, xa), (pb, xb) in zip(
            jax.tree_util.tree_flatten_with_path(a)[0],
            jax.tree_util.tree_flatten_with_path(b)[0]):
        assert pa == pb
        assert xa.dtype == xb.dtype and xa.shape == xb.shape, (pa, xa.shape)
        assert np.array_equal(_bits(xa), _bits(xb)), pa


def _assert_slots_tiled(layout):
    """Every slot is whole sublane tiles of LANE-wide rows, starting on a
    tile boundary right after the previous one: padding under one sublane
    tile per slot, plus the group's tail (one tile of zeros when every leaf
    of the group is empty)."""
    for g in layout.groups:
        sub = bus.sublane_rows(g.dtype)
        assert g.cols == bus.LANE and g.rows % sub == 0
        off = 0
        for slot in g.slots:
            assert slot.offset == off and slot.offset % sub == 0
            assert slot.rows % sub == 0
            assert slot.chunk <= slot.rows * bus.LANE < slot.chunk + sub * bus.LANE
            off += slot.rows
        assert g.n == sum(slot.chunk for slot in g.slots)
        assert g.rows == (off if off else sub)


def _slot_order(leaf, lead_ndim=0):
    """The leaf as its slot holds it: row-major, except that a lane-ragged
    minor dim after whole lanes is swapped with it (the TPU's own layout of
    such a leaf), then flattened past the lead dims."""
    leaf = np.asarray(leaf)
    if leaf.ndim - lead_ndim >= 2 and leaf.shape[-1] % bus.LANE and \
            not leaf.shape[-2] % bus.LANE:
        leaf = np.swapaxes(leaf, -1, -2)
    return leaf.reshape(leaf.shape[:lead_ndim] + (-1,))


def _assert_slots_hold_leaves(tree, layout, bufs, k, s, lead_ndim=0):
    """Shard s's rows of each slot hold its share of the leaf in slot order:
    elements [s·rows·LANE, (s+1)·rows·LANE) of it, zero-padded."""
    leaves = layout.treedef.flatten_up_to(tree)
    for g, buf in zip(layout.groups, bufs):
        buf = np.asarray(buf)
        lead = buf.shape[:lead_ndim]
        for slot in g.slots:
            parts = 1 if slot.sharded else k
            span = slot.rows * bus.LANE
            flat = _slot_order(leaves[slot.leaf_id], lead_ndim)
            want = np.zeros(lead + (parts * span,), flat.dtype)
            want[..., :flat.shape[-1]] = flat
            want = want[..., (s % parts) * span:(s % parts + 1) * span]
            got = buf[..., slot.offset:slot.offset + slot.rows, :]
            assert np.array_equal(_bits(got.reshape(lead + (-1,))),
                                  _bits(want)), slot


def _roundtrip_row_split(tree, k):
    """Emulate the k model shards host-side: every leaf row-split (the local
    value is the full leaf — the shard_map body's view of replicated leaves),
    each shard packs its row range, unpack gathers the shards back."""
    layout = bus.plan_layout(tree, lead_ndim=0, shards=k, **BLK)
    _assert_slots_tiled(layout)
    shard_bufs = [bus.pack(tree, layout, lead_ndim=0, shard_index=s)
                  for s in range(k)]
    for s in range(k):
        _assert_slots_hold_leaves(tree, layout, shard_bufs[s], k, s)
    spans = {}
    for gi, g in enumerate(layout.groups):
        if k > 1 and g.split_off < g.split_end:
            spans[gi] = jnp.stack([shard_bufs[s][gi][g.split_off:g.split_end]
                                   for s in range(k)])
    span_iter = iter([spans[gi] for gi in sorted(spans)])
    return bus.unpack(shard_bufs[0], layout, lead_ndim=0,
                      gather=lambda _span: next(span_iter)), layout


def _rand_tree(shapes_dtypes, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), max(len(shapes_dtypes), 1))
    return {
        f"leaf{i}": jax.random.normal(ks[i], shape, jnp.float32).astype(dt)
        for i, (shape, dt) in enumerate(shapes_dtypes)
    }


# ---------------------------------------------------------------------------
# Deterministic adversarial cases (always run — the fast-lane floor)
# ---------------------------------------------------------------------------

ADVERSARIAL = [
    # prime row counts: 127 rows exactly, plus a 13-elem ragged tail leaf
    [((127 * 128,), jnp.float32), ((13,), jnp.float32)],
    # single-row / sub-row leaves straddling the lane boundary
    [((128,), jnp.float32), ((5,), jnp.float32), ((129,), jnp.float32)],
    # dtype mix: bf16 group rows plan on 16-sublane tiles, fp32 on 8
    [((70, 41), jnp.float32), ((33, 5), jnp.bfloat16), ((257,), jnp.bfloat16)],
    # empty dtype group: the only bf16 leaf has zero elements
    [((64, 3), jnp.float32), ((0,), jnp.bfloat16)],
    # scalar-ish leaves only — payload smaller than one sublane tile
    [((1,), jnp.float32), ((2, 1), jnp.float32)],
    # the chip cells' leaf shapes in small: a stacked lane-ragged minor dim
    # after whole lanes (mamba2 in_proj, 2560 × 10576, 10576 = 80 + 128·82)
    # and one after a ragged dim, a row count that is not whole sublane
    # tiles (the 49155-row vocab table), heads under one lane (granite's
    # 32 × 64), per-head scalars and 1-D scales, f32 and bf16
    [((2, 128, 80 + 128), jnp.bfloat16), ((3, 16, 80 + 128), jnp.bfloat16),
     ((35, 256), jnp.bfloat16),
     ((2, 24, 4, 64), jnp.bfloat16), ((5, 80), jnp.bfloat16),
     ((80,), jnp.float32), ((35, 80 + 128), jnp.float32), ((256,), jnp.float32)],
]


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("case", range(len(ADVERSARIAL)))
def test_row_split_roundtrip_bit_exact(case, k):
    tree = _rand_tree(ADVERSARIAL[case], seed=case)
    back, layout = _roundtrip_row_split(tree, k)
    _assert_tree_bit_equal(back, tree)
    assert layout.shards == k


@pytest.mark.parametrize("k", [2, 4, 16])
def test_mixed_sharded_and_row_split_leaves(k):
    """Tensor-sharded leaves pack their local shard, the rest row-split —
    the exact shard_map-body contract of `_mix_pytree_model_sharded`."""
    full_w = jax.random.normal(jax.random.PRNGKey(7), (48, 16 * k))
    v = jax.random.normal(jax.random.PRNGKey(8), (33, 5))   # indivisible
    locals_ = [{"v": v, "w": full_w[:, s * 16:(s + 1) * 16]} for s in range(k)]
    flags = (False, True)   # flatten order: 'v' (row-split), 'w' (sharded)
    layout = bus.plan_layout(locals_[0], lead_ndim=0, shards=k,
                             leaf_sharded=flags, **BLK)
    shard_bufs = [bus.pack(locals_[s], layout, lead_ndim=0, shard_index=s)
                  for s in range(k)]
    (g,) = layout.groups
    assert g.split_off == 0, "row-split leaves pack at the HEAD of the group"
    span = jnp.stack([shard_bufs[s][0][g.split_off:g.split_end]
                      for s in range(k)])
    for s in range(k):
        back = bus.unpack(shard_bufs[s], layout, lead_ndim=0,
                          gather=lambda _: span)
        _assert_tree_bit_equal(back, locals_[s])


@pytest.mark.parametrize("k", KS)
def test_pass1_rows_are_whole_tiles_per_shard(k):
    """Pass-1 invariant: every slot is whole sublane tiles per shard and
    starts on a tile boundary, so per-shard rows are whole sublane tiles —
    the global buffer satisfies rows % (sublane(dtype)·k) == 0 because every
    shard packs the SAME (rows, cols) buffer shape (SPMD uniformity) — and
    per-shard padding is under one sublane tile per slot."""
    tree = _rand_tree(ADVERSARIAL[2], seed=11)
    layout = bus.plan_layout(tree, lead_ndim=0, shards=k, **BLK)
    _assert_slots_tiled(layout)
    for g in layout.groups:
        sub = bus.sublane_rows(g.dtype)
        assert g.cols == bus.LANE
        assert g.rows % sub == 0
        assert g.rows * g.cols - g.n < sub * bus.LANE * len(g.slots)
    # every shard's packed buffers have identical shapes/dtypes (the global
    # buffer is k equal tile-aligned row blocks, one per model shard)
    shapes = {s: [(b.shape, b.dtype) for b in
                  bus.pack(tree, layout, lead_ndim=0, shard_index=s)]
              for s in range(k)}
    assert all(shapes[s] == shapes[0] for s in range(k))


def test_row_tile_matches_worker_mesh_helper():
    from repro.launch.mesh import WorkerMesh

    wm = WorkerMesh(mesh=None, worker_axes=("data",), model_axis=None)
    assert wm.bus_row_tile(jnp.float32) == 8        # model_factor == 1
    assert bus.sublane_rows(jnp.bfloat16) == 16
    assert bus.sublane_rows(jnp.int8) == 32


def test_layout_cache_keyed_on_shards_and_flags():
    tree = _rand_tree([((40, 7), jnp.float32)], seed=3)
    l1 = bus.plan_layout(tree, lead_ndim=0, shards=2, **BLK)
    l2 = bus.plan_layout(tree, lead_ndim=0, shards=2, **BLK)
    l4 = bus.plan_layout(tree, lead_ndim=0, shards=4, **BLK)
    lf = bus.plan_layout(tree, lead_ndim=0, shards=2, leaf_sharded=(True,),
                         **BLK)
    assert l1 is l2
    assert l4 is not l1 and lf is not l1
    assert lf.groups[0].slots[0].sharded and not l1.groups[0].slots[0].sharded


def test_sharded_flags_from_param_specs():
    from jax.sharding import PartitionSpec as P

    specs = {"q": P("data", None, "model"),
             "o": P(("pod", "data"), ("model", "x"), None),
             "kv": P("data", None, None),
             "b": P("data")}
    flags = bus.sharded_leaf_flags(specs, "model")
    # flatten order: b, kv, o, q
    assert flags == (False, False, True, True)
    assert bus.sharded_leaf_flags(specs, None) == (False,) * 4


def test_shardings_row_split_flags_mirror_bus():
    """shardings.bus_row_split_flags is the user-facing inverse view: True
    for exactly the leaves the bus row-splits (the old replicated carve-out)."""
    from types import SimpleNamespace

    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import WorkerMesh
    from repro.launch.shardings import bus_row_split_flags

    specs = {"q": P("data", None, "model"), "kv": P("data", None, None)}
    fake = SimpleNamespace(axis_names=("data", "model"),
                           shape={"data": 2, "model": 4})
    wm = WorkerMesh(mesh=fake, worker_axes=("data",), model_axis="model")
    out = bus_row_split_flags(specs, wm)
    assert out == {"q": False, "kv": True}
    # k == 1 → nothing row-splits (every leaf packs whole on its one shard)
    wm1 = WorkerMesh(mesh=SimpleNamespace(axis_names=("data",),
                                          shape={"data": 2}),
                     worker_axes=("data",), model_axis=None)
    assert bus_row_split_flags(specs, wm1) == {"q": False, "kv": False}


def test_mix_swap_permutation_is_bit_exact():
    """pack → mix(pure permutation) → unpack through the fused kernel moves
    bits without perturbing them: swapping twice restores the tree exactly
    (weights are 0/1, so the fp32 accumulate is the identity on each leaf)."""
    swap = T.Topology(name="swap2", A=np.array([[0.0, 1.0], [1.0, 0.0]]),
                      directed=True)
    spec = GossipSpec(topology=swap, backend="fused")
    tree = {
        "a": jax.random.normal(jax.random.PRNGKey(0), (2, 127)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (2, 33, 5)).astype(
            jnp.bfloat16),
    }
    once = bus.mix_bus(tree, spec, None, **BLK)
    twice = bus.mix_bus(once, spec, None, **BLK)
    _assert_tree_bit_equal(twice, tree)
    for k_ in tree:  # one swap really moved the rows
        assert np.array_equal(_bits(once[k_]), _bits(tree[k_][::-1]))


# ---------------------------------------------------------------------------
# Hypothesis property layer (skips via the conftest shim when not installed)
# ---------------------------------------------------------------------------


@hypothesis.settings(max_examples=40, deadline=None)
@hypothesis.given(
    sizes=st.lists(st.integers(min_value=0, max_value=2000), min_size=1,
                   max_size=5),
    dtype_bits=st.lists(st.sampled_from([0, 1]), min_size=1, max_size=5),
    k=st.sampled_from(KS),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_property_roundtrip_bit_exact(sizes, dtype_bits, k, seed):
    dts = [jnp.float32, jnp.bfloat16]
    shapes_dtypes = [((n,), dts[dtype_bits[i % len(dtype_bits)]])
                     for i, n in enumerate(sizes)]
    tree = _rand_tree(shapes_dtypes, seed=seed)
    back, _ = _roundtrip_row_split(tree, k)
    _assert_tree_bit_equal(back, tree)


@hypothesis.settings(max_examples=25, deadline=None)
@hypothesis.given(
    rows=st.integers(min_value=1, max_value=600),
    tail=st.integers(min_value=0, max_value=127),
    k=st.sampled_from(KS),
    leaves=st.integers(min_value=1, max_value=4),
)
def test_property_pass1_padding_bound(rows, tail, k, leaves):
    tree = {f"x{i}": jnp.ones((rows * bus.LANE + tail + i,), jnp.float32)
            for i in range(leaves)}
    layout = bus.plan_layout(tree, lead_ndim=0, shards=k, **BLK)
    _assert_slots_tiled(layout)
    (g,) = layout.groups
    sub = bus.sublane_rows(g.dtype)
    assert g.rows % sub == 0
    assert g.rows * g.cols - g.n < sub * bus.LANE * len(g.slots)


# ---------------------------------------------------------------------------
# Gather overlap: the row-split re-assembly folds into the nchunks pipeline
# ---------------------------------------------------------------------------


def test_row_split_gather_count_unchanged_under_chunking_hlo():
    """The post-mix model-axis all-gather of row-split leaves is issued off
    the HEAD chunks of the nchunks pipeline (overlapping the later chunks'
    fused passes) — but it must stay ONE gather per dtype group: chunking
    pipelines the collective, it must not multiply it. Numerics stay equal
    to the dense oracle at every nchunks."""
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import topology as T, bus
from repro.core.gossip import GossipSpec, mix_pytree_reference
from repro.launch.hlo_cost import analyze_hlo

M, k = 2, 4
key = jax.random.PRNGKey(0)
params = {"w":  jax.random.normal(key, (M, 256, 16 * k)),  # shards over k
          "kv": jax.random.normal(key, (M, 257, 5))}       # row-split
pspecs = {"w": P("data", None, "model"), "kv": P("data", None, None)}
topo = T.directed_ring_lattice(M, 1)
spec = GossipSpec(topology=topo, backend="fused", worker_axes=("data",),
                  model_axis="model")
mesh = jax.make_mesh((M, k), ("data", "model"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 2)
ref = mix_pytree_reference(params, topo.A)
with jax.set_mesh(mesh):
    p = jax.tree.map(lambda x, s: jax.device_put(
        x, jax.NamedSharding(mesh, s)), params, pspecs)
    for nchunks in (1, 3):
        f = jax.jit(lambda q: bus.mix_bus(q, spec, mesh, nchunks=nchunks,
                                          block_r=8, param_specs=pspecs))
        got = f(p)
        hc = analyze_hlo(f.lower(p).compile().as_text())
        assert hc.coll_counts["all-gather"] == 1, (nchunks, hc.coll_counts)
        assert hc.coll_counts["collective-permute"] == nchunks, \\
            (nchunks, hc.coll_counts)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            assert np.allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6), nchunks
        print(f"nchunks{nchunks}-ok")
print("gather-count-ok")
""", n_devices=8)
    assert "gather-count-ok" in out
    assert "nchunks1-ok" in out and "nchunks3-ok" in out


# ---------------------------------------------------------------------------
# HLO byte contract (slow lane): zero replicated-leaf bytes, ≤ 1.02× ideal
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_gqa_cp_bytes_hit_ideal_over_k_hlo():
    """GQA-shaped tree at k ∈ {4, 16}: the kv-projections (8 kv heads) can't
    shard over a 16-way model axis, so the pre-v2 bus shipped them fully
    replicated through every bulk ppermute. Layout v2 row-splits them: the
    compiled HLO's per-device collective-permute bytes must equal the
    layout-predicted buffer exactly (replicated-leaf bytes == 0) and land
    within 2% of the ideal bytes(params)/k — while matching the dense
    oracle numerically."""
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import topology as T, bus
from repro.core.gossip import GossipSpec, mix_pytree_reference
from repro.launch.hlo_cost import analyze_hlo

M = 2
key = jax.random.PRNGKey(0)
D, H, KV, HD = 512, 16, 8, 64
params = {"q":  jax.random.normal(key, (M, D, H * HD)),    # shards /k
          "o":  jax.random.normal(key, (M, H * HD, D)),    # shards /k
          "wk": jax.random.normal(key, (M, D, KV * HD)),   # kv heads: 8 < k
          "wv": jax.random.normal(key, (M, D, KV * HD))}   # -> row-split
payload = sum(x.size // M for x in params.values()) * 4    # bytes / worker
topo = T.directed_ring_lattice(M, 1)                       # degree 1: 1 cp
ref = mix_pytree_reference(params, topo.A)
for k in (4, 16):
    mesh = jax.make_mesh((M, k), ("data", "model"),
                            axis_types=(jax.sharding.AxisType.Auto,) * 2,
                            devices=jax.devices()[: M * k])
    spec = GossipSpec(topology=topo, backend="fused", worker_axes=("data",),
                      model_axis="model")
    pspecs = {"q": P("data", None, "model"), "o": P("data", "model", None),
              "wk": P("data", None, None), "wv": P("data", None, None)}
    # layout-predicted per-device bytes: plan the body's local-shard view
    local = {"q": jax.ShapeDtypeStruct((D, H * HD // k), jnp.float32),
             "o": jax.ShapeDtypeStruct((H * HD // k, D), jnp.float32),
             "wk": jax.ShapeDtypeStruct((D, KV * HD), jnp.float32),
             "wv": jax.ShapeDtypeStruct((D, KV * HD), jnp.float32)}
    flags = bus.sharded_leaf_flags(pspecs, "model")
    layout = bus.plan_layout(local, lead_ndim=0, shards=k, leaf_sharded=flags)
    expect = layout.padded_bytes()
    with jax.set_mesh(mesh):
        p = jax.tree.map(lambda x, s: jax.device_put(
            x, jax.NamedSharding(mesh, s)), params, pspecs)
        f = jax.jit(lambda q: bus.mix_bus(q, spec, mesh, param_specs=pspecs))
        out = f(p)
        hlo = f.lower(p).compile().as_text()
    hc = analyze_hlo(hlo)
    cp_bytes = hc.coll_bytes["collective-permute"]
    assert hc.coll_counts["collective-permute"] == 1, (k, hc.coll_counts)
    # replicated-leaf bytes == 0: the cp ships exactly the planned buffer
    assert cp_bytes == expect, ("replicated bytes leaked", k, cp_bytes, expect)
    ideal = payload / k
    assert cp_bytes <= 1.02 * ideal, ("padding > 2 pct", k, cp_bytes, ideal)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert np.allclose(np.asarray(a), np.asarray(b),
                           rtol=1e-5, atol=1e-6), ("numerics", k)
    print(f"gqa-k{k}-ok cp_bytes={int(cp_bytes)} ideal={int(ideal)} "
          f"eff={ideal / cp_bytes:.4f}")
print("gqa-bytes-ok")
""", n_devices=32)
    assert "gqa-bytes-ok" in out
    assert "gqa-k4-ok" in out and "gqa-k16-ok" in out
