"""Low-precision DCI gossip lane: wire quantization, error feedback, byte
contracts, and the sim-facing ``dci_dtype`` plumbing (ISSUE 9 acceptance).

Layers:

* wire rules   — which dtype groups compress at which wire dtype, and the
  int8 absmax/127 error bound (zero rows exact, ``|x−deq| ≤ scale/2``);
* layout bytes — ``BusLayout.padded_bytes(wire)`` per-link-class pricing,
  incl. the ≥3.5× fp32→int8 ratio the DCI lane is sized for;
* mix semantics — ``wire_dtype=None`` delegates BIT-identically to the
  exact lane; int8 + error feedback converges to consensus; the hier sim
  protocol charges compressed bytes on DCI edges only;
* correctness guards — coupled-optimizer ``commit='slice'`` rejection
  (satellite 1) and the actionable snap-ring / batch-cache messages
  (satellite 3);
* HLO lane    — the sharded compressed mix ships exactly
  ``padded_bytes('int8')`` collective-permute bytes per permutation;
* hypothesis  — quantize→dequantize+EF identities over dtype mixes.
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
from repro.core.decentralized import replicate_for_workers
from repro.core.gossip import (GossipSpec, hierarchical_mix,
                               hierarchical_mix_compressed,
                               split_hierarchical)
from repro.data import WorkerBatcher, pad_to_equal, random_split
from repro.optim import adafactor_like, sgd
from repro.sim import scenarios
from repro.train.loop import run_simulated, train

BLK = dict(block_r=32)


def _bits(x):
    return np.asarray(x).view(np.uint8)


def _assert_tree_bit_equal(a, b):
    for (pa, xa), (pb, xb) in zip(
            jax.tree_util.tree_flatten_with_path(a)[0],
            jax.tree_util.tree_flatten_with_path(b)[0]):
        assert pa == pb
        assert xa.dtype == xb.dtype and xa.shape == xb.shape, (pa, xa.shape)
        assert np.array_equal(_bits(xa), _bits(xb)), pa


# ---------------------------------------------------------------------------
# Wire dtype rules
# ---------------------------------------------------------------------------


def test_wire_dtype_rules():
    f = bus.wire_dtype_for
    assert f(jnp.float32, None) is None
    assert f(jnp.float32, "bfloat16") == jnp.dtype(jnp.bfloat16)
    assert f(jnp.float32, "int8") == jnp.dtype(jnp.int8)
    # bf16 groups never "compress" to bf16 (no shrink) but do go to int8
    assert f(jnp.bfloat16, "bfloat16") is None
    assert f(jnp.bfloat16, "int8") == jnp.dtype(jnp.int8)
    # non-floating state (step counters, masks) never quantizes
    assert f(jnp.int32, "int8") is None
    assert f(jnp.bool_, "bfloat16") is None


@pytest.mark.parametrize("bogus", ["int4", "float8_e4m3", "fp16", "e5m2"])
def test_unknown_wire_dtype_raises(bogus):
    with pytest.raises((ValueError, TypeError)):
        bus.wire_dtype_for(jnp.float32, bogus)


# ---------------------------------------------------------------------------
# quantize_wire / dequantize_wire
# ---------------------------------------------------------------------------


def test_int8_quantize_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 200)) * \
        jnp.asarray([1e-3, 1.0, 50.0, 1e4, 1e-8, 0.0])[:, None]
    payload, scale = bus.quantize_wire(x, "int8")
    assert payload.dtype == jnp.int8
    assert scale.dtype == jnp.float32 and scale.shape == (6, 1)
    deq = bus.dequantize_wire(payload, scale, jnp.float32)
    err = np.abs(np.asarray(x) - np.asarray(deq))
    bound = 0.5 * np.asarray(scale) * (1 + 1e-5) + 1e-30
    assert np.all(err <= bound)
    # the all-zero row round-trips exactly (scale clamps to 1, q = 0)
    assert np.array_equal(np.asarray(deq)[5], np.zeros(200))
    assert np.asarray(scale)[5, 0] == 1.0


def test_bf16_quantize_is_a_cast():
    x = jax.random.normal(jax.random.PRNGKey(1), (33, 5))
    payload, scale = bus.quantize_wire(x, "bfloat16")
    assert scale is None and payload.dtype == jnp.bfloat16
    assert np.array_equal(_bits(payload), _bits(x.astype(jnp.bfloat16)))
    back = bus.dequantize_wire(payload, None, jnp.float32)
    assert np.array_equal(np.asarray(back),
                          np.asarray(payload, dtype=np.float32))


def test_quantize_scalar_squeeze_path():
    payload, scale = bus.quantize_wire(jnp.asarray(2.5), "int8")
    assert payload.shape == () and scale.shape == ()
    deq = bus.dequantize_wire(payload, scale, jnp.float32)
    assert abs(float(deq) - 2.5) <= float(scale) / 2 + 1e-7


# ---------------------------------------------------------------------------
# Per-link-class byte pricing: padded_bytes(wire_dtype)
# ---------------------------------------------------------------------------


def _fp32_tree():
    k = jax.random.PRNGKey(2)
    return {"w": jax.random.normal(k, (70, 41)),
            "b": jax.random.normal(k, (257,))}


def test_padded_bytes_int8_ratio_meets_dci_target():
    """Acceptance: an fp32 parameter tree prices ≥3.5× smaller on the int8
    DCI lane (4 bytes → 1 byte + one fp32 row scale per 128-lane row)."""
    layout = bus.plan_layout(_fp32_tree(), lead_ndim=0, **BLK)
    exact = layout.padded_bytes()
    int8 = layout.padded_bytes("int8")
    assert exact / int8 >= 3.5
    rows = sum(g.rows for g in layout.groups)
    assert int8 == exact // 4 + rows * 4   # values/4 + fp32 scale per row


def test_padded_bytes_bf16_halves_fp32_groups():
    layout = bus.plan_layout(_fp32_tree(), lead_ndim=0, **BLK)
    assert layout.padded_bytes("bfloat16") == layout.padded_bytes() // 2


def test_padded_bytes_exact_groups_stay_exact():
    """int/bool groups and already-narrow floats price at their exact bytes
    under every wire dtype."""
    tree = {"steps": jnp.arange(300, dtype=jnp.int32),
            "acc": jnp.ones((64,), jnp.bfloat16)}
    layout = bus.plan_layout(tree, lead_ndim=0, **BLK)
    assert layout.padded_bytes("bfloat16") == layout.padded_bytes()
    # int32 stays, bf16 quantizes to int8 (+ scales)
    int8 = layout.padded_bytes("int8")
    gi = {str(g.dtype): g for g in layout.groups}
    want = gi["int32"].rows * gi["int32"].cols * 4 + \
        gi["bfloat16"].rows * gi["bfloat16"].cols * 1 + \
        gi["bfloat16"].rows * 4
    assert int8 == want


# ---------------------------------------------------------------------------
# mix_bus_compressed semantics
# ---------------------------------------------------------------------------


def _stacked_tree(M=4, seed=3):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"w": jax.random.normal(k1, (M, 127)),
            "b": jax.random.normal(k2, (M, 33, 5))}


def test_wire_none_delegates_bit_identically():
    topo = T.undirected_ring(4)
    spec = GossipSpec(topology=topo, backend="fused")
    tree = _stacked_tree()
    exact = bus.mix_bus(tree, spec, None, **BLK)
    got, res = bus.mix_bus_compressed(tree, spec, None, wire_dtype=None,
                                      **BLK)
    _assert_tree_bit_equal(got, exact)
    assert res is None          # residual passes through untouched
    sentinel = ["opaque"]
    _, res2 = bus.mix_bus_compressed(tree, spec, None, wire_dtype=None,
                                     residual=sentinel, **BLK)
    assert res2 is sentinel


@pytest.mark.parametrize("wire", ["bfloat16", "int8"])
def test_compressed_mix_with_ef_converges_to_consensus(wire):
    """CHOCO-style error feedback: repeated lossy gossip drives worker
    disagreement toward zero and lands near the true initial mean — the
    quantization error is re-injected, not lost."""
    topo = T.undirected_ring(4)
    spec = GossipSpec(topology=topo, backend="fused")
    tree = _stacked_tree()
    mean0 = {k: np.asarray(v).mean(0) for k, v in tree.items()}
    spread0 = max(float(np.abs(np.asarray(v) -
                               np.asarray(v).mean(0)).max())
                  for v in tree.values())
    x, res = tree, None
    for _ in range(40):
        x, res = bus.mix_bus_compressed(x, spec, None, wire_dtype=wire,
                                        residual=res, **BLK)
    for k in tree:
        xs = np.asarray(x[k], np.float32)
        assert np.abs(xs - xs.mean(0)).max() < 0.05 * spread0, k
        assert np.abs(xs.mean(0) - mean0[k]).max() < 0.05 * spread0, k
    assert res is not None and any(r is not None for r in res)


def test_hierarchical_mix_compressed_none_is_exact():
    topo = T.hier(2, 4)
    spec = GossipSpec(topology=topo, backend="einsum")
    intra, inter = split_hierarchical(spec)
    tree = _stacked_tree(M=8)
    want = hierarchical_mix(tree, intra, inter, None)
    got, res = hierarchical_mix_compressed(tree, intra, inter, None,
                                           dci_dtype=None)
    _assert_tree_bit_equal(got, want)
    assert res is None


def test_hierarchical_mix_compressed_int8_tracks_exact():
    topo = T.hier(2, 4)
    spec = GossipSpec(topology=topo, backend="einsum")
    intra, inter = split_hierarchical(spec)
    tree = _stacked_tree(M=8)
    want = hierarchical_mix(tree, intra, inter, None)
    got, res = hierarchical_mix_compressed(tree, intra, inter, None,
                                           dci_dtype="int8")
    assert res is not None
    for k in tree:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        # one lossy DCI stage: close, not exact
        assert np.abs(a - b).max() < 0.1
        assert not np.array_equal(a, b)


# ---------------------------------------------------------------------------
# Sim plumbing: dci_dtype end to end through run_simulated
# ---------------------------------------------------------------------------


def _linear_problem(n=8, S_=256, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(S_, n))
    w_true = rng.normal(size=n)
    y = X @ w_true + 0.1 * rng.normal(size=S_)

    def loss(params, batch):
        bx, by = batch
        return jnp.mean((bx @ params["w"] - by) ** 2)

    return X, y, {"w": jnp.zeros(n)}, loss


def _batches(X, y, M, *, batch_size=16, seed=0):
    parts = pad_to_equal(random_split(len(X), M, seed=seed))
    batcher = WorkerBatcher((X, y), parts, batch_size=batch_size, seed=seed)
    while True:
        yield tuple(jnp.asarray(a) for a in batcher.next())


def _sim(topo, **kw):
    X, y, params0, loss = _linear_problem()
    opt = kw.pop("opt", None)
    return run_simulated(
        loss, replicate_for_workers(params0, topo.M), opt or sgd(0.05),
        _batches(X, y, topo.M),
        gossip=GossipSpec(topology=topo, backend="einsum"), **kw)


HIER_KW = dict(protocol="hier", rounds=8, mesh="topology")


def _hier_scenario():
    return scenarios.datacenter("asciq", seed=0)


def test_dci_none_is_bit_identical_to_default():
    """Acceptance: dci_dtype=None leaves the hier protocol untouched — same
    event trace signature, bit-identical params."""
    topo = T.hier(2, 4)
    r0 = _sim(topo, scenario=_hier_scenario(), **HIER_KW)
    r1 = _sim(topo, scenario=_hier_scenario(), dci_dtype=None, **HIER_KW)
    assert r0.trace.signature() == r1.trace.signature()
    _assert_tree_bit_equal(r0.params, r1.params)


def test_dci_int8_lane_bytes_gauges_and_vtime():
    """Acceptance: the int8 DCI lane charges compressed bytes on DCI edges
    only (ICI stays exact), publishes the bytes-ratio / EF-residual gauges,
    achieves ≥3.5× DCI byte reduction, and is never slower in virtual time
    than the exact hier run."""
    topo = T.hier(2, 4)
    r0 = _sim(topo, scenario=_hier_scenario(), **HIER_KW)
    r2 = _sim(topo, scenario=_hier_scenario(), dci_dtype="int8", **HIER_KW)
    _, _, params0, _ = _linear_problem()
    layout = bus.plan_layout(params0, lead_ndim=0)
    exact_b, int8_b = layout.padded_bytes(), layout.padded_bytes("int8")

    acct = r2.trace.link_accounting()
    assert acct["dci"]["bytes"] == acct["dci"]["messages"] * int8_b
    assert acct["ici"]["bytes"] == acct["ici"]["messages"] * exact_b
    acct0 = r0.trace.link_accounting()
    assert acct0["dci"]["bytes"] == acct0["dci"]["messages"] * exact_b

    gauges = {g.name: g.value for g in r2.trace.gauges}
    assert gauges["hier.dci_bytes_ratio"] == pytest.approx(exact_b / int8_b)
    assert gauges["hier.dci_bytes_ratio"] >= 3.5
    assert any(g.name == "hier.dci_ef_residual_norm" for g in r2.trace.gauges)

    t0, l0 = r0.trace.round_loss_curve()
    t2, l2 = r2.trace.round_loss_curve()
    assert np.isfinite(np.asarray(l2)).all()
    assert t2[-1] <= t0[-1] + 1e-9      # smaller DCI payloads: never slower
    assert abs(l2[-1] - l0[-1]) < 0.25 * max(abs(l0[0] - l0[-1]), 1e-9)


def test_dci_dtype_rejected_off_hier_and_for_unknown_wire():
    with pytest.raises(ValueError, match="hier"):
        _sim(T.undirected_ring(8), protocol="sync", rounds=2,
             dci_dtype="int8")
    with pytest.raises(ValueError, match="int4"):
        _sim(T.hier(2, 4), scenario=_hier_scenario(), dci_dtype="int4",
             **HIER_KW)


# ---------------------------------------------------------------------------
# Satellite 1: coupled optimizer state × per-slice commits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("protocol", ["sync", "hier"])
def test_coupled_optimizer_slice_commit_raises(protocol):
    """adafactor_like factors a stacked 1-D leaf ACROSS workers; per-slice
    commits would silently compute wrong second moments. Constructing the
    executor must fail loudly, pointing at commit='full'."""
    topo = T.hier(2, 4) if protocol == "hier" else T.undirected_ring(8)
    kw = dict(protocol=protocol, rounds=4, opt=adafactor_like(0.05))
    if protocol == "hier":
        kw.update(scenario=_hier_scenario())
    with pytest.raises(ValueError) as ei:
        _sim(topo, **kw)
    msg = str(ei.value)
    assert "commit='full'" in msg
    assert "adafactor" in msg
    assert "second moments" in msg


def test_elementwise_optimizer_slice_commit_still_fine():
    r = _sim(T.undirected_ring(4), protocol="sync", rounds=3)
    _, losses = r.trace.round_loss_curve()
    assert np.isfinite(np.asarray(losses)).all()


def test_hier_full_commit_rejects_coupled_optimizer():
    """hier commits per worker slice even under commit='full' (full mode
    only changes mix-source assembly) — following the construction error's
    commit='full' advice on hier must fail loudly, not KeyError deep in the
    optimizer."""
    with pytest.raises(ValueError, match="sync"):
        _sim(T.hier(2, 4), scenario=_hier_scenario(),
             opt=adafactor_like(0.05), commit="full", **HIER_KW)


def test_adafactor_full_commit_bitmatches_train_loop():
    """Regression for the fix's flip side: commit='full' runs the full
    M-row reference program with each worker owning its OWN full optimizer
    state. On the clique every worker's assembled round stack is the true
    round-(k-1) stack, so every worker computes exactly the non-simulated
    train step — params and losses bit-match the train loop."""
    X, y, params0, loss = _linear_problem()
    M, steps = 4, 12
    topo = T.clique(M)
    spec = GossipSpec(topology=topo, backend="einsum")
    opt = adafactor_like(0.05)
    stacked = replicate_for_workers(params0, M)

    state, hist = train(loss, stacked, opt, _batches(X, y, M), steps=steps,
                        gossip=spec, verbose=False)
    sim = run_simulated(loss, stacked, opt, _batches(X, y, M), gossip=spec,
                        protocol="sync", scenario=scenarios.ideal(),
                        rounds=steps, commit="full")
    assert np.array_equal(np.asarray(state.params["w"]),
                          np.asarray(sim.params["w"]))
    _, sim_loss = sim.loss_curve()
    assert np.allclose(sim_loss, np.asarray(hist.loss), rtol=1e-5)


def test_adafactor_full_commit_runs_on_sparse_topology():
    """Off the clique the coupled reference is still well-defined (worker-
    local optimizer states over each worker's assembled stack) — it just
    need not equal the centralized train loop. It must run and descend."""
    r = _sim(T.undirected_ring(4), protocol="sync", rounds=10,
             opt=adafactor_like(0.05), commit="full")
    _, losses = r.trace.round_loss_curve()
    assert np.isfinite(np.asarray(losses)).all()
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# Satellite 3: overrun / retirement errors name the knob to turn
# ---------------------------------------------------------------------------


def test_snap_ring_overrun_message_names_the_knob():
    from repro.sim.protocols import SnapPlanes, TrainExecutor

    X, y, params0, loss = _linear_problem()
    ex = TrainExecutor(
        loss, sgd(0.05), replicate_for_workers(params0, 4),
        _batches(X, y, 4),
        GossipSpec(topology=T.undirected_ring(4), backend="einsum"))
    planes = SnapPlanes(ex, 2)
    with pytest.raises(RuntimeError) as ei:
        planes.row(1, 7)
    msg = str(ei.value)
    assert "snap_depth=2" in msg          # the current knob value
    assert "round-7" in msg and "worker 1" in msg   # the offending lookup
    assert "snap_depth=4" in msg          # the suggested fix (doubled)
    assert "run_simulated" in msg


def test_batch_cache_retired_message_names_the_watermark():
    from repro.sim.protocols import BatchCache

    cache = BatchCache(iter([]))
    cache._floor = 5
    with pytest.raises(RuntimeError) as ei:
        cache.get(2)
    msg = str(ei.value)
    assert "retired" in msg               # anchor other suites match on
    assert "batch 2" in msg
    assert "watermark is 5" in msg
    assert "retire_below" in msg


# ---------------------------------------------------------------------------
# HLO lane: the sharded compressed mix ships exactly the priced bytes
# ---------------------------------------------------------------------------


def test_compressed_mix_cp_bytes_match_layout_prediction_hlo():
    """Per permutation, the compressed sharded mix collective-permutes the
    int8 value buffer plus its fp32 row scales — together EXACTLY
    ``padded_bytes('int8')`` — and nothing else rides the wire."""
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import topology as T, bus
from repro.core.gossip import GossipSpec
from repro.launch.hlo_cost import analyze_hlo

M = 4
key = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(key, (M, 127)),
          "b": jax.random.normal(key, (M, 33, 5))}
topo = T.undirected_ring(M)
spec = GossipSpec(topology=topo, backend="fused", worker_axes=("data",))
mesh = jax.make_mesh((M,), ("data",),
                        axis_types=(jax.sharding.AxisType.Auto,))
layout = bus.plan_layout(params, lead_ndim=1, block_r=32)
n_perms = len(bus._split_perms(spec)[1])
with jax.set_mesh(mesh):
    p = jax.tree.map(lambda x: jax.device_put(
        x, jax.NamedSharding(mesh, P("data"))), params)
    f = jax.jit(lambda q: bus.mix_bus_compressed(
        q, spec, mesh, wire_dtype="int8", block_r=32)[0])
    f(p)
    hc = analyze_hlo(f.lower(p).compile().as_text())
    # int8 groups ship values + scales: two cps per permutation
    assert hc.coll_counts["collective-permute"] == 2 * n_perms, \\
        hc.coll_counts
    assert hc.coll_bytes["collective-permute"] == \\
        n_perms * layout.padded_bytes("int8"), \\
        (hc.coll_bytes, n_perms, layout.padded_bytes("int8"))
print("cp-bytes-ok")
""", n_devices=8)
    assert "cp-bytes-ok" in out


# ---------------------------------------------------------------------------
# Hypothesis property layer (skips via the conftest shim when not installed)
# ---------------------------------------------------------------------------


_vals = st.lists(st.floats(min_value=-1e30, max_value=1e30,
                           allow_nan=False, allow_infinity=False,
                           width=32),
                 min_size=1, max_size=64)


@hypothesis.settings(max_examples=60, deadline=None)
@hypothesis.given(xs=_vals, rs=_vals, wire=st.sampled_from(bus.WIRE_DTYPES))
def test_property_error_feedback_identity(xs, rs, wire):
    """EF bookkeeping is EXACT in fp32: deq + new_residual == x + residual.
    (Sterbenz: deq is within a factor of two of xe elementwise — or zero —
    so the subtraction xe − deq is exact, and adding deq back is exact.)"""
    n = max(len(xs), len(rs))
    x = jnp.asarray((xs * n)[:n], jnp.float32)
    r = jnp.asarray((rs * n)[:n], jnp.float32)
    xe = x + r
    payload, scale = bus.quantize_wire(xe, wire)
    deq = bus.dequantize_wire(payload, scale, jnp.float32)
    new_r = xe - deq
    assert np.array_equal(np.asarray(deq + new_r), np.asarray(xe))


@hypothesis.settings(max_examples=60, deadline=None)
@hypothesis.given(
    xs=_vals,
    rows=st.integers(min_value=1, max_value=4),
    dtype_bit=st.sampled_from([0, 1]),
)
def test_property_int8_bound_over_dtypes(xs, rows, dtype_bit):
    dt = [jnp.float32, jnp.bfloat16][dtype_bit]
    n = len(xs) * rows
    x = jnp.asarray((xs * rows)[:n], jnp.float32).reshape(rows, -1).astype(dt)
    wt = bus.wire_dtype_for(dt, "int8")
    assert wt == jnp.dtype(jnp.int8)
    payload, scale = bus.quantize_wire(x, "int8")
    deq = bus.dequantize_wire(payload, scale, dt)
    err = np.abs(np.asarray(x, np.float32) - np.asarray(deq, np.float32))
    # bf16 inputs quantize via their fp32 value; the dequant cast back to
    # bf16 adds at most one bf16 rounding on top of the scale/2 bound
    slack = 1e-5 if dt == jnp.float32 else 2.0 ** -7
    bound = 0.5 * np.asarray(scale) * (1 + slack) + \
        slack * np.abs(np.asarray(x, np.float32)) + 1e-30
    assert np.all(err <= bound)
