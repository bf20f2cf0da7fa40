"""Pallas kernel validation: interpret=True vs pure-jnp oracles, with
shape/dtype sweeps and hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import run_in_subprocess
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.ops import attention as flash_attention_op
from repro.kernels.flash_attention.ref import attention_reference
from repro.models import attention as attn_lib
from repro.kernels.gossip_mix.ops import gossip_mix_leaf, gossip_mix_pytree
from repro.kernels.gossip_mix.ref import gossip_mix_reference

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# gossip_mix
# ---------------------------------------------------------------------------

# Fast lane: small/odd shapes in fp32; big shapes and the bf16 sweep are
# heavy on CPU interpret mode and run under `-m slow`.
GOSSIP_SHAPES = [(64,), (37, 129), (3, 3)] + [
    pytest.param(s, marks=pytest.mark.slow)
    for s in [(1000,), (4, 8, 65), (512, 512)]]
GOSSIP_DTYPES = [jnp.float32, pytest.param(jnp.bfloat16, marks=pytest.mark.slow)]


@pytest.mark.parametrize("shape", GOSSIP_SHAPES)
@pytest.mark.parametrize("dtype", GOSSIP_DTYPES)
@pytest.mark.parametrize("k", [1, 2, 4])
def test_gossip_mix_matches_reference(shape, dtype, k):
    ks = jax.random.split(KEY, 4)
    w = jax.random.normal(ks[0], shape, dtype)
    nb = jax.random.normal(ks[1], (k,) + shape, dtype)
    wt = jax.nn.softmax(jax.random.normal(ks[2], (k + 1,)))
    up = jax.random.normal(ks[3], shape, dtype)
    out = gossip_mix_leaf(w, nb, wt, up, 0.1)
    ref = gossip_mix_reference(w, nb, wt, up, 0.1)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
    assert out.dtype == w.dtype


def test_gossip_mix_pytree():
    params = {"a": jnp.ones((10, 7)), "b": {"c": jnp.arange(5.0)}}
    nbrs = [jax.tree.map(lambda x: x * (i + 2.0), params) for i in range(2)]
    upd = jax.tree.map(jnp.ones_like, params)
    wt = jnp.asarray([0.5, 0.25, 0.25])
    out = gossip_mix_pytree(params, nbrs, wt, upd, eta=0.1)
    # a: 0.5*1 + 0.25*2 + 0.25*3 - 0.1 = 1.65
    np.testing.assert_allclose(np.asarray(out["a"]), 1.65, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 300), st.integers(1, 4), st.integers(0, 100))
def test_gossip_mix_property_random_sizes(n, k, seed):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    w = jax.random.normal(ks[0], (n,))
    nb = jax.random.normal(ks[1], (k, n))
    wt = jax.nn.softmax(jax.random.normal(ks[2], (k + 1,)))
    up = jax.random.normal(ks[3], (n,))
    out = gossip_mix_leaf(w, nb, wt, up, 0.05)
    ref = gossip_mix_reference(w, nb, wt, up, 0.05)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_gossip_mix_identity_weights():
    """weights = [1, 0, ...], eta = 0 ⇒ identity."""
    w = jax.random.normal(KEY, (100,))
    nb = jax.random.normal(KEY, (2, 100))
    out = gossip_mix_leaf(w, nb, jnp.asarray([1.0, 0.0, 0.0]),
                          jnp.zeros(100), 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(w), atol=1e-7)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Lq, Lkv, H, Hkv, hd, causal, window)
    (2, 128, 128, 4, 2, 64, True, None),
    (1, 256, 256, 8, 1, 32, True, 64),     # MQA + sliding window
    (2, 128, 128, 4, 4, 64, False, None),  # encoder (bidirectional)
    (1, 64, 64, 2, 2, 128, True, None),
    (1, 128, 128, 4, 2, 16, True, 32),
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [
    jnp.float32, pytest.param(jnp.bfloat16, marks=pytest.mark.slow)])
def test_flash_attention_matches_reference(case, dtype):
    B, Lq, Lkv, H, Hkv, hd, causal, window = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Lq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Lkv, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, Lkv, Hkv, hd), dtype)
    out = flash_attention_op(q, k, v, causal=causal, window=window,
                             block_q=64, block_kv=64)
    ref = attention_reference(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window
    ).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_matches_model_blockwise_twin():
    """The Pallas kernel and the XLA blockwise twin implement the same math."""
    from repro.models.attention import blockwise_attention

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32))
    k = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    a = flash_attention_op(q, k, v, causal=True, block_q=64, block_kv=64)
    b = blockwise_attention(q, k, v, 0, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([32, 64, 128]), st.sampled_from([1, 2, 4]),
       st.integers(0, 50))
def test_flash_property_softmax_rows(L, Hkv, seed):
    """Attention output is a convex combination of V rows: bounded by V range."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    H = Hkv * 2
    q = jax.random.normal(ks[0], (1, L, H, 16))
    k = jax.random.normal(ks[1], (1, L, Hkv, 16))
    v = jax.random.normal(ks[2], (1, L, Hkv, 16))
    out = flash_attention_op(q, k, v, causal=True, block_q=32, block_kv=32)
    assert float(out.max()) <= float(v.max()) + 1e-4
    assert float(out.min()) >= float(v.min()) - 1e-4


# (B, L, H, Kh, d_qk, d_v, scale): granite-like GQA, and MLA's head dims
# with DeepSeek-V2-Lite's softmax scale
TRAIN_CASES = {
    "gqa": (2, 256, 4, 2, 64, 64, None),
    "mla": (1, 512, 2, 2, 192, 128, 0.1147214),
}


def _qkvg(B, L, H, Kh, dqk, dv, dtype, key=KEY, lead=()):
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], lead + (B, L, H, dqk), dtype),
            jax.random.normal(ks[1], lead + (B, L, Kh, dqk), dtype),
            jax.random.normal(ks[2], lead + (B, L, Kh, dv), dtype),
            jax.random.normal(ks[3], lead + (B, L, H, dv), dtype))


def _ref_bl(q, k, v, scale):
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(attention_reference(t(q), t(k), t(v), causal=True, scale=scale))


def _out_and_grads(f, q, k, v, g):
    o, vjp = jax.vjp(f, q, k, v)
    return (o,) + vjp(g)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(TRAIN_CASES))
def test_flash_forward_and_gradients_match_oracles(case, dtype):
    """Output and dq, dk, dv against ref.py (float32 scores and softmax)
    and against the XLA blockwise twin, with 128-row blocks so the causal
    skip, the diagonal mask and (MLA) the separate value dim all run."""
    B, L, H, Kh, dqk, dv, scale = TRAIN_CASES[case]
    q, k, v, g = _qkvg(B, L, H, Kh, dqk, dv, dtype)
    flash = lambda q, k, v: flash_attention_op(q, k, v, scale=scale,
                                               block_q=128, block_kv=128)
    twin = lambda q, k, v: attn_lib.blockwise_attention(
        q, k, v, 0, causal=True, q_chunk=128, kv_chunk=128, scale=scale)
    got = _out_and_grads(flash, q, k, v, g)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
    truth = _out_and_grads(lambda q, k, v: _ref_bl(q, k, v, scale), *f32)
    same_dtype = _out_and_grads(twin, q, k, v, g)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for name, a, r, t in zip(("o", "dq", "dk", "dv"), got, truth, same_dtype):
        assert a.shape == r.shape and a.dtype == dtype, name
        assert _rel(a, r) < tol, (name, _rel(a, r))
        assert _rel(a, t) < tol, (name, _rel(a, t))


@pytest.mark.parametrize("case", sorted(TRAIN_CASES))
def test_flash_per_worker_under_vmap(case):
    """The train step's vmap over 4 workers of a loss's value and gradient:
    the kernel runs the workers folded into its batch grid, and matches
    the twin worker by worker."""
    B, L, H, Kh, dqk, dv, scale = TRAIN_CASES[case]
    q, k, v, _ = _qkvg(B, L, H, Kh, dqk, dv, jnp.float32, lead=(4,))

    def loss(att):
        return lambda q, k, v: jnp.sum(jnp.sin(att(q, k, v)))

    flash = lambda q, k, v: flash_attention_op(q, k, v, scale=scale,
                                               block_q=128, block_kv=128)
    twin = lambda q, k, v: attn_lib.blockwise_attention(
        q, k, v, 0, causal=True, q_chunk=128, kv_chunk=128, scale=scale)
    vg = lambda att: jax.jit(jax.vmap(jax.value_and_grad(
        loss(att), argnums=(0, 1, 2))))
    got, want = vg(flash)(q, k, v), vg(twin)(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_per_worker_on_a_mesh():
    """Workers spread over a 4-device mesh: the batched rule runs the
    kernel per device inside a shard_map (forward and backward), with the
    twin's values."""
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels.flash_attention.ops import attention
from repro.models.attention import blockwise_attention
ks = jax.random.split(jax.random.PRNGKey(1), 3)
q = jax.random.normal(ks[0], (4, 1, 256, 4, 64))
k = jax.random.normal(ks[1], (4, 1, 256, 2, 64))
v = jax.random.normal(ks[2], (4, 1, 256, 2, 64))
loss = lambda att: lambda q, k, v: jnp.sum(jnp.sin(att(q, k, v)))
vg = lambda att: jax.jit(jax.vmap(jax.value_and_grad(loss(att),
                                                     argnums=(0, 1, 2))))
flash = vg(lambda q, k, v: attention(q, k, v, block_q=128, block_kv=128))
twin = vg(lambda q, k, v: blockwise_attention(q, k, v, 0, q_chunk=128,
                                              kv_chunk=128))
want = twin(q, k, v)
mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
with jax.set_mesh(mesh):
    args = [jax.device_put(x, NamedSharding(mesh, P("data")))
            for x in (q, k, v)]
    jaxpr = str(jax.make_jaxpr(flash)(*args))
    got = flash(*args)
assert jaxpr.count("shard_map") >= 2, jaxpr
for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)
print("ok")
""", n_devices=4)
    assert "ok" in out


# how the kernel's operands lie on 4 devices: (worker lead dims, rows per
# worker, mesh shape, operand spec, the q block each kernel call gets)
MESH_CASES = {
    # the all-reduce step: no vmap, the batch's rows over the chips
    "rows-over-chips": ((), 4, (4,), ("data",), (1, 4)),
    # 8 workers on 4 chips: the workers fold into the rows, split 4 ways
    "folded-workers": ((8,), 1, (4,), ("data",), (2, 4)),
    # a data x model mesh: one worker per data slice, heads over model
    "heads-over-model": ((2,), 1, (2, 2), ("data", None, None, "model"),
                         (1, 2)),
    # 2 rows do not split 4 ways: each chip runs them all, in a shard_map
    "rows-that-do-not-divide": ((), 2, (4,), (), (2, 4)),
}


@pytest.mark.parametrize("case", sorted(MESH_CASES))
def test_flash_runs_per_device_on_a_mesh(case):
    """Wherever the operands lie on the mesh, each kernel call (forward, dQ,
    dK/dV) runs in a shard_map on its device's rows and heads: the TPU
    compiler partitions no Pallas call. The values are the twin's."""
    lead, B, shape, spec, local = MESH_CASES[case]
    out = run_in_subprocess(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels.flash_attention.ops import attention
from repro.models.attention import blockwise_attention
lead, B = {lead!r}, {B}
ks = jax.random.split(jax.random.PRNGKey(1), 3)
q = jax.random.normal(ks[0], lead + (B, 256, 4, 64))
k = jax.random.normal(ks[1], lead + (B, 256, 2, 64))
v = jax.random.normal(ks[2], lead + (B, 256, 2, 64))
loss = lambda att: lambda q, k, v: jnp.sum(jnp.sin(att(q, k, v)))
def vg(att):
    f = jax.value_and_grad(loss(att), argnums=(0, 1, 2))
    return jax.jit(jax.vmap(f) if lead else f)
flash = vg(lambda q, k, v: attention(q, k, v, block_q=128, block_kv=128))
twin = vg(lambda q, k, v: blockwise_attention(q, k, v, 0, q_chunk=128,
                                              kv_chunk=128))
want = twin(q, k, v)
names = ("data", "model")[:{len(shape)}]
mesh = jax.make_mesh({shape!r}, names,
                     axis_types=(jax.sharding.AxisType.Auto,) * len(names))
def q_blocks(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.invars[0].aval.shape)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += q_blocks(sub)
    return out
with jax.set_mesh(mesh):
    args = [jax.device_put(x, NamedSharding(mesh, P(*{spec!r})))
            for x in (q, k, v)]
    blocks = q_blocks(jax.make_jaxpr(flash)(*args).jaxpr)
    got = flash(*args)
assert len(blocks) == 3 and all(b[:2] == {local!r} for b in blocks), blocks
for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)
print("ok")
""", n_devices=4)
    assert "ok" in out


_TAKES = dict(Lq=2048, Lkv=2048, q_base=0, causal=True, window=None,
              kv_valid=None)


@pytest.mark.parametrize("change", [
    {}, {"Lq": 512, "Lkv": 512}, {"Lq": 128, "Lkv": 128}])
def test_flash_dispatch_takes_the_kernel(monkeypatch, change):
    monkeypatch.setattr(flash_ops, "on_tpu", lambda: True)
    a = dict(_TAKES, **change)
    assert attn_lib.takes_flash_kernel(a.pop("Lq"), a.pop("Lkv"),
                                       a.pop("q_base"), **a)


@pytest.mark.parametrize("change", [
    {"backend": "cpu"},
    {"causal": False},
    {"window": 1024},
    {"kv_valid": jnp.ones((1, 2048), bool)},
    {"Lq": 1024},
    {"q_base": 1024},
    {"q_base": jnp.int32(0)},
    {"Lq": 2000, "Lkv": 2000},
], ids=["off-tpu", "bidirectional", "window", "kv_valid", "Lq!=Lkv",
        "q_base", "traced-q_base", "partial-block"])
def test_flash_dispatch_keeps_the_xla_paths(monkeypatch, change):
    """The kernel is taken only under all six conditions: each one broken
    alone sends the call back to the dense or blockwise path."""
    change = dict(change)
    tpu = change.pop("backend", "tpu") == "tpu"
    monkeypatch.setattr(flash_ops, "on_tpu", lambda: tpu)
    a = dict(_TAKES, **change)
    assert not attn_lib.takes_flash_kernel(a.pop("Lq"), a.pop("Lkv"),
                                           a.pop("q_base"), **a)


def test_flash_dispatch_off_tpu_runs_the_twin():
    """Off the TPU ``attention_any`` gives the blockwise twin's result
    exactly: the kernel is not taken."""
    q, k, v, _ = _qkvg(1, 2048, 2, 1, 16, 16, jnp.float32)
    assert not flash_ops.on_tpu()
    np.testing.assert_array_equal(
        np.asarray(attn_lib.attention_any(q, k, v, 0)),
        np.asarray(attn_lib.blockwise_attention(q, k, v, 0)))


def _kernel_names(jaxpr):
    """Names of the Pallas calls in a jaxpr and every jaxpr inside it."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += _kernel_names(sub)
    return names


@pytest.mark.parametrize("arch", ["granite-3-2b", "deepseek-v2-lite-16b"])
def test_flash_trains_the_model_as_the_twin_does(monkeypatch, arch):
    """The model's loss and gradients, per worker under the train step's
    vmap, with remat and scanned layers: through the kernel (taken by
    ``attention_any`` as on TPU, interpreted here) against the blockwise
    twin. Remat keeps the kernel's output and logsumexp, so the backward
    runs dQ and dK/dV without a second forward kernel."""
    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config(arch, reduced=True, n_layers=2, remat=True,
                     scan_layers=True)
    params = M.init(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(lambda x: jnp.stack([x, 1.01 * x]), params)
    tokens = jax.random.randint(KEY, (2, 1, 513), 0, cfg.vocab_size)

    def run():
        """Kernels traced and (loss, grads), from a fresh trace."""
        vg = jax.vmap(jax.value_and_grad(
            lambda p, t: M.loss_fn(p, cfg, {"tokens": t})))
        names = _kernel_names(jax.make_jaxpr(vg)(params, tokens).jaxpr)
        return names, jax.jit(vg)(params, tokens)

    names, want = run()
    assert names == []
    monkeypatch.setattr(attn_lib, "takes_flash_kernel",
                        lambda *a, **k: True)
    names, got = run()
    # one forward, one dQ and one dK/dV per scanned segment of layers
    kinds = ("flash_attention_fwd", "flash_attention_dq",
             "flash_attention_dkv")
    assert set(names) == set(kinds), names
    assert len({names.count(k) for k in kinds}) == 1, names
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
