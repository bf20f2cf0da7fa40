"""Trip-count-aware HLO cost model: exactness on synthetic programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import analyze_hlo
from repro.launch.roofline import collective_bytes, model_flops, active_params

D = 256
X = jnp.ones((32, D))
WS = jnp.ones((8, D, D))
TRUE = 2 * 32 * D * D * 8


def _flops(f, *args):
    c = jax.jit(f).lower(*args).compile()
    return analyze_hlo(c.as_text()).flops


def test_unrolled_exact():
    def f(x, ws):
        for i in range(8):
            x = jnp.tanh(x @ ws[i])
        return x.sum()
    assert np.isclose(_flops(f, X, WS) / TRUE, 1.0, rtol=1e-3)


def test_scan_trip_count_exact():
    def f(x, ws):
        y, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)
        return y.sum()
    assert np.isclose(_flops(f, X, WS) / TRUE, 1.0, rtol=1e-3)


def test_grad_is_3x_forward():
    def f(ws):
        y, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), X, ws)
        return y.sum()
    assert np.isclose(_flops(jax.grad(f), WS) / (3 * TRUE), 1.0, rtol=1e-3)


def test_remat_adds_forward_recompute():
    def f(ws):
        body = jax.checkpoint(lambda c, w: jnp.tanh(c @ w))
        y, _ = jax.lax.scan(lambda c, w: (body(c, w), None), X, ws)
        return y.sum()
    assert np.isclose(_flops(jax.grad(f), WS) / (4 * TRUE), 1.0, rtol=1e-3)


def test_nested_scan_multiplies():
    def f(x, ws):
        def outer(c, _):
            y, _ = jax.lax.scan(lambda cc, w: (jnp.tanh(cc @ w), None), c, ws)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y.sum()
    assert np.isclose(_flops(f, X, WS) / (3 * TRUE), 1.0, rtol=1e-3)


def test_bytes_scale_with_scan_trips():
    def f(x, ws):
        y, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)
        return y.sum()
    c = jax.jit(f).lower(X, WS).compile()
    b = analyze_hlo(c.as_text()).bytes
    weight_bytes = 8 * D * D * 4
    assert b > weight_bytes          # at least reads all weights once
    assert b < 20 * weight_bytes     # and is not wildly overcounted


def test_collective_regex_parser():
    hlo = """
ENTRY %main (p: f32[16,32]) -> f32[16,32] {
  %ag = f32[16,32]{1,0} all-gather(%p), replica_groups={}
  %ar = bf16[8,8]{1,0} all-reduce(%x), to_apply=%sum
  ROOT %cp = f32[4]{0} collective-permute(%y), source_target_pairs={{0,1}}
}
"""
    got = collective_bytes(hlo)
    assert got["all-gather"] == 16 * 32 * 4
    assert got["all-reduce"] == 8 * 8 * 2
    assert got["collective-permute"] == 4 * 4
    assert got["total"] == 16 * 32 * 4 + 8 * 8 * 2 + 16


def test_model_flops_moe_active_only():
    from repro.configs import get_config
    mix = get_config("mixtral-8x7b")
    n_active = active_params(mix)
    assert 10e9 < n_active < 20e9          # ~13B active of 47B total
    assert n_active < mix.n_params() * 0.4
    assert model_flops(mix, 1000, "train") == 6.0 * n_active * 1000


def test_roofline_peaks_are_keyed_by_device_kind():
    from repro.launch.roofline import V5E, peaks

    assert peaks(V5E).bf16_flops == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


def test_hlo_parser_reads_tpu_layouts():
    """TPU HLO annotates shapes with tiling and memory-space parentheses;
    the parser must still see the collective as the opcode."""
    hlo = """ENTRY %main (p: bf16[8,128]) -> bf16[8,128] {
  %p = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %cp = (bf16[8,128]{1,0:T(8,128)(2,1)S(1)}, bf16[8,128]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) collective-permute-start(%p), source_target_pairs={{0,1},{1,0}}
  ROOT %d = bf16[8,128]{1,0:T(8,128)(2,1)} collective-permute-done(%cp)
}
"""
    assert analyze_hlo(hlo).coll_counts["collective-permute"] == 1
