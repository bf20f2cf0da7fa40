"""The operation and byte counts against hand counts."""
import pytest

import tiny
from harness import spec, work
from references import dense_gqa, mamba2_ssd


def _model(config):
    return spec.load({"granite-3-2b": "granite-ring4-1chip",
                      "mamba2-2.7b": "mamba2-ring4-1chip"}[config]).model


def test_granite_flops_per_token():
    m = _model("granite-3-2b")
    per_layer = (2048 * 32 * 64 + 2 * 2048 * 8 * 64 + 32 * 64 * 2048
                 + 3 * 2048 * 8192)
    assert per_layer == 60_817_408
    n = 2 * per_layer + 49155 * 2048
    assert n == 222_304_256
    attn = 2 * 6 * 32 * 64 * 2049
    assert dense_gqa.train_flops_per_token(m, 2048) == 6 * n + attn \
        == 1_384_181_760


def test_mamba2_flops_per_token():
    m = _model("mamba2-2.7b")
    assert m["n_layers"] == 5
    per_layer = 2560 * (2 * 5120 + 2 * 128 + 80) + 5120 * 2560
    assert per_layer == 40_181_760
    n = 5 * per_layer + 50280 * 2560
    assert n == 329_625_600
    ssd = 128 * 257 + 5120 * 257 + 4 * 5120 * 128
    conv = 2 * 4 * (5120 + 256)
    assert mamba2_ssd.train_flops_per_token(m, 2048) == \
        6 * n + 3 * 5 * (ssd + conv) == 2_037_951_360


@pytest.mark.parametrize("cell,chips,nbytes,flops", [
    ("granite-ring4-1chip", 1, 5 * 4 * 222_314_496 * 2, 2 * 4 * 4 * 222_314_496),
    ("granite-ring4-4chip", 4, 5 * 222_314_496 * 2, 2 * 4 * 222_314_496),
])
def test_gossip_mix_work(cell, chips, nbytes, flops):
    # 222,304,256 matmul weights + 5 norm gains of 2048: 1,736,832 rows of
    # 128 lanes, already a whole number of 16-row bf16 tiles
    tr = tiny.load(cell).traffic
    assert work.gossip_mix_work(tr, 222_314_496, 2, chips) == (flops, nbytes)


def test_gossip_mix_pads_to_sublane_tiles():
    tr = spec.load("granite-ring4-1chip").traffic
    # 129 elements: 2 rows, padded to one 16-row tile of 128 lanes
    assert work.gossip_mix_work(tr, 129, 2, 4)[1] == 5 * 1 * 16 * 128 * 2


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        work.peaks("cpu", spec.BENCH_DIR)
    assert work.peaks("TPU v5 lite", spec.BENCH_DIR)["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("cell", ["granite-ring4-1chip", "mamba2-ring4-1chip"])
def test_bus_bytes_match_the_programs_layout(cell):
    """The count from shapes agrees with the bus the program plans."""
    import jax
    import numpy as np

    from harness.program import build, seed_key
    from repro.core import bus

    c = spec.load(cell)
    prog = build(c, jax.devices())
    replica = jax.eval_shape(prog.init_replica, seed_key(0))
    layout = bus.plan_layout(jax.eval_shape(prog.replicate, replica),
                             lead_ndim=1)
    per_replica = sum(int(np.prod(x.shape))
                      for x in jax.tree.leaves(replica))
    nbytes = work.gossip_mix_work(c.traffic, per_replica, 2, 1)[1]
    assert nbytes == 5 * 4 * layout.padded_elements() * 2
