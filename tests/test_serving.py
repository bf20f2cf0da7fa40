"""Continuous-batching serving plane: paged cache, batcher, exactness.

Covers the serving contracts CI gates on:
  * ragged batched prefill bit-matches unbatched prefill (pad leakage);
  * the continuous batcher reproduces the unbatched ``generate()`` tokens
    exactly (dense GQA and pure-MLA archs);
  * steady-state serving never recompiles (trace counters flat after
    warmup);
  * PagePool allocation invariants (dump page, retire/reuse).

MoE archs (deepseek) are not bit-match tested against unbatched decoding:
the expert layer is dropless, but a bucket-padded admission prefill sorts a
different set of (token, expert) pairs into its grouped matmuls than an
exact-length unbatched prefill, so results may differ in rounding. Those
archs get a serves-all + determinism test instead.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import model as M
from repro.serving import (ContinuousBatcher, WaveBatcher, generate,
                           supports_paged)
from repro.serving.kvcache import PagePool

KEY = jax.random.PRNGKey(0)


def _requests(cfg, n, max_prompt=10, max_new=8, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size,
                          size=int(rng.integers(2, max_prompt + 1)))
             .astype(np.int32),
             int(rng.integers(1, max_new + 1))) for _ in range(n)]


# ---------------------------------------------------------------------------
# PagePool
# ---------------------------------------------------------------------------


def test_pagepool_admit_retire_invariants():
    pool = PagePool(slots=3, max_len=16, page_size=4)
    assert pool.nb == 4 and pool.n_pages == 13 and pool.dump == 12
    row = pool.admit(0, 6)                     # 2 pages, tail = dump
    assert (row[:2] != pool.dump).all() and (row[2:] == pool.dump).all()
    assert np.array_equal(pool.tables[0], row)
    with pytest.raises(RuntimeError):
        pool.admit(0, 4)                       # double admission
    with pytest.raises(ValueError):
        pool.admit(1, 17)                      # > max_len
    used = set(row[:2].tolist())
    pool.retire(0)
    assert (pool.tables[0] == pool.dump).all()
    assert used <= set(pool.free)              # pages returned for reuse
    # full occupancy: every slot can hold max_len simultaneously
    rows = [pool.admit(s, 16) for s in range(3)]
    ids = [p for r in rows for p in r.tolist()]
    assert len(ids) == len(set(ids)) == 12 and pool.dump not in ids


# ---------------------------------------------------------------------------
# Pad leakage: ragged batched prefill vs unbatched (satellite 1)
# ---------------------------------------------------------------------------


def test_batched_prefill_pads_never_leak_bitwise():
    """Pad leakage contract, bit-for-bit: a row's last-real-token logits
    must not change when (a) the pad tail holds different garbage or (b)
    the OTHER rows of the batch hold different prompts. Both comparisons
    keep the prefill shape fixed, so any bit difference is real leakage,
    not an XLA tiling artifact."""
    cfg = get_config("granite-3-2b", reduced=True)
    params = M.init(KEY, cfg)
    rng = np.random.default_rng(0)
    lens = np.asarray([2, 5, 9, 12, 12, 1], np.int32)
    Lb = 12
    prompts = np.zeros((len(lens), Lb), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.integers(0, cfg.vocab_size, size=n)

    def last_logits(toks):
        logits, *_ = M.prefill(params, cfg, jnp.asarray(toks),
                               max_len=Lb + 4,
                               lengths=jnp.asarray(lens))
        return np.asarray(logits[:, -1])

    base = last_logits(prompts)
    # (a) different garbage in the pad tail
    noisy = prompts.copy()
    for i, n in enumerate(lens):
        noisy[i, n:] = rng.integers(0, cfg.vocab_size, size=Lb - n)
    assert np.array_equal(base, last_logits(noisy))
    # (b) different prompts in every OTHER row
    for i, n in enumerate(lens):
        other = rng.integers(0, cfg.vocab_size,
                             size=prompts.shape).astype(np.int32)
        other[i] = prompts[i]
        assert np.array_equal(base[i], last_logits(other)[i]), (
            f"row {i} (len {n}): neighbouring rows leaked into its logits")


def test_ragged_prefill_matches_exact_length_prefill():
    """Cross-shape semantic check: the ragged path's last-real logits agree
    with an exact-length unbatched prefill (allclose — different shapes
    compile to different reduction tilings, so bitwise equality across
    shapes is not a meaningful bar)."""
    cfg = get_config("granite-3-2b", reduced=True)
    params = M.init(KEY, cfg)
    rng = np.random.default_rng(1)
    lens = [2, 5, 9, 12, 1]
    Lb = 12
    prompts = np.zeros((len(lens), Lb), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.integers(0, cfg.vocab_size, size=n)
    logits, *_ = M.prefill(params, cfg, jnp.asarray(prompts), max_len=Lb + 4,
                           lengths=jnp.asarray(lens, jnp.int32))
    batched = np.asarray(logits[:, -1])
    for i, n in enumerate(lens):
        solo, *_ = M.prefill(params, cfg, jnp.asarray(prompts[i:i + 1, :n]),
                             max_len=Lb + 4)
        np.testing.assert_allclose(batched[i], np.asarray(solo[0, -1]),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"row {i} (len {n})")


def test_wave_batcher_ragged_matches_unbatched():
    cfg = get_config("granite-3-2b", reduced=True)
    params = M.init(KEY, cfg)
    reqs = _requests(cfg, 7)
    wb = WaveBatcher(params, cfg, 4, 24)
    rids = [wb.submit(p, n) for p, n in reqs]
    while wb.queue:
        wb.run_wave()
    for rid, (p, n) in zip(rids, reqs):
        ref = generate(params, cfg, p[None], n_new=n, max_len=len(p) + n)
        assert np.array_equal(np.asarray(ref.tokens[0]),
                              np.asarray(wb.done[rid]))


# ---------------------------------------------------------------------------
# Continuous batcher exactness + compile-cache discipline
# ---------------------------------------------------------------------------


def _run_continuous(cfg, params, reqs, slots=4, max_len=32, page=4,
                    max_new=8):
    cb = ContinuousBatcher(params, cfg, slots, max_len, page_size=page,
                           max_new=max_new)
    cb.warmup()
    rids = [cb.submit(p, n) for p, n in reqs]
    cb.run_until_done()
    return cb, rids


def test_continuous_bit_matches_unbatched_generate():
    cfg = get_config("granite-3-2b", reduced=True)
    params = M.init(KEY, cfg)
    reqs = _requests(cfg, 8)
    cb, rids = _run_continuous(cfg, params, reqs)
    assert len(cb.done) == len(reqs)
    for rid, (p, n) in zip(rids, reqs):
        ref = generate(params, cfg, p[None], n_new=n, max_len=len(p) + n)
        assert np.array_equal(np.asarray(ref.tokens[0]), cb.done[rid]), rid
        assert cb.done_logprobs[rid].shape == (n,)


def test_continuous_bit_matches_unbatched_mla():
    """Paged MLA (absorbed compressed-KV attention) exactness — with the
    MoE switched off (see module docstring for why the expert layer is not
    bit-match tested batched against unbatched)."""
    cfg = get_config("deepseek-v2-lite-16b", reduced=True, n_experts=0,
                     n_shared_experts=0, top_k=0)
    params = M.init(KEY, cfg)
    reqs = _requests(cfg, 4, max_prompt=7, max_new=4)
    cb, rids = _run_continuous(cfg, params, reqs, slots=2, max_len=16,
                               max_new=4)
    for rid, (p, n) in zip(rids, reqs):
        ref = generate(params, cfg, p[None], n_new=n, max_len=len(p) + n)
        assert np.array_equal(np.asarray(ref.tokens[0]), cb.done[rid]), rid


@pytest.mark.slow
def test_continuous_moe_serves_all_and_is_deterministic():
    """MoE: exactness vs unbatched is out of scope (see the module
    docstring), but serving must complete every request and be run-to-run
    deterministic."""
    cfg = get_config("deepseek-v2-lite-16b", reduced=True)
    params = M.init(KEY, cfg)
    reqs = _requests(cfg, 6, max_prompt=7, max_new=6)
    cb1, rids1 = _run_continuous(cfg, params, reqs, max_len=16, max_new=6)
    cb2, rids2 = _run_continuous(cfg, params, reqs, max_len=16, max_new=6)
    assert len(cb1.done) == len(reqs)
    for r1, r2, (p, n) in zip(rids1, rids2, reqs):
        assert cb1.done[r1].shape == (n,)
        assert np.array_equal(cb1.done[r1], cb2.done[r2])


def test_no_recompiles_after_warmup():
    """Steady-state serving must reuse warmup's compiled programs: ONE decode
    trace, ONE trace per (group size, bucket) admission program, zero
    compile-cache misses after warmup — the CI gate bench_serving asserts."""
    cfg = get_config("granite-3-2b", reduced=True)
    params = M.init(KEY, cfg)
    reqs = _requests(cfg, 17, max_prompt=14, max_new=8, seed=9)
    cb, _ = _run_continuous(cfg, params, reqs, slots=4, max_len=32)
    st = cb.stats()
    assert st["decode_traces"] == 1
    assert st["retire_traces"] == 1
    assert st["bucket_misses"] == 0
    assert st["bucket_hits"] > 0
    assert all(v == 1 for v in st["admit_traces"].values()), st
    # every (A, bucket) admission program was pre-traced by warmup
    sizes = {int(k.split("x")[0]) for k in st["admit_traces"]}
    assert sizes == set(cb.admit_sizes)


def test_slot_refill_keeps_occupancy_high():
    """Freed slots are refilled from the queue immediately: with 3x more
    requests than slots and uniform lengths, mean occupancy stays near 1
    (a lock-step wave would idle short rows against the wave max)."""
    cfg = get_config("granite-3-2b", reduced=True)
    params = M.init(KEY, cfg)
    reqs = [(np.ones((4,), np.int32), 6) for _ in range(12)]
    cb, _ = _run_continuous(cfg, params, reqs, slots=4, max_len=16)
    assert len(cb.done) == 12
    assert cb.stats()["mean_occupancy"] > 0.9
    assert all(v is None for v in cb.slots)    # drained clean


def test_continuous_rejects_unsupported_arch():
    cfg = get_config("mamba2-2.7b", reduced=True)
    assert not supports_paged(cfg)
    with pytest.raises(ValueError, match="use WaveBatcher"):
        ContinuousBatcher(M.init(KEY, cfg), cfg, 2, 16, page_size=4)


def test_continuous_validates_request_bounds():
    cfg = get_config("granite-3-2b", reduced=True)
    params = M.init(KEY, cfg)
    cb = ContinuousBatcher(params, cfg, 2, 16, page_size=4, max_new=4)
    with pytest.raises(ValueError, match="max_new"):
        cb.submit(np.ones((3,), np.int32), 5)
    with pytest.raises(ValueError, match="max_len"):
        cb.submit(np.ones((14,), np.int32), 4)
