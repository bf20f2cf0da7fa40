"""Telemetry plane tests (ISSUE 7): health gauges vs dense numpy oracles,
zero-overhead-when-disabled bit-match guarantees, Chrome-trace export
validation, provenance stamping, and the report CLI."""
import json
import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.core import topology as T
from repro.core.decentralized import replicate_for_workers
from repro.core.gossip import GossipSpec
from repro.optim import sgd
from repro.sim import scenarios
from repro.sim.trace import Trace, TraceRecord
from repro.train.loop import run_simulated, train


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def _linear_problem(n=6, S_=128, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(S_, n))
    y = X @ rng.normal(size=n) + 0.1 * rng.normal(size=S_)

    def loss(params, batch):
        bx, by = batch
        return jnp.mean((bx @ params["w"] - by) ** 2)

    return X, y, {"w": jnp.zeros(n)}, loss


def _batches(X, y, M, seed=0):
    from repro.data import WorkerBatcher, pad_to_equal, random_split

    parts = pad_to_equal(random_split(len(X), M, seed=seed))
    batcher = WorkerBatcher((X, y), parts, batch_size=16, seed=seed)
    while True:
        yield tuple(jnp.asarray(a) for a in batcher.next())


def _sim(protocol, topo, *, rounds, scenario, seed=0, **kw):
    X, y, params0, loss = _linear_problem(seed=seed)
    return run_simulated(
        loss, replicate_for_workers(params0, topo.M), sgd(0.1),
        _batches(X, y, topo.M, seed=seed),
        gossip=GossipSpec(topology=topo, backend="einsum"),
        protocol=protocol, scenario=scenario, rounds=rounds, **kw)


def _neff_oracle(A, gamma, K=6000):
    """Independent truncated-series oracle: tr Σ_∞ = Σ_k γ^{2k}·‖A^k‖_F²."""
    A = np.asarray(A, np.float64)
    M = A.shape[0]
    g2 = gamma * gamma
    tr, Ak = 0.0, np.eye(M)
    for k in range(1, K + 1):
        Ak = Ak @ A
        term = g2**k * np.linalg.norm(Ak, "fro") ** 2
        tr += term
        if term < 1e-15:
            break
    return (g2 / (1.0 - g2)) / (tr / M)


# ---------------------------------------------------------------------------
# Health gauges vs dense numpy oracles
# ---------------------------------------------------------------------------


def test_effective_neighbors_extremes():
    M = 12
    # isolated workers average with nobody: n_eff = 1
    assert telemetry.effective_neighbors(np.eye(M)) == pytest.approx(1.0)
    # the clique averages everybody every step: n_eff = M
    assert telemetry.effective_neighbors(np.ones((M, M)) / M) == \
        pytest.approx(M)
    assert telemetry.effective_neighbors(np.ones((1, 1))) == 1.0


@pytest.mark.parametrize("gamma", [0.5, 0.9, 0.99])
@pytest.mark.parametrize("mk", [
    lambda: T.undirected_ring(8), lambda: T.clique(8),
    lambda: T.hier(4, 4), lambda: T.ring_lattice(16, 4)])
def test_effective_neighbors_matches_series_oracle(mk, gamma):
    A = mk().A
    got = telemetry.effective_neighbors(A, gamma)
    want = _neff_oracle(A, gamma)
    assert got == pytest.approx(want, rel=1e-6)
    assert 1.0 <= got <= A.shape[0] + 1e-9


def test_effective_neighbors_monotone_in_connectivity():
    """Denser graphs reduce more variance: ring < torus-ish lattice < clique."""
    ring = telemetry.effective_neighbors(T.undirected_ring(16).A)
    lattice = telemetry.effective_neighbors(T.ring_lattice(16, 6).A)
    clique = telemetry.effective_neighbors(T.clique(16).A)
    assert ring < lattice < clique
    assert clique == pytest.approx(16.0)


@pytest.mark.parametrize("mode", ["reabsorb", "renormalize"])
def test_effective_neighbors_survivor_repaired_oracle(mode):
    """The non-normal (Lyapunov-iteration) path agrees with the series
    oracle on survivor-repaired ring and hier matrices."""
    topo = T.undirected_ring(8)
    alive = np.ones(8, bool)
    alive[[2, 5]] = False
    A = T.survivor_matrix(topo.A, alive, mode)
    assert telemetry.effective_neighbors(A, 0.9) == \
        pytest.approx(_neff_oracle(A, 0.9), rel=1e-6)

    th = T.hier(4, 4)
    alive = np.ones(16, bool)
    alive[4:8] = False  # whole pod drop → bridged outer stage
    intra, inter = T.repair_hier_stages(th, alive, mode)
    Ah = inter @ intra
    assert telemetry.effective_neighbors(Ah, 0.9) == \
        pytest.approx(_neff_oracle(Ah, 0.9), rel=1e-6)


def test_health_gauges_spectral_gap_matches_topology():
    for topo in (T.undirected_ring(8), T.clique(8), T.hier(4, 2)):
        g = telemetry.health_gauges(topo.A)
        assert g["spectral_gap"] == pytest.approx(topo.spectral_gap)
        assert g["lambda2"] == pytest.approx(topo.lambda2)
        assert set(g) == {"spectral_gap", "lambda2", "effective_neighbors"}


def test_active_matrix_healthy_is_identity_repair():
    topo = T.undirected_ring(8)
    assert np.array_equal(telemetry.active_matrix(topo), topo.A)


def test_active_matrix_survivors_and_blocked_edges():
    topo = T.undirected_ring(8)
    alive = np.ones(8, bool)
    alive[3] = False
    A = telemetry.active_matrix(topo, alive)
    assert np.array_equal(A, T.survivor_matrix(topo.A, alive, "reabsorb"))

    # blocking an in-edge re-stochasticizes that column only
    blocked = lambda i, j: (i, j) == (1, 0)
    A = telemetry.active_matrix(topo, blocked=blocked)
    assert A[1, 0] == 0.0
    np.testing.assert_allclose(A.sum(0), np.ones(8), atol=1e-12)
    np.testing.assert_array_equal(A[:, 1:], topo.A[:, 1:])


def test_active_matrix_hier_pod_drop_uses_staged_repair():
    th = T.hier(4, 4)
    alive = np.ones(16, bool)
    alive[4:8] = False
    A = telemetry.active_matrix(th, alive, hier=True)
    intra, inter = T.repair_hier_stages(th, alive, "reabsorb")
    np.testing.assert_allclose(A, inter @ intra, atol=1e-12)


def test_round_bytes_by_class_cross_checks_edge_classes():
    th = T.hier(4, 4)
    payload = 1000
    got = telemetry.round_bytes_by_class(th, payload, th.group_of)
    classes = T.edge_classes(th, th.group_of)
    assert got == {cls: len(e) * payload for cls, e in classes.items()}
    assert got["ici"] > 0 and got["dci"] > 0


# ---------------------------------------------------------------------------
# Zero overhead when disabled: bit-match guarantees
# ---------------------------------------------------------------------------


def test_disabled_telemetry_train_bit_match():
    """Instrumented-but-disabled train() is bit-identical to a telemetry
    run of the same training — numerics never touch the sink."""
    X, y, params0, loss = _linear_problem()
    M = 4
    spec = GossipSpec(topology=T.undirected_ring(M), backend="fused")
    p0 = replicate_for_workers(params0, M)

    s1, h1 = train(loss, p0, sgd(0.05), _batches(X, y, M), steps=12,
                   gossip=spec, log_every=4, verbose=False)
    with telemetry.run() as tel:
        s2, h2 = train(loss, p0, sgd(0.05), _batches(X, y, M), steps=12,
                       gossip=spec, log_every=4, verbose=False)
    assert np.array_equal(np.asarray(s1.params["w"]),
                          np.asarray(s2.params["w"]))
    assert h1.loss == h2.loss
    # the sink actually recorded the run
    assert tel.counters["train.steps"] == 12
    assert tel.counters["bus.mix_calls"] >= 1
    assert any(s["name"] == "train.window" for s in tel.spans)
    assert telemetry.get() is telemetry.NULL  # context restored the null sink


def test_train_host_spans_reach_the_profile_without_a_sink(tmp_path):
    """train() opens train.dispatch and train.host_sync once per log window
    as profiler annotations, with the null sink installed."""
    import glob

    import jax

    X, y, params0, loss = _linear_problem()
    M = 4
    spec = GossipSpec(topology=T.undirected_ring(M), backend="einsum")
    p0 = replicate_for_workers(params0, M)
    assert telemetry.get() is telemetry.NULL
    with jax.profiler.trace(str(tmp_path)):
        train(loss, p0, sgd(0.05), _batches(X, y, M), steps=9, gossip=spec,
              log_every=4, verbose=False)
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    names = [e.name for plane in pd.planes if plane.name.startswith("/host")
             for ln in plane.lines for e in ln.events]
    # log windows: step 0, steps 1-4, 5-8 (the last flush finds none left)
    assert names.count("train.dispatch") == 3
    assert names.count("train.host_sync") == 3


def test_health_gauges_do_not_perturb_trace_signature():
    """health=True adds gauges but leaves the event schedule, the signature,
    and the trained parameters bit-identical."""
    topo = T.undirected_ring(4)
    scen = scenarios.heavy_tail("spark", seed=3)
    r_off = _sim("sync", topo, rounds=10, scenario=scen)
    r_on = _sim("sync", topo, rounds=10, scenario=scen, health=True)
    assert r_off.trace.signature() == r_on.trace.signature()
    assert np.array_equal(np.asarray(r_off.params["w"]),
                          np.asarray(r_on.params["w"]))
    assert len(r_off.trace.gauges) == 0
    assert len(r_on.trace.gauges) == 3  # t=0 baseline, no churn/faults


def test_bus_collectives_counter_matches_bulk_formula():
    from repro.core.bus import bulk_collectives_per_step, mix_bus

    spec = GossipSpec(topology=T.ring_lattice(8, 4))
    params = {"w": jnp.ones((8, 40)), "b": jnp.ones((8, 3))}
    with telemetry.run() as tel:
        mix_bus(params, spec, nchunks=2)
    assert tel.counters["bus.collectives"] == \
        bulk_collectives_per_step(spec, 2)
    assert tel.counters["bus.mix_calls"] == 1
    assert tel.gauges[0]["name"] == "bus.padded_bytes"
    assert tel.gauges[0]["value"] > 0


# ---------------------------------------------------------------------------
# Trace gauges: recording + JSON roundtrip
# ---------------------------------------------------------------------------


def test_trace_gauge_json_roundtrip(tmp_path):
    tr = Trace(2)
    tr.record(TraceRecord(0, 0.5, "compute_done", 0, round=1, loss=1.0))
    tr.record_gauge(0.0, "health.spectral_gap", 0.25)
    tr.record_gauge(1.5, "health.effective_neighbors", 3.5)
    path = tr.save(str(tmp_path / "trace.json"))
    tr2 = Trace.load(path)
    assert [(g.t, g.name, g.value) for g in tr2.gauges] == \
        [(0.0, "health.spectral_gap", 0.25),
         (1.5, "health.effective_neighbors", 3.5)]
    assert tr2.signature() == tr.signature()


def test_trace_without_gauges_has_no_gauges_key(tmp_path):
    tr = Trace(1)
    tr.record(TraceRecord(0, 0.5, "compute_done", 0, round=1, loss=1.0))
    assert "gauges" not in tr.to_json()
    assert Trace.load(tr.save(str(tmp_path / "t.json"))).gauges == []


# ---------------------------------------------------------------------------
# Traced outage sim → Chrome-trace export + report
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_outage_run(tmp_path_factory):
    run_dir = str(tmp_path_factory.mktemp("outage-run"))
    topo = T.hier(2, 2)
    scen = scenarios.regional_outage(pod=1, start=2.0, duration=4.0, seed=3)
    with telemetry.run(run_dir):
        r = _sim("hier", topo, rounds=10, scenario=scen, mesh="topology",
                 barrier_timeout=1.5, health=True, run_dir=run_dir)
    return run_dir, r


def test_traced_run_emits_bundle(traced_outage_run):
    run_dir, r = traced_outage_run
    for f in ("trace.json", "perfetto.json", "telemetry.json"):
        assert os.path.exists(os.path.join(run_dir, f)), f
    prov = json.load(open(os.path.join(run_dir, "trace.json")))[
        "meta"]["provenance"]
    assert prov["schema_version"] == telemetry.SCHEMA_VERSION
    assert "config_digest" in prov
    # the outage shows as a gauge dip and recovery
    gaps = [g.value for g in r.trace.gauges
            if g.name == "health.spectral_gap"]
    assert len(gaps) >= 3
    assert min(gaps) < gaps[0] and gaps[-1] == pytest.approx(gaps[0])


def test_perfetto_export_is_valid_and_lossless(traced_outage_run):
    run_dir, r = traced_outage_run
    doc = json.load(open(os.path.join(run_dir, "perfetto.json")))
    assert telemetry.validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    # worker lanes: one thread_name metadata per worker
    lanes = {e["tid"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name" and e["pid"] == 1}
    assert lanes >= set(range(r.trace.M))
    # link-fault duration events + gauge counter tracks + round slices
    assert any(e["ph"] == "X" and e["name"].startswith("fault") for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "health.spectral_gap"
               for e in evs)
    n_rounds = sum(1 for e in evs
                   if e["ph"] == "X" and e["name"].startswith("round "))
    n_dones = sum(1 for rec in r.trace.records
                  if rec.kind == "compute_done" and not rec.retried)
    assert n_rounds == n_dones  # lossless: every commit is a slice
    # every ARRIVAL becomes a link-lane slice spanning its wire time
    n_arr = sum(1 for e in evs if e["ph"] == "X" and e.get("pid") == 2
                and "→" in e["name"])
    assert n_arr == sum(1 for rec in r.trace.records
                        if rec.kind == "arrival")


def test_validate_chrome_trace_rejects_malformed():
    assert telemetry.validate_chrome_trace([]) != []
    assert telemetry.validate_chrome_trace({}) != []
    bad = {"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 0,
                            "ts": -5, "dur": 1}]}
    assert any("bad ts" in e for e in telemetry.validate_chrome_trace(bad))
    bad = {"traceEvents": [{"ph": "C", "name": "c", "pid": 1, "ts": 0,
                            "args": {"v": "high"}}]}
    assert any("numeric args" in e
               for e in telemetry.validate_chrome_trace(bad))
    good = {"traceEvents": [{"ph": "i", "s": "t", "name": "ok", "pid": 1,
                             "tid": 0, "ts": 0.0}]}
    assert telemetry.validate_chrome_trace(good) == []


def test_report_summarize_and_check(traced_outage_run, capsys):
    from repro.telemetry import report

    run_dir, r = traced_outage_run
    summary = report.summarize(run_dir)
    assert summary["workers"] == r.trace.M
    assert summary["links"]  # per-class accounting present
    assert "health.spectral_gap" in summary["gauges"]
    assert summary["gauges"]["health.spectral_gap"]["n"] >= 3
    text = report.render(summary)
    assert "health.spectral_gap" in text and "dci" in text

    rc = report.main([run_dir, "--check"])
    assert rc == 0
    assert os.path.exists(os.path.join(run_dir, "report.json"))
    out = capsys.readouterr().out
    assert "perfetto.json OK" in out


def test_report_missing_trace_raises(tmp_path):
    from repro.telemetry import report

    with pytest.raises(FileNotFoundError):
        report.summarize(str(tmp_path))


# ---------------------------------------------------------------------------
# Provenance
# ---------------------------------------------------------------------------


def test_provenance_header_keys_and_digest_stability():
    p = telemetry.provenance(config={"a": 1, "b": [2, 3]}, writer="t")
    assert p["schema_version"] == telemetry.SCHEMA_VERSION
    assert isinstance(p["git_sha"], str) and p["git_sha"]
    assert p["writer"] == "t"
    # digest is key-order independent and value sensitive
    assert telemetry.config_digest({"a": 1, "b": 2}) == \
        telemetry.config_digest({"b": 2, "a": 1})
    assert telemetry.config_digest({"a": 1}) != \
        telemetry.config_digest({"a": 2})
    assert telemetry.config_digest({"a": 1}).startswith("sha256:")


def test_stamp_sets_header_once_and_passes_non_dicts():
    payload = {"x": 1}
    telemetry.stamp(payload, writer="w1")
    first = payload["provenance"]
    telemetry.stamp(payload, writer="w2")   # no overwrite
    assert payload["provenance"] is first
    assert payload["provenance"]["writer"] == "w1"
    assert telemetry.stamp([1, 2]) == [1, 2]


def test_bench_save_json_stamps_and_registers(tmp_path, monkeypatch):
    from benchmarks import common

    monkeypatch.setattr(common, "RESULTS", str(tmp_path))
    n0 = len(common.ARTIFACTS)
    path = common.save_json("unit", {"rows": [1, 2]})
    blob = json.load(open(path))
    assert blob["provenance"]["schema_version"] == telemetry.SCHEMA_VERSION
    assert common.ARTIFACTS[n0:] == [("unit", path)]


# ---------------------------------------------------------------------------
# Sink mechanics
# ---------------------------------------------------------------------------


def test_null_sink_is_inert_and_reusable():
    tel = telemetry.NULL
    assert tel.active is False
    with tel.span("x") as s:
        assert s is None
    with tel.span("x", steps=1) as s:   # a fresh profiler annotation each time
        assert s is None
    tel.counter("c")
    tel.gauge("g", 1.0)
    tel.save()


def test_run_context_installs_saves_and_restores(tmp_path):
    run_dir = str(tmp_path / "rd")
    assert telemetry.get() is telemetry.NULL
    with telemetry.run(run_dir, meta={"k": "v"}) as tel:
        assert telemetry.get() is tel and telemetry.enabled()
        tel.counter("n", 2)
        tel.counter("n", 3)
        with tel.span("work", tag="a"):
            pass
        tel.instant("evt")
    assert telemetry.get() is telemetry.NULL
    blob = json.load(open(os.path.join(run_dir, "telemetry.json")))
    assert blob["meta"] == {"k": "v"}
    assert blob["counters"] == {"n": 5}
    assert blob["spans"][0]["name"] == "work"
    assert blob["spans"][0]["attrs"] == {"tag": "a"}
    assert blob["instants"][0]["name"] == "evt"
    assert blob["provenance"]["schema_version"] == telemetry.SCHEMA_VERSION
