"""Training loop: drives the decentralized (or baseline) train step, logs the
paper's gradient statistics, and periodically checkpoints.

This is the host-side orchestration layer; the math lives in
``repro.core.decentralized``.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Iterable

import jax
import numpy as np

from repro import telemetry
from repro.core.decentralized import StepMetrics, TrainState, init_state, make_train_step
from repro.core.gossip import GossipSpec
from repro.optim import Optimizer
from repro.train import checkpoint as ckpt_lib

PyTree = Any


@dataclasses.dataclass
class History:
    loss: list[float] = dataclasses.field(default_factory=list)
    grad_energy: list[float] = dataclasses.field(default_factory=list)
    grad_spread: list[float] = dataclasses.field(default_factory=list)
    mean_grad_norm: list[float] = dataclasses.field(default_factory=list)
    param_spread: list[float] = dataclasses.field(default_factory=list)
    step_time: list[float] = dataclasses.field(default_factory=list)

    def append(self, m: StepMetrics, dt: float) -> None:
        self.loss.append(float(m.loss))
        self.grad_energy.append(float(m.grad_energy))
        self.grad_spread.append(float(m.grad_spread))
        self.mean_grad_norm.append(float(m.mean_grad_norm))
        self.param_spread.append(float(m.param_spread))
        self.step_time.append(dt)

    def extend_from_device(self, pending: list[StepMetrics],
                           window_start: float) -> None:
        """Batched host transfer: ONE device_get for a whole log window.

        The per-step ``float()`` calls in :meth:`append` each forced a
        device→host sync, serializing dispatch with the device — five
        blocking transfers *per step*. Here the device arrays accumulate
        asynchronously and land in one ``jax.device_get`` per ``log_every``
        window (EXPERIMENTS.md §Perf, "Batched metric host-sync").

        The window is clocked AFTER the (blocking) transfer: device_get
        waits for every step in the window to finish, so the recorded
        per-step time covers real execution, not just async dispatch.
        """
        if not pending:
            return
        host = jax.device_get(pending)
        dt = (time.perf_counter() - window_start) / len(pending)
        for m in host:
            self.append(m, dt)

    def as_arrays(self) -> dict[str, np.ndarray]:
        return {k: np.asarray(v) for k, v in dataclasses.asdict(self).items()}


def train(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    params0: PyTree,
    optimizer: Optimizer,
    batches: Iterable[PyTree],
    *,
    steps: int,
    gossip: GossipSpec | None = None,
    mode: str = "gossip",
    mesh=None,
    param_specs: PyTree | None = None,
    log_every: int = 50,
    ckpt_path: str | None = None,
    ckpt_every: int = 0,
    ckpt_sharded: bool = False,
    verbose: bool = True,
    routing: Callable[[PyTree, PyTree], jax.Array] | None = None,
) -> tuple[TrainState, History]:
    """Run `steps` iterations; `batches` yields per-step batch pytrees.

    ``routing(params, batch)`` gives one worker's (expert layers, held
    experts) counts of routed (token, expert) pairs (e.g.
    ``lambda p, b: model.routing_counts(p, cfg, b["tokens"])``);
    with a recording sink, each log window emits worker 0's at its last
    step: the counter ``moe.held_pairs`` (pairs routed to held experts,
    summed over layers) and the gauge ``moe.load_max_over_mean`` (the
    largest, over layers, of the busiest held expert's load over the
    mean). Nothing runs with the null sink.

    ``mesh`` accepts a raw jax mesh or a :class:`~repro.launch.mesh.WorkerMesh`;
    ``param_specs`` (shardings.param_pspecs output) composes gossip with
    model-sharded replicas — see core/bus.mix_bus.

    Host/device sync discipline: metrics are NOT fetched per step — device
    arrays accumulate and transfer in one batch per ``log_every`` window
    (plus checkpoint/final boundaries), so step dispatch runs ahead of the
    device instead of blocking five times per iteration. Checkpoints follow
    the same discipline: saves go through
    :class:`~repro.train.checkpoint.AsyncCheckpointWriter` — a device-side
    snapshot (safe against the donated state) handed to a background writer
    thread — so the synchronous ``np.savez`` never stalls the loop.
    ``ckpt_sharded=True`` writes per-worker shard files keyed by the
    WorkerMesh coordinates (``checkpoint.save_sharded``) instead of
    device-getting the full stacked tree on one host.
    """
    # Donating the state makes the step in-place on HBM: the params / opt
    # buffers (and the gossip bus pack buffers) reuse the incoming allocation
    # instead of doubling the parameter footprint every iteration. The
    # caller's params0 leaves are copied first — donation would otherwise
    # delete them out from under the caller on backends where it is real.
    from repro.launch.mesh import WorkerMesh

    raw_mesh = WorkerMesh.raw(mesh)
    step_fn = jax.jit(make_train_step(loss_fn, optimizer, gossip=gossip,
                                      mode=mode, mesh=mesh,
                                      param_specs=param_specs),
                      donate_argnums=(0,))
    params0 = jax.tree.map(lambda x: x.copy() if hasattr(x, "copy") else x,
                           params0)
    state = init_state(params0, optimizer)
    hist = History()
    it = iter(batches)
    pending: list[StepMetrics] = []
    t_win = time.perf_counter()
    # Telemetry rides the existing amortized boundaries: one emit batch per
    # log window (inside flush), nothing per step. The two host spans,
    # ``train.dispatch`` (the window's step dispatches) and
    # ``train.host_sync`` (its one fetch), reach a profile whatever the sink;
    # the numerics are untouched either way, so instrumented-but-disabled
    # train() bit-matches plain train().
    tel = telemetry.get()
    dispatch = None       # the open train.dispatch span of this log window
    route = jax.jit(routing) if routing is not None else None
    batch = None

    def flush() -> None:
        nonlocal t_win, dispatch
        if dispatch is not None:
            dispatch.__exit__(None, None, None)
            dispatch = None
        n = len(pending)
        if n:
            with tel.span("train.host_sync", steps=n):
                hist.extend_from_device(pending, t_win)
        if tel.active and n:
            dur = time.perf_counter() - t_win
            tel.complete("train.window", tel.now() - dur, dur, steps=n)
            tel.counter("train.steps", n)
            tel.gauge("train.loss", hist.loss[-1])
            if routing is not None:
                c = np.asarray(route(*jax.tree.map(lambda a: a[0],
                                                   (state.params, batch))),
                               np.float64)
                if c.size:
                    tel.counter("moe.held_pairs", float(c.sum()))
                    tel.gauge("moe.load_max_over_mean", float(np.max(
                        c.max(1) / np.maximum(c.mean(1), 1e-9))))
        pending.clear()
        t_win = time.perf_counter()

    writer = ckpt_lib.AsyncCheckpointWriter() if ckpt_path else None
    ckpt_kw = {}
    if ckpt_sharded:
        ckpt_kw = dict(sharded=True,
                       wmesh=mesh if isinstance(mesh, WorkerMesh) else None)
    ctx = jax.set_mesh(raw_mesh) if raw_mesh is not None else _nullcontext()
    try:
        with ctx:
            for k in range(steps):
                batch = next(it)
                if dispatch is None:
                    dispatch = tel.span("train.dispatch")
                    dispatch.__enter__()
                state, metrics = step_fn(state, batch)
                pending.append(metrics)
                if k % log_every == 0 or k == steps - 1:
                    flush()
                    if verbose:
                        print(f"step {k:5d}  loss {hist.loss[-1]:.5f}  "
                              f"E {hist.grad_energy[-1]:.3e}  Esp {hist.grad_spread[-1]:.3e}  "
                              f"spread {hist.param_spread[-1]:.3e}")
                if ckpt_path and ckpt_every and (k + 1) % ckpt_every == 0:
                    flush()
                    writer.save(ckpt_path, state.params, step=k + 1, **ckpt_kw)
                    tel.counter("train.checkpoints")
        flush()
        if ckpt_path:
            writer.save(ckpt_path, state.params, step=steps, **ckpt_kw)
            tel.counter("train.checkpoints")
        if writer is not None:
            writer.close()        # surfaces background write errors
    except BaseException:
        # the loop is already failing: drain the writer but don't let a
        # secondary checkpoint-write error mask the real exception
        if dispatch is not None:
            dispatch.__exit__(None, None, None)
        if writer is not None:
            try:
                writer.close()
            except Exception:
                pass
        raise
    return state, hist


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


# ---------------------------------------------------------------------------
# Event-driven simulated training (repro.sim)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """How a simulated fleet responds to step failures and rejoins.

    A failed step attempt (the ``fault_inject`` hook of
    :func:`run_simulated`) is retried after exponential backoff
    (``backoff_base * backoff_factor**attempt`` virtual seconds) up to
    ``max_retries`` times; once retries exhaust, the worker's parameter
    slice is restored — from the consensus average of the last checkpoint
    when ``ckpt_path`` is set and one has landed, else from the live
    fleet's current mean — and the step proceeds from the restored state.
    Rejoining workers (churn JOIN events) restore the same way. With
    ``ckpt_path`` set, the stacked state is checkpointed through the
    :class:`~repro.train.checkpoint.AsyncCheckpointWriter` every
    ``ckpt_every`` commits (sharded per worker when ``ckpt_sharded``).
    """

    max_retries: int = 3
    backoff_base: float = 0.5
    backoff_factor: float = 2.0
    ckpt_path: str | None = None
    ckpt_every: int = 10
    ckpt_sharded: bool = True

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        if not self.backoff_base > 0:
            raise ValueError(f"backoff_base must be positive, got {self.backoff_base}")
        if not self.backoff_factor >= 1.0:
            raise ValueError(f"backoff_factor must be >= 1, got {self.backoff_factor}")
        if self.ckpt_every <= 0:
            raise ValueError(f"ckpt_every must be positive, got {self.ckpt_every}")


class _RecoveryManager:
    """Wires a :class:`RecoveryPolicy` into a sim protocol (its ``recovery``
    attribute): answers the per-attempt failure/backoff question, writes
    periodic consensus checkpoints, and restores failed/rejoining workers."""

    def __init__(self, policy: RecoveryPolicy, executor,
                 fault_inject: Callable[[int, int, int], bool] | None = None):
        self.policy = policy
        self.executor = executor
        self.fault_inject = fault_inject
        self.engine = None   # set by run_simulated once the Engine exists
        self.attempts: dict[tuple[int, int], int] = {}
        self.stats = {"step_failures": 0, "retries": 0, "restores": 0,
                      "rejoins": 0, "checkpoints": 0}
        self.writer = ckpt_lib.AsyncCheckpointWriter() \
            if policy.ckpt_path else None
        self._saved_any = False
        self._commits = 0

    # -- protocol hooks ---------------------------------------------------

    def step_failure_delay(self, j: int, k: int) -> float | None:
        """None → the attempt proceeds; a float → this attempt failed,
        retry after that many virtual seconds. Exhausted retries restore
        worker j and let the attempt proceed from the restored state."""
        if self.fault_inject is None:
            return None
        a = self.attempts.get((j, k), 0)
        if not self.fault_inject(j, k, a):
            self.attempts.pop((j, k), None)
            return None
        self.stats["step_failures"] += 1
        a += 1
        self.attempts[(j, k)] = a
        if a <= self.policy.max_retries:
            self.stats["retries"] += 1
            return self.policy.backoff_base * \
                self.policy.backoff_factor ** (a - 1)
        self.attempts.pop((j, k), None)
        self._restore(j)
        return None

    def after_commit(self, j: int, k: int) -> None:
        if self.writer is None:
            return
        self._commits += 1
        if self._commits % self.policy.ckpt_every == 0:
            self.writer.save(self.policy.ckpt_path, self.executor.W, step=k,
                             sharded=self.policy.ckpt_sharded)
            self._saved_any = True
            self.stats["checkpoints"] += 1

    def on_rejoin(self, j: int) -> None:
        self.stats["rejoins"] += 1
        self._restore(j)

    # -- restore ----------------------------------------------------------

    def _restore(self, j: int) -> None:
        """Overwrite worker j's slice with the latest consensus estimate:
        the worker-mean of the last sharded/monolithic checkpoint if one
        landed, else the live fleet's current mean (excluding j)."""
        self.stats["restores"] += 1
        ex = self.executor
        w = None
        if self.writer is not None and self._saved_any:
            self.writer.wait()   # the snapshot must be fully on disk
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), ex.W)
            stacked = ckpt_lib.restore(self.policy.ckpt_path, like=like)
            w = ckpt_lib.consensus_params(stacked)
        if w is None:
            mask = np.asarray(self.engine.alive).copy()
            mask[j] = False
            if not mask.any():
                mask[:] = True
            w = ex.mean_params(mask)
        ex.W = ex.set_slice(ex.W, j, w)

    def close(self) -> None:
        if self.writer is not None:
            self.writer.close()


@dataclasses.dataclass
class SimRun:
    """Result of a simulated run: final stacked state + the event trace."""

    params: PyTree           # (M, ...) stacked parameters at the end
    opt_state: PyTree
    trace: Any               # repro.sim.trace.Trace
    rounds: np.ndarray       # per-worker completed rounds
    virtual_time: float      # final virtual clock

    def loss_curve(self) -> tuple[np.ndarray, np.ndarray]:
        """(virtual times, per-round mean train-batch loss)."""
        return self.trace.round_loss_curve()

    def eval_curve(self) -> tuple[np.ndarray, np.ndarray]:
        """(virtual times, global loss of the worker-mean parameters)."""
        return self.trace.eval_curve()


def _meshless_payload_bytes(params_template: PyTree,
                            wire_dtype: str | None = None) -> int:
    """Per-message bytes of one whole-replica gossip payload: the bus
    layout-v2 plan's padded buffer for an unsharded (k = 1) replica
    (``wire_dtype`` prices the compressed DCI lane of the same plan)."""
    from repro.core.bus import plan_layout

    return plan_layout(params_template, lead_ndim=0).padded_bytes(wire_dtype)


def run_simulated(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    params0: PyTree,
    optimizer: Optimizer,
    batches: Iterable[PyTree],
    *,
    gossip: GossipSpec,
    protocol: str = "sync",
    scenario=None,
    mesh=None,
    rounds: int = 100,
    eval_fn: Callable[[PyTree], float] | None = None,
    eval_every: int = 1,
    max_events: int | None = None,
    max_time: float | None = None,
    trace_path: str | None = None,
    barrier_timeout: float | None = None,
    degrade_mode: str = "reabsorb",
    commit: str = "slice",
    commit_batch: bool = True,
    snap_depth: int = 4,
    dci_dtype: str | None = None,
    recovery: RecoveryPolicy | None = None,
    fault_inject: Callable[[int, int, int], bool] | None = None,
    health: "bool | object" = False,
    run_dir: str | None = None,
) -> SimRun:
    """Train under virtual wall-clocks on the discrete-event simulator.

    Executes *real* train steps — the sync protocol runs the very
    ``make_train_step`` program ``train()`` jits, so with deterministic
    compute times its trajectory bit-matches the non-simulated loop — while
    the engine advances per-worker clocks through the scenario's straggler
    distribution, link delays, churn, and topology switches.

    Args:
      loss_fn / optimizer: as in :func:`train`.
      params0: stacked parameters with leading worker dim M
        (``replicate_for_workers``).
      batches: per-step batch iterable, leaves shaped (M, B, ...) — same
        contract as :func:`train`; replayed out-of-order via a cache for the
        asynchronous protocols.
      gossip: GossipSpec (topology + mixing backend; runs meshless).
      protocol: 'sync' | 'async' | 'stale' | 'hier'
        (see ``repro.sim.protocols``).
      scenario: ``repro.sim.Scenario`` (default: ideal unit-time world).
      mesh: makes the engine mesh-aware (two link classes): a
        ``sim.MeshSpec``, a ``launch.mesh.WorkerMesh`` (mirrored — worker
        groups from the pod axis, per-message payload bytes from the bus
        layout plan over ``params0``), or the string ``'topology'`` to adopt
        a hierarchical (kronecker) topology's own pod assignment. Required
        for scenarios with per-class ``link_classes`` costs.
      rounds: per-worker round budget (protocols stop scheduling past it).
      eval_fn: optional (mean-params pytree) -> float global loss; recorded
        per round (sync/hier: every `eval_every` rounds when the whole round
        completes; async/stale: every `eval_every` completed computations).
      trace_path: if set, write the JSON event trace there.
      barrier_timeout / degrade_mode: makes the barrier protocols
        (sync/hier) churn-capable — a worker whose barrier stalls for
        `barrier_timeout` virtual seconds commits over the snapshots that
        arrived, with the survivor-repaired weight column (`degrade_mode`
        'reabsorb' | 'renormalize'). Fault-free runs are unaffected.
      commit / commit_batch / snap_depth: barrier-protocol commit
        architecture. ``commit='slice'`` (default) runs the O(M) compiled
        per-slice step per completion, mixing over the round-tagged
        snapshot planes (``snap_depth`` deep); with ``commit_batch=True``
        same-instant completions additionally ride ONE vmapped per-slice
        step (disabled automatically when a recovery manager is attached).
        ``commit='full'`` opts back into the O(M²) full M-row reference
        program — bit-identical trajectories either way (asserted in CI;
        exception: ``adafactor_like``'s factored second moment is not
        worker-elementwise, use ``commit='full'`` for bit-exactness there —
        per-slice runs with such a coupled optimizer are rejected at
        construction).
      dci_dtype: 'bfloat16' | 'int8' | None — compress the cross-pod (DCI)
        stage of the ``hier`` protocol: outgoing cross-pod snapshots are
        quantized through the bus wire format with error feedback
        (``repro.sim.protocols.HierGossip``), and with a mesh attached the
        engine charges DCI messages the compressed wire bytes
        (``BusLayout.padded_bytes(dci_dtype)``) instead of the exact
        payload. Intra-pod traffic stays exact; ``None`` (default) is
        bit-identical to the uncompressed protocol.
      recovery / fault_inject: attach a :class:`RecoveryPolicy`.
        ``fault_inject(worker, round, attempt) -> bool`` marks a step
        attempt as failed (retried with backoff per the policy; restored
        from the last consensus checkpoint once retries exhaust). Passing
        either enables the recovery manager; its counters land in
        ``trace.meta['recovery']``.
      health: emit gossip-health gauges (spectral gap / effective number of
        neighbors of the ACTIVE — survivor-repaired, fault-blocked — mixing
        matrix) onto the trace timeline at t=0 and on every matrix-changing
        event. True for defaults, or a ``telemetry.HealthConfig``. Gauges
        are excluded from ``Trace.signature()``, so determinism tests and
        signature bit-match guarantees are unaffected.
      run_dir: if set, export the full telemetry bundle there —
        ``trace.json`` (provenance-stamped meta), ``perfetto.json``
        (Chrome-trace timeline, loadable at ui.perfetto.dev), and
        ``telemetry.json`` when a telemetry sink is active. Summarize with
        ``python -m repro.telemetry.report <run_dir>``. Implies saving the
        trace even without ``trace_path``.
    """
    from repro import sim

    proto_cls = sim.PROTOCOLS.get(protocol)
    if proto_cls is None:
        raise ValueError(f"unknown protocol {protocol!r}; "
                         f"choose from {sorted(sim.PROTOCOLS)}")
    proto_kw = {}
    if barrier_timeout is not None:
        if protocol not in ("sync", "hier"):
            raise ValueError(
                "barrier_timeout configures the barrier protocols "
                f"(sync/hier); protocol {protocol!r} has no barrier")
        proto_kw = dict(barrier_timeout=barrier_timeout,
                        degrade_mode=degrade_mode)
    if protocol in ("sync", "hier"):
        proto_kw.update(commit=commit, commit_batch=commit_batch,
                        snap_depth=snap_depth)
    elif commit != "slice":
        raise ValueError(
            "commit configures the barrier protocols (sync/hier); "
            f"protocol {protocol!r} has no commit mode")
    if dci_dtype is not None:
        if protocol != "hier":
            raise ValueError(
                "dci_dtype compresses the cross-pod (DCI) stage of the "
                f"hier protocol; protocol {protocol!r} has no DCI stage")
        proto_kw.update(dci_dtype=dci_dtype)
    if mesh is not None:
        from repro.launch.mesh import WorkerMesh

        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), params0)
        if mesh == "topology":
            mesh = sim.MeshSpec.from_topology(gossip.topology)
        elif isinstance(mesh, WorkerMesh):
            mesh = mesh.sim_spec(params_template=template,
                                 dci_dtype=dci_dtype)
        if isinstance(mesh, sim.MeshSpec) and not mesh.payload_bytes:
            # fill in the per-message wire bytes from the bus layout plan so
            # bandwidth terms and the per-class byte accounting are real
            mesh = dataclasses.replace(
                mesh, payload_bytes=_meshless_payload_bytes(template))
        if dci_dtype is not None and isinstance(mesh, sim.MeshSpec) and \
                not mesh.dci_payload_bytes:
            # cross-pod messages ship the quantized image: charge the
            # compressed wire bytes (same plan, wire pricing) on DCI links
            mesh = dataclasses.replace(
                mesh, dci_payload_bytes=_meshless_payload_bytes(
                    template, dci_dtype))
    executor = sim.TrainExecutor(loss_fn, optimizer, params0, batches,
                                 gossip, commit=commit)
    if executor.coupled and protocol == "hier":
        raise ValueError(
            "the hier protocol commits per worker slice in both commit "
            "modes (its commit='full' only changes the mix-source "
            "assembly), so optimizers with cross-worker-coupled state "
            "cannot run on it. Use protocol='sync' with commit='full', or "
            "a worker-elementwise optimizer.")
    proto = proto_cls(executor=executor, eval_fn=eval_fn,
                      eval_every=eval_every, **proto_kw)
    mgr = None
    if recovery is not None or fault_inject is not None:
        mgr = _RecoveryManager(recovery or RecoveryPolicy(), executor,
                               fault_inject)
        proto.recovery = mgr
    eng = sim.Engine(gossip.topology, scenario, mesh=mesh, health=health)
    if mgr is not None:
        mgr.engine = eng
    try:
        eng.run(proto, until_round=rounds, max_events=max_events,
                max_time=max_time)
    finally:
        if mgr is not None:
            mgr.close()
    if mgr is not None:
        eng.trace.meta["recovery"] = dict(mgr.stats)
        tel = telemetry.get()
        if tel.active:
            for k, v in mgr.stats.items():
                tel.counter(f"recovery.{k}", v)
    if trace_path:
        eng.trace.save(trace_path)
    if run_dir:
        from repro.telemetry.perfetto import save_perfetto

        eng.trace.meta["provenance"] = telemetry.provenance(
            config=dict(protocol=protocol, rounds=rounds,
                        topology=gossip.topology.name,
                        M=gossip.topology.M,
                        scenario=eng.scenario.describe()),
            writer="run_simulated")
        eng.trace.save(os.path.join(run_dir, "trace.json"))
        save_perfetto(eng.trace, os.path.join(run_dir, "perfetto.json"))
        tel = telemetry.get()
        if tel.active:
            tel.save(os.path.join(run_dir, "telemetry.json"))
    return SimRun(params=executor.W, opt_state=executor.opt, trace=eng.trace,
                  rounds=proto.rounds.copy(), virtual_time=eng.clock)
