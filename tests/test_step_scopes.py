"""The train step names its layers in the compiled program's op metadata,
whatever telemetry sink is installed, and the names change no instruction.

A profile of the step is attributed to layers through the executable's
``as_text()``: every instruction carries an ``op_name`` such as
``jit(step)/model/vmap(transpose(jvp(lm_head)))/...``.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import run_in_subprocess
from repro import telemetry
from repro.configs.base import ModelConfig
from repro.core import topology as T
from repro.core.decentralized import (init_state, make_train_step,
                                      replicate_for_workers)
from repro.core.gossip import GossipSpec
from repro.models import model as M
from repro.optim import momentum_sgd

W = 4
TINY = {
    "dense": dict(arch_type="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, head_dim=16, d_ff=128),
    "ssm": dict(arch_type="ssm", n_layers=2, d_model=64, n_heads=0,
                n_kv_heads=0, head_dim=0, d_ff=0, ssm_state=16,
                ssm_headdim=16, ssm_chunk=16),
}
# every layer of the fused-bus step, as (scope, scope under it); inside a
# shard_map body the bus's scopes follow ``shard_map/``
STEP_LAYERS = (("optimizer", ""), ("stats", ""), ("gossip", "pack"),
               ("gossip", "mix"), ("gossip", "unpack"))
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_META = re.compile(r',? metadata=\{(?:[^}"]|"(?:[^"\\]|\\.)*")*\}')
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _compiled_text(arch: str) -> str:
    cfg = ModelConfig(name="tiny", vocab_size=256, tie_embeddings=True,
                      **TINY[arch])
    params = replicate_for_workers(M.init(jax.random.PRNGKey(0), cfg), W)
    opt = momentum_sgd(1e-2, 0.9)
    step = make_train_step(lambda p, b: M.loss_fn(p, cfg, b), opt,
                           gossip=GossipSpec(topology=T.make("ring", W),
                                             backend="fused"))
    batch = {"tokens": jnp.zeros((W, 1, 17), jnp.int32)}
    return jax.jit(step, donate_argnums=(0,)).lower(
        init_state(params, opt), batch).compile().as_text()


def _named(names, scope: str, sub: str = "") -> bool:
    pat = re.compile(rf"^jit\(step\)/{scope}/" + (rf"(.*/)?{sub}/" if sub
                                                   else ""))
    return any(pat.match(n) for n in names)


def _strip(text: str) -> str:
    """The instructions alone: metadata and stack-frame tables dropped."""
    out, skip = [], False
    for line in text.splitlines():
        if line.strip() in _TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
            continue
        if not skip:
            out.append(_META.sub("", line))
    return "\n".join(out)


@pytest.fixture(scope="module")
def dense_text():
    assert telemetry.get() is telemetry.NULL      # no sink: scopes anyway
    return _compiled_text("dense")


def test_vmapped_step_names_its_layers(dense_text):
    names = _OP_NAME.findall(dense_text)
    model = [n for n in names if n.startswith("jit(step)/model/")]
    assert any("transpose(" not in n for n in model)      # forward
    assert any("transpose(" in n for n in model)          # backward
    for scope, sub in STEP_LAYERS:
        assert _named(names, scope, sub), (scope, sub)
    for sub in ("attention", "mlp", "lm_head", "embed"):
        assert any(n.startswith("jit(step)/model/") and sub in n
                   for n in model), sub


def test_ssd_scope_reaches_the_compiled_step():
    names = _OP_NAME.findall(_compiled_text("ssm"))
    assert any(n.startswith("jit(step)/model/") and "ssd" in n
               for n in names)
    assert _named(names, "gossip", "mix")


def test_scopes_change_no_instruction(dense_text, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_text("dense")
    assert not any("/gossip/" in n for n in _OP_NAME.findall(bare))
    assert bare != dense_text
    assert _strip(bare) == _strip(dense_text)


def test_mesh_step_names_its_layers():
    """The sharded bus on four virtual devices: one worker per device, and
    two workers of a two-way model-sharded replica."""
    out = run_in_subprocess(r"""
import re
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import telemetry
from repro.core import topology as T
from repro.core.decentralized import (init_state, make_train_step,
                                      replicate_for_workers)
from repro.core.gossip import GossipSpec
from repro.launch.mesh import WorkerMesh, make_host_mesh
from repro.optim import momentum_sgd

assert telemetry.get() is telemetry.NULL
def loss(p, b): return jnp.sum(jnp.tanh(b @ p["w"] + p["b"]))
opt = momentum_sgd(0.05, 0.9)
for data, model in ((4, 1), (2, 2)):
    wm = WorkerMesh.from_mesh(make_host_mesh(data=data, model=model))
    spec = GossipSpec.for_mesh(T.undirected_ring(data), wm, backend="fused")
    pspecs = {"w": P("data", None, "model"),
              "b": P("data", "model")} if model > 1 else None
    with jax.set_mesh(wm.mesh):
        s = init_state(replicate_for_workers(
            {"w": jnp.full((16, 128), 0.1), "b": jnp.zeros(128)}, data),
            opt)
        step = make_train_step(loss, opt, gossip=spec, mesh=wm,
                               param_specs=pspecs)
        text = jax.jit(step).lower(s, jnp.ones((data, 8, 16))).compile() \
            .as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    model_ops = [n for n in names if n.startswith("jit(step)/model/")]
    assert any("transpose(" not in n for n in model_ops), data
    assert any("transpose(" in n for n in model_ops), data
    for scope, sub in LAYERS:
        pat = re.compile(rf"^jit\(step\)/{scope}/" + (rf"(.*/)?{sub}/"
                                                       if sub else ""))
        assert any(pat.match(n) for n in names), (data, model, scope, sub)
print("mesh-scopes-ok")
""".replace("LAYERS", repr(STEP_LAYERS)), n_devices=4)
    assert "mesh-scopes-ok" in out
