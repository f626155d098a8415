"""The compiled serving steps carry the named scopes of
serving/telemetry.DEVICE_SCOPES in their ops' metadata, and the scopes
change nothing else: with them and without, the compiled programs hold
the same instructions.

Each engine's jitted steps are lowered with the arguments of a real
batcher run (the first call of each), then compiled on the CPU."""
import contextlib
import re

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import params as Pm
from repro.serving.config import ServingConfig
from repro.serving.scheduler import ContinuousBatcher, Request
from repro.serving.telemetry import DEVICE_SCOPES

ENGINES = {"paged_xla": dict(cache_layout="paged", kernel="xla"),
           "paged_pallas": dict(cache_layout="paged", kernel="pallas"),
           "dense": dict(cache_layout="dense")}


def _setup(arch: str):
    cfg = get_smoke_config(arch)
    params, _ = Pm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def setup():
    return _setup("qwen3_0_6b")


def _expected(cfg, engine: str, step: str) -> set:
    """The scopes a step program holds ops under.  A paged prefill of a
    pure-attention model has no step-level pool op: its slot slice and
    update pass the shared pools through whole (only recurrent lanes
    are sliced)."""
    if (step == "prefill" and engine.startswith("paged")
            and cfg.block_kind == "attention"):
        return {"attn", "sample"}
    return set(DEVICE_SCOPES)


def _lowered_steps(cfg, params, serving: dict) -> dict:
    """{"decode": Lowered, "prefill": Lowered} of one engine, lowered
    from the arguments of its first decode and prefill calls."""
    eng = ContinuousBatcher(cfg, params, ServingConfig(
        n_slots=2, capacity=64, **serving))
    lowered = {}
    for name in ("decode", "prefill"):
        fn = getattr(eng.engine, "_" + name)

        def first_call(*args, _fn=fn, _name=name):
            lowered.setdefault(_name, _fn.lower(*args))
            return _fn(*args)

        setattr(eng.engine, "_" + name, first_call)
    rng = np.random.default_rng(0)
    eng.submit([Request(rid=0, prompt=rng.integers(1, 100, 5).tolist(),
                        max_new=3)])
    eng.run()
    assert set(lowered) == {"decode", "prefill"}
    return lowered


def _op_names(hlo_text: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def _instructions(hlo_text: str) -> list[str]:
    """The compiled module's instructions without their metadata, each
    %name replaced by the order of its first appearance (the lowering
    numbers names by what it has seen, metadata included)."""
    lines = [re.sub(r",? metadata=\{[^}]*\}", "", line)
             for line in hlo_text.splitlines()
             if re.match(r"\s*(ROOT )?%", line)]
    order: dict = {}
    return [re.sub(r"%[\w.\-]+",
                   lambda m: f"%{order.setdefault(m.group(), len(order))}",
                   line) for line in lines]


@pytest.mark.parametrize("arch,engine", [
    ("qwen3_0_6b", "paged_xla"), ("qwen3_0_6b", "paged_pallas"),
    ("qwen3_0_6b", "dense"), ("zamba2_2_7b", "paged_xla")])
def test_compiled_steps_carry_every_device_scope(arch, engine):
    cfg, params = _setup(arch)
    for step, low in _lowered_steps(cfg, params, ENGINES[engine]).items():
        names = _op_names(low.compile().as_text())
        scopes = {part for n in names for part in n.split("/")}
        assert scopes & set(DEVICE_SCOPES) == \
            _expected(cfg, engine, step), (step, names[:5])


@pytest.mark.parametrize("engine", ["paged_xla", "dense"])
def test_scopes_leave_the_compiled_ops_unchanged(setup, engine,
                                                 monkeypatch):
    cfg, params = setup
    scoped = {k: low.compile().as_text() for k, low in
              _lowered_steps(cfg, params, ENGINES[engine]).items()}
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = {k: low.compile().as_text() for k, low in
             _lowered_steps(cfg, params, ENGINES[engine]).items()}
    for step in scoped:
        assert not any(s in n.split("/") for n in _op_names(plain[step])
                       for s in DEVICE_SCOPES)
        assert _instructions(scoped[step]) == _instructions(plain[step])
