"""A run with the timed path broken underneath comes out not correct,
once for each fault a serving cell can have; a sound run comes out
correct.  The harness's look for a chip is skipped: CPU, tiny cells."""
import numpy as np
import pytest

from bench import controls, run
from bench.tests import tiny

SEED = 2**31 + 4242


def _run(cell_name, patch=None, seconds=3.0, controls=None):
    c = tiny.cell(cell_name)
    return run.run(c["name"], SEED, seconds, False, require_chip=False,
                   cell=c, patch=patch, controls=controls)


def altered_tokens(batcher):
    """A token altered where it is produced: the decode step's choice is
    replaced by its neighbour in the vocabulary."""
    eng, decode = batcher.engine, batcher.engine.decode
    V = batcher.cfg.vocab_size

    def broken(toks, active, sampling):
        nxt, margins, logps = decode(toks, active, sampling)
        return (nxt + 1) % V, margins, logps

    eng.decode = broken


def unchanged_state(batcher):
    """A step that returns its state unchanged: the decode step's KV
    writes are thrown away (a copy is kept, as the step donates its
    input)."""
    import jax
    import jax.numpy as jnp

    eng, decode = batcher.engine, batcher.engine.decode

    def broken(toks, active, sampling):
        old = jax.tree.map(jnp.copy, eng.cache)
        out = decode(toks, active, sampling)
        eng.cache = old
        return out

    eng.decode = broken


@pytest.mark.parametrize("cell", ["qwen3-0.6b.chat", "qwen1.5-4b.decode"])
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", ["qwen3-0.6b.chat", "qwen1.5-4b.decode"])
@pytest.mark.parametrize("fault", [altered_tokens, unchanged_state])
def test_fault_is_not_correct(cell, fault):
    res = _run(cell, patch=fault)
    assert not res["correct"], res["checks"]
    assert res["checks"]["greedy_gap"]["value"] > \
        res["checks"]["greedy_gap"]["limit"]


def test_sampled_token_outside_nucleus_is_caught():
    """Only the sampled requests' tokens altered: the nucleus check, not
    the greedy one, fails."""
    def sampled_only(batcher):
        eng, decode = batcher.engine, batcher.engine.decode
        V = batcher.cfg.vocab_size

        def broken(toks, active, sampling):
            nxt, margins, logps = decode(toks, active, sampling)
            hot = np.asarray(sampling.temperature) > 0
            return np.where(hot, (nxt + V // 2) % V, nxt), margins, logps

        eng.decode = broken

    res = _run("qwen3-0.6b.chat", patch=sampled_only)
    assert not res["correct"]
    assert res["checks"]["greedy_gap"]["value"] <= \
        res["checks"]["greedy_gap"]["limit"]
    assert res["checks"]["nucleus_excess"]["value"] > \
        res["checks"]["nucleus_excess"]["limit"]


@pytest.mark.parametrize("cell", ["qwen3-0.6b.chat", "qwen1.5-4b.decode"])
def test_controls_are_not_correct(cell):
    """The float8 reference in the program's place fails the greedy gap,
    and sampled tokens altered where they are produced fail the nucleus
    check, through the harness's own comparison; the run itself stays
    correct."""
    vocab = tiny.conf(tiny.cell(cell)["config"])["config"]["vocab_size"]
    res = _run(cell, controls={
        "fp8": controls.fp8,
        "altered": lambda r, w, a: controls.altered(vocab, SEED)})
    assert res["correct"], res["checks"]
    fp8 = res["controls"]["fp8"]
    assert not fp8["correct"]
    assert fp8["checks"]["greedy_gap"]["value"] > \
        fp8["checks"]["greedy_gap"]["limit"]
    if "nucleus_excess" in res["checks"]:
        alt = res["controls"]["altered"]
        assert not alt["correct"]
        assert alt["checks"]["nucleus_excess"]["value"] > \
            alt["checks"]["nucleus_excess"]["limit"]
