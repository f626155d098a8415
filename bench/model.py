"""A configuration file's sizes as the program's model config, and the
weights the benchmark makes for it.

The weights are the benchmark's, not the program's: one jitted call
draws every leaf from the run's seed, on the device, in the dtype they
are served in, laid out as the program's parameter tree.  The plain
reference (bench/reference.py) reads the same arrays, so the program
and the reference compute with one set of numbers that neither made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# seeds reach a little past 2**31: fold the high word in, so every seed
# gives its own key whatever width JAX keeps integers in
_LO = 2**32


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file (its published
    keys under "config", its architecture under "architecture")."""
    from repro.models.config import ModelConfig

    c, arch = conf["config"], conf["architecture"]
    hidden, heads = c["hidden_size"], c["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], arch_type="dense",
        n_layers=c["num_hidden_layers"], d_model=hidden,
        n_heads=heads, n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", hidden // heads),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=arch["qkv_bias"], qk_norm=arch["qk_norm"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        remat=False)


def key_for(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed % _LO), seed // _LO)


def shapes(cfg) -> dict:
    """(shape, std) of every leaf, in the program's tree: embedding,
    optional untied head, final norm, and the layers stacked on a
    leading axis.  A std of None marks a norm gain (ones)."""
    L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
    qd, kvd, hd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim, \
        cfg.head_dim
    # logits of std ~2.5 over the vocabulary: a peaked distribution, so
    # top-p keeps a small nucleus as it does for a trained model
    out_std = 2.5 / D ** 0.5
    attn = {"wq": ((L, D, qd), D ** -0.5), "wk": ((L, D, kvd), D ** -0.5),
            "wv": ((L, D, kvd), D ** -0.5), "wo": ((L, qd, D), qd ** -0.5)}
    if cfg.qkv_bias:
        attn.update(bq=((L, qd), 0.5), bk=((L, kvd), 0.5),
                    bv=((L, kvd), 0.5))
    if cfg.qk_norm:
        attn.update(q_norm=((L, hd), None), k_norm=((L, hd), None))
    tree = {"embed": {"tok": ((V, D), out_std)},
            "final_norm": ((D,), None),
            "layers": {"ln1": ((L, D), None), "ln2": ((L, D), None),
                       "attn": attn,
                       "mlp": {"w_gate": ((L, D, F), D ** -0.5),
                               "w_up": ((L, D, F), D ** -0.5),
                               "w_down": ((L, F, D), F ** -0.5)}}}
    if not cfg.tie_embeddings:
        tree["lm_head"] = ((D, V), out_std)
    return tree


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_weights(cfg, seed: int):
    """Every leaf drawn from `seed` in one jitted call on the default
    device, in the served dtype."""
    spec = shapes(cfg)
    dtype = jnp.dtype(cfg.dtype)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, std) in zip(keys, leaves):
            if std is None:
                out.append(jnp.ones(shape, dtype))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * std).astype(dtype))
        return out

    return jax.tree.unflatten(treedef, draw(key_for(seed)))
