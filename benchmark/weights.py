"""Seeded weights, made on the device in one jitted call, in the type they
are served in. The benchmark makes them (not ``models.init_params``): the
reference and the control take their inputs from the seed and nothing the
program has made. The tree's layout is the program's interface
(``models.param_axes``): stacked layers, ``wq [L, d, h, k]`` and so on.
"""

from __future__ import annotations

from typing import Any, Dict


#: toy widths for ``--rehearse-cpu`` and the CPU tests: control flow only
TOY_WIDTHS = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512,
              "sliding_window": 48}


def transformer_config(cf: Dict[str, Any], **overrides):
    """``TransformerConfig`` from a configuration file's published keys. The
    presets in ``models/config.py`` are not used: they depart from the
    published files (``rope_theta``, ``rms_norm_eps``)."""
    from ray_tpu.models.config import TransformerConfig

    prec = cf["precision"]
    kw = dict(
        vocab_size=cf["vocab_size"], d_model=cf["hidden_size"],
        n_layers=cf["num_hidden_layers"], n_heads=cf["num_attention_heads"],
        n_kv_heads=cf["num_key_value_heads"], head_dim=cf["head_dim"],
        d_ff=cf["intermediate_size"],
        max_seq_len=cf["max_position_embeddings"],
        mlp="swiglu", norm="rms", positions="rope",
        rope_theta=float(cf["rope_theta"]), norm_eps=float(cf["rms_norm_eps"]),
        tie_embeddings=bool(cf["tie_word_embeddings"]),
        attn_qkv_bias=bool(cf["attention_bias"]),
        sliding_window=(int(cf["sliding_window"])
                        if cf["use_sliding_window"] else 0),
        dtype=prec["activations"], param_dtype=prec["weights"])
    kw.update(overrides)
    return TransformerConfig(**kw)


def prng_key(seed: int):
    """A key from a seed that may pass 2**31 (PRNGKey takes 32 bits)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_params(config, seed: int):
    """``build_params`` as one jitted call on the default device."""
    import jax

    return jax.jit(lambda key: build_params(config, key))(prng_key(seed))


def build_params(config, key):
    """The parameter tree for ``config`` from a key (traceable): normal
    weights at the usual scales, norm gains and q/k/v biases away from their
    trivial values so that leaving one out shows in the logits."""
    import jax
    import jax.numpy as jnp

    c = config
    dt = jnp.dtype(c.param_dtype)
    d, hd, f, L = c.d_model, c.hdim, c.ff, c.n_layers
    h, kv, v = c.n_heads, c.kv_heads, c.vocab_size
    proj = d ** -0.5
    out = proj / (2 * L) ** 0.5

    names = ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
             "w_up", "w_down", "bq", "bk", "bv", "embed", "final_norm",
             "lm_head"]
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stacked(name, shape, std, mean=0.0):
        # one layer at a time: the float32 draw of a whole stacked leaf
        # (3.7 GB for an FFN matrix) never exists
        def one(k):
            x = jax.random.normal(k, shape, jnp.float32) * std + mean
            return x.astype(dt)
        return jax.lax.map(one, jax.random.split(ks[name], L))

    def flat(name, shape, std, mean=0.0):
        x = jax.random.normal(ks[name], shape, jnp.float32) * std + mean
        return x.astype(dt)

    layers = {
        "attn_norm": stacked("attn_norm", (d,), 0.1, 1.0),
        "wq": stacked("wq", (d, h, hd), proj),
        "wk": stacked("wk", (d, kv, hd), proj),
        "wv": stacked("wv", (d, kv, hd), proj),
        "wo": stacked("wo", (h, hd, d), out),
        "mlp_norm": stacked("mlp_norm", (d,), 0.1, 1.0),
        "w_gate": stacked("w_gate", (d, f), proj),
        "w_up": stacked("w_up", (d, f), proj),
        "w_down": stacked("w_down", (f, d), out),
    }
    if c.attn_qkv_bias:
        layers["bq"] = stacked("bq", (h, hd), 0.1)
        layers["bk"] = stacked("bk", (kv, hd), 0.1)
        layers["bv"] = stacked("bv", (kv, hd), 0.1)
    params = {"embed": flat("embed", (v, d), 0.02), "layers": layers,
              "final_norm": flat("final_norm", (d,), 0.1, 1.0)}
    if not c.tie_embeddings:
        params["lm_head"] = flat("lm_head", (d, v), proj)
    return params
