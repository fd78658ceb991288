"""Seeded weights for a decoder-only transformer, made on the device in one
jitted call, in the type they are served in.

The tree follows the layout the system under test declares for its dense
decoder (embed, layers[l].{ln1, ln2, attn.{wq, wk, wv, wo},
ffn.{w1, w3, w2}}, ln_f, unembed); the serve driver checks it against the
system's own declaration before use. Matrices are N(0, std^2); norms are
ones; a tied configuration gets unembed = embed^T.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_KINDS = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")


def decoder_shapes(config: dict) -> dict:
    d, H = config["hidden_size"], config["num_attention_heads"]
    G, ff = config["num_key_value_heads"], config["intermediate_size"]
    hd = d // H
    return {"wq": (d, H * hd), "wk": (d, G * hd), "wv": (d, G * hd),
            "wo": (H * hd, d), "w1": (d, ff), "w3": (d, ff), "w2": (ff, d)}


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _make(key, shape, dtype):
    cfg = dict(shape)
    L, d, V = cfg["layers"], cfg["d"], cfg["vocab"]
    std = cfg["std"]
    kinds = dict(cfg["kinds"])
    keys = jax.random.split(key, len(_KINDS) + 1)
    stacked = {n: (std * jax.random.normal(k, (L,) + kinds[n], jnp.float32))
               .astype(dtype) for n, k in zip(_KINDS, keys[1:])}
    embed = (std * jax.random.normal(keys[0], (V, d), jnp.float32)).astype(dtype)
    ones = jnp.ones((d,), dtype)
    layers = [{"ln1": ones, "ln2": ones,
               "attn": {n: stacked[n][l] for n in ("wq", "wk", "wv", "wo")},
               "ffn": {n: stacked[n][l] for n in ("w1", "w3", "w2")}}
              for l in range(L)]
    unembed = embed.T if cfg["tied"] else (
        std * jax.random.normal(jax.random.fold_in(keys[0], 1), (d, V),
                                jnp.float32)).astype(dtype)
    return {"embed": embed, "layers": layers, "ln_f": ones,
            "unembed": unembed}


def decoder_weights(config: dict, key, std: float = 0.02):
    """The weight tree for `config` (a configuration file's dict)."""
    dtype = jnp.dtype(config["torch_dtype"])
    shape = (("d", config["hidden_size"]), ("layers", config["num_hidden_layers"]),
             ("vocab", config["vocab_size"]), ("std", std),
             ("tied", bool(config["tie_word_embeddings"])),
             ("kinds", tuple(sorted(decoder_shapes(config).items()))))
    return jax.block_until_ready(_make(key, shape, dtype.name))
