"""Plain float32 reference of a decoder-only transformer with grouped-query
attention, partial rotary embeddings, RMSNorm and a SwiGLU feed-forward
(Phi-3/Phi-4-mini family, `configs/phi4mini-prefix-kv.json`).

It imports nothing of the system under test. It reads a weight tree made by
the benchmark (`harness.weights`) and runs layer by layer on the device at
`Precision.HIGHEST`, one jitted layer at a time, so that the 32 layers never
sit on the device in float32 at once.

Departures from the published model, shared with the system under test and
listed in the configuration file: rotary pairs are interleaved (dims 2i and
2i+1 rotate together) where Hugging Face's Phi-3 code rotates halves; the two
differ by a fixed permutation of the query and key columns. The longrope
rescaling of the rotary frequencies is not applied.

`fp8=True` is the control: every matmul operand (weights per output column,
activations per row) is scaled into float8_e4m3fn and back before the
product, the precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0


def _q8(x, axis):
    """Round x to float8_e4m3fn with one scale per slice along `axis`."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, fp8):
    """x (..., k) @ w (k, n) in float32, or through float8 operands."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, rot_dim, theta):
    """Rotate the first `rot_dim` dims of x (B, S, H, D), pairs interleaved."""
    half = rot_dim // 2
    freq = 1.0 / theta ** (np.arange(0, rot_dim, 2, dtype=np.float32) / rot_dim)
    ang = pos.astype(jnp.float32)[:, :, None] * freq          # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xr = x[..., :rot_dim].reshape(x.shape[:-1] + (half, 2))
    x1, x2 = xr[..., 0], xr[..., 1]
    rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([rot.reshape(x.shape[:-1] + (rot_dim,)),
                            x[..., rot_dim:]], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "fp8"))
def _layer(x, p, cfg, fp8):
    """One decoder layer on x (B, S, d) float32, causal over S."""
    cfg = dict(cfg)
    H, G, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    B, S, _ = x.shape
    h = _rms(x, p["ln1"], cfg["eps"])
    q = _mm(h, p["attn"]["wq"], fp8).reshape(B, S, H, hd)
    k = _mm(h, p["attn"]["wk"], fp8).reshape(B, S, G, hd)
    v = _mm(h, p["attn"]["wv"], fp8).reshape(B, S, G, hd)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q = _rope(q, pos, cfg["rot_dim"], cfg["theta"])
    k = _rope(k, pos, cfg["rot_dim"], cfg["theta"])
    # query head h reads key/value head h // (H // G)
    k = jnp.repeat(k, H // G, axis=2)
    v = jnp.repeat(v, H // G, axis=2)
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    if fp8:
        a = _q8(a, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=HI).reshape(B, S, H * hd)
    x = x + _mm(o, p["attn"]["wo"], fp8)
    h = _rms(x, p["ln2"], cfg["eps"])
    f = p["ffn"]
    g = jax.nn.silu(_mm(h, f["w1"], fp8)) * _mm(h, f["w3"], fp8)
    return x + _mm(g, f["w2"], fp8)


@functools.partial(jax.jit, static_argnames=("fp8",))
def _embed(embed, tokens, fp8):
    del fp8
    return embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg", "fp8"))
def _head(x, ln_f, unembed, cfg, fp8):
    cfg = dict(cfg)
    return _mm(_rms(x, ln_f, cfg["eps"]), unembed, fp8)


def shape_of(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's dict."""
    hd = config["hidden_size"] // config["num_attention_heads"]
    rot = int(hd * config.get("partial_rotary_factor", 1.0))
    return dict(heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"], head_dim=hd,
                rot_dim=rot - rot % 2, theta=float(config["rope_theta"]),
                eps=float(config["rms_norm_eps"]))


def logits_at(weights, config: dict, tokens: np.ndarray, first: int,
              fp8: bool = False) -> np.ndarray:
    """Float32 logits (B, S - first, V) at positions first..S-1 of tokens
    (B, S): the prediction of the token at each following position."""
    c = tuple(sorted(shape_of(config).items()))
    x = _embed(weights["embed"], jnp.asarray(tokens), fp8)
    for p in weights["layers"]:
        x = _layer(x, p, c, fp8)
    out = _head(x[:, first:], weights["ln_f"], weights["unembed"], c, fp8)
    return np.asarray(out)
