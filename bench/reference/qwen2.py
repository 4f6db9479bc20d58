"""Plain float32 forward pass of a Qwen2 decoder (arXiv:2407.10671).

Per layer: RMS norm, q/k/v projections with bias, rotary position
embedding, causal grouped-query attention, output projection, residual;
RMS norm, SwiGLU MLP, residual.  A final RMS norm and the tied embedding
give the logits.  It runs on the whole sequence at once, one layer after
another inside a ``lax.scan``, with each layer's weights cast to float32
as it is reached, so the float32 copy of the model never exists whole.

One departure from the published model, which random weights cannot see:
the rotation pairs dimensions ``(2i, 2i + 1)`` of each head (the RoFormer
layout); the Hugging Face checkpoint pairs ``i`` with ``i + head_dim / 2``.
The two are the same model up to a fixed permutation of the q and k
columns.

``mode="fp8"`` is the control: every matmul input (weights and
activations) rounded to float8 e4m3 first, the precision below the
configuration's bfloat16.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x (S, H, hd): rotate each pair (2i, 2i+1) by positions * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None, None].astype(jnp.float32) * inv  # (S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


F8_MAX = 448.0  # largest finite float8 e4m3fn


def _quant(x, mode):
    if mode == "fp8":
        return jnp.clip(x, -F8_MAX, F8_MAX).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, w, mode):
    return jnp.matmul(_quant(a, mode), _quant(w.astype(jnp.float32), mode))


@partial(jax.jit, static_argnames=("cfg", "mode"))
def logits(params: Dict[str, Any], tokens: jax.Array, *, cfg, mode: str = "f32") -> jax.Array:
    """tokens (S,) int32 -> logits (S, vocab) float32.  ``cfg`` is a tuple
    of ``(key, value)`` pairs of the configuration's model sizes."""
    c = dict(cfg)
    d, heads, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, eps = d // heads, c["rms_norm_eps"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]  # (query, key)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        f = lambda name: p[name].astype(jnp.float32)  # noqa: E731
        h = _rms(x, f("ln1"), eps)
        q = (_mm(h, p["attn"]["wq"], mode) + p["attn"]["bq"].astype(jnp.float32)).reshape(s, heads, hd)
        k = (_mm(h, p["attn"]["wk"], mode) + p["attn"]["bk"].astype(jnp.float32)).reshape(s, kv, hd)
        v = (_mm(h, p["attn"]["wv"], mode) + p["attn"]["bv"].astype(jnp.float32)).reshape(s, kv, hd)
        q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
        k = jnp.repeat(k, heads // kv, axis=1)  # query head j reads kv head j // group
        v = jnp.repeat(v, heads // kv, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", _quant(q, mode), _quant(k, mode)) / np.sqrt(hd)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        att = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", _quant(att, mode), _quant(v, mode)).reshape(s, heads * hd)
        x = x + _mm(o, p["attn"]["wo"], mode)
        h = _rms(x, f("ln2"), eps)
        g = _mm(h, p["mlp"]["w_gate"], mode)
        u = _mm(h, p["mlp"]["w_up"], mode)
        x = x + _mm(jax.nn.silu(g) * u, p["mlp"]["w_down"], mode)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms(x, params["ln_f"].astype(jnp.float32), eps)
    return _mm(x, params["embed"].T, mode)


def served_gaps(params, prompt, served, cfg, pad_to: int, mode: str = "f32") -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the reference agrees).

    With ``mode="fp8"`` the 'served' tokens are the control's own picks:
    its argmax at each position of the same prompt and tokens.  The
    sequence is padded to ``pad_to`` at the end, which the causal mask
    hides from every real position, so one program serves every length.
    """
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    padded = np.zeros(pad_to, np.int32)
    padded[: len(seq)] = seq
    key = tuple(sorted(cfg.items()))
    with jax.default_matmul_precision("highest"):
        ref = logits(params, jnp.asarray(padded), cfg=key, mode="f32")
        if mode != "f32":
            low = logits(params, jnp.asarray(padded), cfg=key, mode=mode)
    at = np.arange(len(prompt) - 1, len(seq))  # positions that picked a token
    ref = np.asarray(ref[at], np.float64)
    picks = np.asarray(served) if mode == "f32" else np.asarray(jnp.argmax(low[at], axis=-1))
    return ref.max(axis=-1) - ref[np.arange(len(at)), picks]
