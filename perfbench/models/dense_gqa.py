"""The plain reference of the dense GQA decoder, and its weights.

RMSNorm, rotary position encoding (rotate-half pairing), grouped-query
attention (query head ``h`` reads key/value head ``h // (H / H_kv)``),
SwiGLU feed-forward, untied output head, no biases: the block of
Mistral-7B and of DeepSeek-LLM-7B as their configurations state it.
Straightforward ``jax.numpy`` in float32, with no cache, batching or
kernels, and nothing imported from the program under test.

Weights are drawn here, on the device, from the run's seed, in the layout
the program takes them in (``Q_weights_L{i}`` is ``[H, head_dim, hidden]``
and maps ``x`` to ``q[h, r] = sum_d W[h, r, d] x[d]``; matrices are
``[out, in]``).  The program gets a host copy; the reference draws them
again after the program has been freed.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return dict(d=d, h=h, kv=int(cfg["num_key_value_heads"]),
                dh=int(cfg.get("head_dim") or d // h),
                ff=int(cfg["intermediate_size"]),
                vocab=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def weight_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    m = dims(cfg)
    d, dh, ff = m["d"], m["dh"], m["ff"]
    shapes = {"vocabulary": (m["vocab"], d), "Final_Norm": (d,),
              "lm_head": (m["vocab"], d)}
    for i in range(m["layers"]):
        shapes.update({
            f"Q_weights_L{i}": (m["h"], dh, d),
            f"K_weights_L{i}": (m["kv"], dh, d),
            f"V_weights_L{i}": (m["kv"], dh, d),
            f"o_weights_L{i}": (d, m["h"] * dh),
            f"GLU_W1_L{i}": (ff, d), f"GLU_W2_L{i}": (d, ff),
            f"GLU_W3_L{i}": (ff, d),
            f"Attention_Norm_L{i}": (d,), f"FFN_Norm_L{i}": (d,)})
    return shapes


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds may pass 32 bits)."""
    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _draw(shapes, key):
    names = sorted(shapes)
    keys = jax.random.split(key, len(names))
    out = {}
    for name, k in zip(names, keys):
        shape = shapes[name]
        z = jax.random.normal(k, shape, jnp.float32)
        if name.endswith("Norm") or "_Norm_" in name:
            out[name] = 1.0 + 0.1 * z  # norm gains near one, not all one
        elif name == "vocabulary":
            out[name] = 0.02 * z
        else:
            out[name] = z / math.sqrt(shape[-1])
    return out


@functools.lru_cache(maxsize=None)
def _drawer(shapes_key):
    return jax.jit(functools.partial(_draw, dict(shapes_key)))


def make_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, f32, on the default device, in one jitted call."""
    shapes = tuple(sorted(weight_shapes(cfg).items()))
    return _drawer(shapes)(seed_key(seed))


# -- forward --------------------------------------------------------------


def _mm(eq, a, b, mode):
    """One contraction at the precision ``mode`` names: ``highest`` (f32)
    or ``bf16`` (one pass, the control)."""
    if mode == "highest":
        return jnp.einsum(eq, a, b, precision=HI)
    if mode == "bf16":
        return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision mode {mode!r}")


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward_rows(m: dict, mode: str, w, tokens, rows):
    """Logits at chosen positions: ``tokens`` is ``[S, T]``, ``rows`` is
    ``[S, N]`` positions; returns ``[S, N, vocab]``.  Causal, so tokens
    after the last chosen position do not matter."""
    S, T = tokens.shape
    g = m["h"] // m["kv"]
    half = m["dh"] // 2
    inv = 1.0 / (m["theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    x = w["vocabulary"][tokens]
    for i in range(m["layers"]):
        h = _rmsnorm(x, w[f"Attention_Norm_L{i}"], m["eps"])
        q = _rope(_mm("std,hrd->sthr", h, w[f"Q_weights_L{i}"], mode),
                  cos, sin)
        k = _rope(_mm("std,hrd->sthr", h, w[f"K_weights_L{i}"], mode),
                  cos, sin)
        v = _mm("std,hrd->sthr", h, w[f"V_weights_L{i}"], mode)
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        s = _mm("sthr,suhr->shtu", q, k, mode) / math.sqrt(m["dh"])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        o = _mm("shtu,suhr->sthr", p, v, mode).reshape(S, T, -1)
        x = x + _mm("stc,jc->stj", o, w[f"o_weights_L{i}"], mode)
        h = _rmsnorm(x, w[f"FFN_Norm_L{i}"], m["eps"])
        a = jax.nn.silu(_mm("std,fd->stf", h, w[f"GLU_W1_L{i}"], mode))
        b = _mm("std,fd->stf", h, w[f"GLU_W3_L{i}"], mode)
        x = x + _mm("stf,jf->stj", a * b, w[f"GLU_W2_L{i}"], mode)
    x = _rmsnorm(x, w["Final_Norm"], m["eps"])
    x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return _mm("snd,vd->snv", x, w["lm_head"], mode)


@functools.lru_cache(maxsize=None)
def _forward(dims_key, mode):
    return jax.jit(functools.partial(forward_rows, dict(dims_key), mode))


def logits_at(cfg: dict, weights, tokens: np.ndarray, rows: np.ndarray,
              mode: str = "highest") -> np.ndarray:
    """Host copy of the logits at ``rows`` of each sequence in
    ``tokens``, one sequence per call so that the reference fits beside
    its weights."""
    fn = _forward(tuple(sorted(dims(cfg).items())), mode)
    return np.concatenate([
        np.asarray(fn(weights, jnp.asarray(tokens[i:i + 1], jnp.int32),
                      jnp.asarray(rows[i:i + 1], jnp.int32)))
        for i in range(tokens.shape[0])])
