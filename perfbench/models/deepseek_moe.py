"""The plain reference of DeepSeekMoE, and its weights.

The block of DeepSeekMoE-16B (arXiv:2401.06066; the published
``modeling_deepseek.py``), as its configuration states it:

* every layer: ``h = x + Attn(RMSNorm(x))``, ``out = h + FFN(RMSNorm(h))``;
* attention: multi-head, rotary position encoding (rotate-half pairing;
  inverse frequencies, positions and angles in float32 as the published
  code computes them), no biases, scale ``1 / sqrt(head_dim)``;
* the first ``first_k_dense_replace`` layers: a SwiGLU of width
  ``intermediate_size``;
* every later layer: gates ``s = softmax(x W_gate^T)`` over all
  ``router_experts`` experts, the ``num_experts_per_tok`` largest kept and
  not renormalised, ``y = sum_{i in top} s_i E_i(x) + S(x)`` with each
  ``E_i`` a SwiGLU of width ``moe_intermediate_size`` and ``S`` the
  ``n_shared_experts`` shared experts as one SwiGLU of their summed width;
* a final RMSNorm and an untied output head.

A chip that holds a share of the experts (``n_routed_experts`` of them, ids
from ``first_held_expert``) is given the same share here: the router keeps
its width and its top-k, and only the held experts' terms are summed.
Straightforward ``jax.numpy`` in float32 with every contraction at the
highest precision (``jax.default_matmul_precision("highest")``), each held
expert computed for every token and weighted by its gate, or zero where
the token was not routed to it; no cache, batching or kernels, and
nothing imported from the program under test.

Weights are drawn here, on the device, from the run's seed, in the layout
the program takes them in: matrices ``[out, in]``, ``Q_weights_L{i}``
``[H, head_dim, hidden]``, ``Router_L{i}`` ``[router_experts, hidden]``
and the held experts' ``Experts_W{1,3}_L{i}`` ``[held, width, hidden]``
and ``Experts_W2_L{i}`` ``[held, hidden, width]``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.models.dense_gqa import _drawer, _mm, _rmsnorm, _rope, seed_key


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    held = int(cfg["n_routed_experts"])
    fe = int(cfg["moe_intermediate_size"])
    return dict(d=d, h=h, kv=int(cfg.get("num_key_value_heads", h)),
                dh=int(cfg.get("head_dim") or d // h),
                ff=int(cfg["intermediate_size"]), fe=fe,
                fs=int(cfg["n_shared_experts"]) * fe,
                router=int(cfg.get("router_experts", held)), held=held,
                first=int(cfg.get("first_held_expert", 0)),
                k=int(cfg["num_experts_per_tok"]),
                dense=int(cfg["first_k_dense_replace"]),
                vocab=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def weight_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    m = dims(cfg)
    d, dh = m["d"], m["dh"]
    shapes = {"vocabulary": (m["vocab"], d), "Final_Norm": (d,),
              "lm_head": (m["vocab"], d)}
    for i in range(m["layers"]):
        shapes.update({
            f"Q_weights_L{i}": (m["h"], dh, d),
            f"K_weights_L{i}": (m["kv"], dh, d),
            f"V_weights_L{i}": (m["kv"], dh, d),
            f"o_weights_L{i}": (d, m["h"] * dh),
            f"Attention_Norm_L{i}": (d,), f"FFN_Norm_L{i}": (d,)})
        if i < m["dense"]:
            ff = m["ff"]
            shapes.update({f"GLU_W1_L{i}": (ff, d), f"GLU_W2_L{i}": (d, ff),
                           f"GLU_W3_L{i}": (ff, d)})
            continue
        g, fe, fs = m["held"], m["fe"], m["fs"]
        shapes.update({
            f"Router_L{i}": (m["router"], d),
            f"Experts_W1_L{i}": (g, fe, d), f"Experts_W2_L{i}": (g, d, fe),
            f"Experts_W3_L{i}": (g, fe, d),
            f"Shared_W1_L{i}": (fs, d), f"Shared_W2_L{i}": (d, fs),
            f"Shared_W3_L{i}": (fs, d)})
    return shapes


def make_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, f32, on the default device, in one jitted call: norm
    gains near one, the embedding at 0.02, every matrix (the router too)
    at ``1 / sqrt(fan_in)``."""
    shapes = tuple(sorted(weight_shapes(cfg).items()))
    return _drawer(shapes)(seed_key(seed))


# -- forward --------------------------------------------------------------


def _swiglu(x, w1, w3, w2, mode):
    a = jax.nn.silu(_mm("std,fd->stf", x, w1, mode))
    return _mm("stf,jf->stj", a * _mm("std,fd->stf", x, w3, mode), w2, mode)


def _experts(m: dict, mode: str, w, i: int, x):
    """The held experts' part of layer ``i``'s FFN: gates from the whole
    router, each held expert weighted by its gate where it is among the
    token's top-k, else by zero."""
    gates = jax.nn.softmax(_mm("std,ed->ste", x, w[f"Router_L{i}"], mode),
                           axis=-1)
    top, ids = jax.lax.top_k(gates, m["k"])
    held = m["first"] + jnp.arange(m["held"])
    g = jnp.sum(jnp.where(ids[..., None] == held, top[..., None], 0.0),
                axis=-2)                                   # [S, T, held]
    a = jax.nn.silu(_mm("std,efd->stef", x, w[f"Experts_W1_L{i}"], mode))
    a = a * _mm("std,efd->stef", x, w[f"Experts_W3_L{i}"], mode)
    y = _mm("stef,ejf->stej", a, w[f"Experts_W2_L{i}"], mode)
    return jnp.sum(g[..., None] * y, axis=2)


def rotary(m: dict, positions: int) -> Tuple[np.ndarray, np.ndarray]:
    """cos and sin ``[positions, 1, head_dim / 2]`` of the rotary angles,
    computed as the published rotary embedding computes them, in float32
    (inverse frequencies ``theta ** -(i / half)``, positions, their
    products), here on the host."""
    half = m["dh"] // 2
    inv = np.float32(1.0) / (np.float32(m["theta"]) ** (
        np.arange(half, dtype=np.float32) / np.float32(half)))
    ang = np.arange(positions, dtype=np.float32)[:, None] * inv[None, :]
    return np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]


def forward_rows(m: dict, mode: str, w, tokens, rows, cos, sin):
    """Logits at chosen positions: ``tokens`` is ``[S, T]``, ``rows`` is
    ``[S, N]`` positions, ``cos`` and ``sin`` the rotary tables of
    :func:`rotary`; returns ``[S, N, vocab]``.  Causal, so tokens after
    the last chosen position do not matter."""
    S, T = tokens.shape
    g = m["h"] // m["kv"]
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
        if i < m["dense"]:
            x = x + _swiglu(h, w[f"GLU_W1_L{i}"], w[f"GLU_W3_L{i}"],
                            w[f"GLU_W2_L{i}"], mode)
        else:
            x = x + _experts(m, mode, w, i, h) + _swiglu(
                h, w[f"Shared_W1_L{i}"], w[f"Shared_W3_L{i}"],
                w[f"Shared_W2_L{i}"], mode)
    x = _rmsnorm(x, w["Final_Norm"], m["eps"])
    x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return _mm("snd,vd->snv", x, w["lm_head"], mode)


@functools.lru_cache(maxsize=None)
def _forward(dims_key, mode):
    return jax.jit(functools.partial(forward_rows, dict(dims_key), mode))


def logits_at(cfg: dict, weights, tokens: np.ndarray, rows: np.ndarray,
              mode: str = "highest") -> np.ndarray:
    """Host copy of the logits at ``rows`` of each sequence in
    ``tokens``, computed in blocks of one sequence so that the reference
    fits beside its weights."""
    m = dims(cfg)
    fn = _forward(tuple(sorted(m.items())), mode)
    cos, sin = rotary(m, tokens.shape[1])
    with jax.default_matmul_precision("highest"):
        return np.concatenate([
            np.asarray(fn(weights, jnp.asarray(tokens[i:i + 1], jnp.int32),
                          jnp.asarray(rows[i:i + 1], jnp.int32), cos, sin))
            for i in range(tokens.shape[0])])
