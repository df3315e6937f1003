"""Mixture-of-experts FFN with sort-based token dispatch (EP-friendly).

Top-k routing uses a capacity-bounded, sort-based dispatch: (token, k) pairs
are sorted by expert id, scattered into per-expert buffers [E, C, D], run
through batched expert FFNs (``E`` sharded over the model axis = expert
parallelism), and combined back with the router gates.  Gather/scatter carry
no FLOPs, so the compiled cost analysis reflects *active* compute
(top-k × capacity), unlike one-hot dispatch einsums.

Relational reading (DESIGN.md §4): the expert id is one more chunk-table
key; routing = ORDER BY gate DESC LIMIT k per token row; dispatch = the
equi-join of the token table against the expert weight tables.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models.layers import dense_init, mlp_apply


def moe_init(key, cfg: ModelConfig) -> Dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)

    def experts(k, i, o):
        sub = jax.random.split(k, E)
        return jax.vmap(lambda kk: dense_init(kk, i, o, cfg))(sub)

    p = {
        "router": dense_init(ks[0], d, E, cfg, scale=0.02),
        "w1": experts(ks[1], d, f),
        "w3": experts(ks[2], d, f),
        "w2": experts(ks[3], f, d),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        sk = jax.random.split(ks[4], 3)
        p["shared"] = {"w1": dense_init(sk[0], d, fs, cfg),
                       "w3": dense_init(sk[1], d, fs, cfg),
                       "w2": dense_init(sk[2], fs, d, cfg)}
    return p


def capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(n_tokens * cfg.top_k / cfg.n_experts
                      * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly tiling


def moe_apply(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    N = B * T
    xf = x.reshape(N, D)

    logits = (xf @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, K)  # [N, K]
    if cfg.router_normalize:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
    gates = gates.astype(x.dtype)

    # ---- sort-based dispatch ------------------------------------------------
    flat_e = idx.reshape(-1)                         # [N*K] expert ids
    flat_t = jnp.repeat(jnp.arange(N), K)            # [N*K] token ids
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]

    counts = jnp.bincount(flat_e, length=E)          # tokens per expert
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(N * K) - starts[se]             # slot within expert
    C = capacity(N, cfg)
    keep = pos < C
    pos_c = jnp.where(keep, pos, 0)

    buf = jnp.zeros((E, C, D), x.dtype)
    buf = buf.at[se, pos_c].add(
        jnp.where(keep[:, None], xf[st], 0).astype(x.dtype))
    buf = shard(buf, "expert", None, None)

    # ---- batched expert FFN (SwiGLU) ---------------------------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w1"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w3"])
    h = shard(h, "expert", None, None)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w2"])

    # ---- combine -------------------------------------------------------------
    y = jnp.zeros((N, D), x.dtype)
    contrib = out_buf[se, pos_c] * (sg * keep.astype(sg.dtype))[:, None]
    y = y.at[st].add(contrib)

    if "shared" in p:
        y = y + mlp_apply(p["shared"], xf, cfg)
    return y.reshape(B, T, D)


def moe_apply_ep_local(p: Dict, x: jnp.ndarray, cfg: ModelConfig
                       ) -> jnp.ndarray:
    """Expert-parallel MoE with *local* dispatch (§Perf hillclimb B).

    Under TP, activations are replicated across the model axis while the
    expert stack is sharded over it.  The pjit dense formulation then pays
    an all-gather of the whole [E, C, D] expert buffer at combine time
    (SPMD cannot partition a value-gather along the sharded expert dim).
    Here we drop to shard_map: every model shard already *has* all tokens,
    so it simply filters the (token, k) pairs routed to its own experts,
    runs its expert slice, and contributes its partial output to one psum —
    the same wire cost as a TP MLP all-reduce, instead of gathering the
    full expert buffer.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import current_mesh, logical_spec

    mesh = current_mesh()
    if mesh is None or "model" not in mesh.shape or mesh.shape["model"] == 1 \
            or cfg.n_experts % mesh.shape["model"] != 0:
        return moe_apply(p, x, cfg)

    n_shards = mesh.shape["model"]
    E_loc = cfg.n_experts // n_shards
    B, T, D = x.shape
    N = B * T
    K = cfg.top_k
    batch_spec = logical_spec("batch")
    bax = batch_spec[0] if len(batch_spec) else None
    bspec = P(bax, None, None)
    n_b = 1
    for a in ((bax,) if isinstance(bax, str) else (bax or ())):
        n_b *= mesh.shape[a]
    C = capacity(max(1, N // n_b), cfg)  # per-shard token count

    def local_fn(xl, router, w1, w3, w2):
        me = jax.lax.axis_index("model")
        Bl, Tl, _ = xl.shape
        Nl = Bl * Tl
        xf = xl.reshape(Nl, D)
        logits = (xf @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, K)
        if cfg.router_normalize:
            gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
        gates = gates.astype(xl.dtype)

        flat_e = idx.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(Nl), K)
        flat_g = gates.reshape(-1)
        mine = (flat_e // E_loc) == me          # my experts only
        local_e = jnp.where(mine, flat_e % E_loc, E_loc)  # E_loc = drop row
        order = jnp.argsort(local_e)
        se, st, sg = local_e[order], flat_t[order], flat_g[order]
        counts = jnp.bincount(local_e, length=E_loc + 1)
        starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(Nl * K) - starts[jnp.minimum(se, E_loc)]
        keep = (se < E_loc) & (pos < C)
        pos_c = jnp.where(keep, pos, 0)
        se_c = jnp.where(keep, se, 0)

        buf = jnp.zeros((E_loc, C, D), xl.dtype)
        buf = buf.at[se_c, pos_c].add(
            jnp.where(keep[:, None], xf[st], 0).astype(xl.dtype))
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1))
        h = h * jnp.einsum("ecd,edf->ecf", buf, w3)
        out_buf = jnp.einsum("ecf,efd->ecd", h, w2)

        y = jnp.zeros((Nl, D), xl.dtype)
        contrib = out_buf[se_c, pos_c] * (sg * keep.astype(sg.dtype))[:, None]
        y = y.at[st].add(contrib)
        # combine across expert shards: one TP-style all-reduce
        y = jax.lax.psum(y, "model")
        return y.reshape(Bl, Tl, D)

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(bspec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=bspec, check_vma=False)
    y = fn(x, p["router"], p["w1"], p["w3"], p["w2"])
    if "shared" in p:
        y = y + mlp_apply(p["shared"], x.reshape(N, D), cfg).reshape(B, T, D)
    return y


def aux_load_balance_loss(p: Dict, x: jnp.ndarray, cfg: ModelConfig
                          ) -> jnp.ndarray:
    """Switch-style load-balancing auxiliary loss (training only)."""
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    probs = jax.nn.softmax((xf @ p["router"]).astype(jnp.float32), -1)
    _, idx = jax.lax.top_k(probs, cfg.top_k)
    onehot = jax.nn.one_hot(idx[:, 0], cfg.n_experts)
    frac_tokens = onehot.mean(0)
    frac_probs = probs.mean(0)
    return cfg.n_experts * jnp.sum(frac_tokens * frac_probs)
