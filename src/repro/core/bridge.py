"""Bridge between the relational path's Appendix-A weight layout and the
production model stack's parameter tree (dense Llama family only).

Used by the equivalence tests and the quickstart example to prove the two
execution paths (relational pipelines vs direct JAX) implement the same
model, weight-for-weight, and by the serving entry points to build the
relational path from a registered dense config.  Sparse-expert models
(DeepSeekMoE) are read from their published ``config.json`` by
``repro.serving.boot.spec_from_config``, not from the registry.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.llama_graph import LlamaSpec


def config_to_spec(cfg: ModelConfig, n_layers: Optional[int] = None
                   ) -> LlamaSpec:
    """Registered ``ModelConfig`` → the relational path's ``LlamaSpec``
    (the inverse of :func:`spec_to_config`).  ``n_layers`` cuts the depth;
    every width stays as registered.  Raises ``ValueError`` for a config
    outside the one graph family the relational compiler builds."""
    plain = ModelConfig(name=cfg.name, family="dense", n_layers=cfg.n_layers,
                        d_model=cfg.d_model, n_heads=cfg.n_heads,
                        n_kv=cfg.n_kv, d_ff=cfg.d_ff, vocab=cfg.vocab,
                        rope_theta=cfg.rope_theta, eps=cfg.eps)
    # every architecture field must be the plain dense GQA decoder's;
    # numerics/execution fields (dtype, remat, ...) do not shape the graph
    arch = [f.name for f in dataclasses.fields(ModelConfig)
            if f.name not in ("name", "scan_unroll", "dtype", "param_dtype",
                              "remat", "ce_impl", "moe_impl", "max_positions",
                              "capacity_factor")]
    differ = [f for f in arch if getattr(cfg, f) != getattr(plain, f)]
    if differ:
        raise ValueError(f"{cfg.name}: a registered config builds only the "
                         f"dense GQA Llama family; {differ} differ from it "
                         f"(sparse-expert models are read from their "
                         f"published config.json by "
                         f"repro.serving.boot.spec_from_config)")
    n = cfg.n_layers if n_layers is None else n_layers
    if not 1 <= n <= cfg.n_layers:
        raise ValueError(f"{cfg.name}: n_layers {n} outside [1, "
                         f"{cfg.n_layers}]")
    return LlamaSpec(vocab=cfg.vocab, d_model=cfg.d_model, n_layers=n,
                     n_heads=cfg.n_heads, n_kv=cfg.n_kv, d_ff=cfg.d_ff,
                     rope_theta=cfg.rope_theta, eps=cfg.eps)


def spec_to_config(spec: LlamaSpec, dtype: str = "float32") -> ModelConfig:
    return ModelConfig(
        name="llama-bridge", family="dense", n_layers=spec.n_layers,
        d_model=spec.d_model, n_heads=spec.n_heads, n_kv=spec.n_kv,
        d_ff=spec.d_ff, vocab=spec.vocab, head_dim=spec.head_dim,
        rope_theta=spec.rope_theta, eps=spec.eps, dtype=dtype,
        param_dtype=dtype, remat="none",
    )


def llama_params_to_tree(params: Dict[str, np.ndarray], spec: LlamaSpec
                         ) -> Dict:
    """Appendix-A tables → models/transformer parameter tree (stacked)."""
    L = spec.n_layers

    def stack(fn):
        return jnp.stack([jnp.asarray(fn(i)) for i in range(L)])

    d, dh = spec.d_model, spec.head_dim
    g0 = {
        "ln1": {"scale": stack(lambda i: params[f"Attention_Norm_L{i}"])},
        "ln2": {"scale": stack(lambda i: params[f"FFN_Norm_L{i}"])},
        "attn": {
            # [H, dh, D] → [D, H, dh]
            "wq": stack(lambda i: params[f"Q_weights_L{i}"].transpose(2, 0, 1)),
            "wk": stack(lambda i: params[f"K_weights_L{i}"].transpose(2, 0, 1)),
            "wv": stack(lambda i: params[f"V_weights_L{i}"].transpose(2, 0, 1)),
            # [Dout, Din] → [H, dh, Dout]
            "wo": stack(lambda i: params[f"o_weights_L{i}"].T.reshape(
                spec.n_heads, dh, d)),
        },
        "mlp": {
            "w1": stack(lambda i: params[f"GLU_W1_L{i}"].T),
            "w3": stack(lambda i: params[f"GLU_W3_L{i}"].T),
            "w2": stack(lambda i: params[f"GLU_W2_L{i}"].T),
        },
    }
    return {
        "embed": {"embedding": jnp.asarray(params["vocabulary"])},
        "g0": g0,
        "final_norm": {"scale": jnp.asarray(params["Final_Norm"])},
        "lm_head": jnp.asarray(params["lm_head"].T),
    }
