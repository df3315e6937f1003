"""Build the served relational path from a model name or configuration.

The one construction path behind ``examples/serve_http.py`` and
``chip_smoke.py``: a registry name (or ``"tiny"``), or a model's published
``config.json`` read by its ``model_type`` (:func:`spec_from_config`), and
a layer count give a ``LlamaSpec``; a spec and its weights give an
``AsyncLLMServer`` over a ``RelationalEngine``.  Also places JAX's
persistent compile cache for both entry points.
"""

from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path
from typing import Dict, Optional

import jax
import numpy as np

from repro.configs import get_config
from repro.core.bridge import config_to_spec
from repro.core.llama_graph import LlamaSpec
from repro.serving.engine import RelationalEngine
from repro.serving.kvcache import PagedKVCache, PagedKVConfig
from repro.serving.server import AsyncLLMServer, ServerConfig

# the toy model the CI serving job and the HTTP tests boot by default
TINY = LlamaSpec(vocab=512, d_model=128, n_layers=3, n_heads=4, n_kv=2,
                 d_ff=256, rope_theta=10000.0)

PAGE_SIZE = 8  # tokens per KV page of the admission page table

# <checkout>/.jax_cache: a fixed path, since the path is part of the key
# under which JAX finds a cached executable again
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` where it is set, else the one fixed
    directory inside the checkout.  Thresholds stay JAX's defaults."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# model_type of a published config.json -> the block built for it
DENSE_TYPES = ("llama", "mistral")
MOE_TYPES = ("deepseek",)


def spec_from_config(cfg: dict) -> LlamaSpec:
    """The relational path's spec of a model from the keys of its published
    ``config.json``, read by ``model_type``:

    * ``llama``, ``mistral``: the dense GQA decoder;
    * ``deepseek`` (DeepSeekMoE): multi-head attention, then
      ``first_k_dense_replace`` layers with the dense SwiGLU of width
      ``intermediate_size`` and after them expert layers: softmax gates
      over the router's experts, the ``num_experts_per_tok`` best kept and
      not renormalised, experts of width ``moe_intermediate_size``, and
      ``n_shared_experts`` shared experts merged into one SwiGLU.
      ``n_routed_experts`` counts the experts held here; a chip that holds
      a share of them gives the router's width as ``router_experts`` and
      its first held id as ``first_held_expert`` (by default all are held).

    Any other ``model_type``, or a key that asks for a block other than
    these, raises ``ValueError`` before a weight is drawn."""
    kind = cfg.get("model_type")
    if kind not in DENSE_TYPES + MOE_TYPES:
        raise ValueError(f"model_type {kind!r}: the relational path builds "
                         f"{', '.join(DENSE_TYPES + MOE_TYPES)}")
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    head_dim = cfg.get("head_dim")
    if d % h or (head_dim is not None and int(head_dim) != d // h):
        raise ValueError(f"head_dim {head_dim}: the relational path derives "
                         f"it as hidden_size / num_attention_heads "
                         f"({d} / {h})")
    unbuilt = {k: cfg[k] for k, ok in (
        ("rope_scaling", None), ("tie_word_embeddings", False),
        ("hidden_act", "silu"), ("scoring_func", "softmax"),
        ("norm_topk_prob", False), ("topk_method", "greedy"),
        ("moe_layer_freq", 1)) if cfg.get(k, ok) != ok}
    if unbuilt:
        raise ValueError(f"{kind}: the relational path does not build "
                         f"{unbuilt}")
    spec = LlamaSpec(vocab=int(cfg["vocab_size"]), d_model=d,
                     n_layers=int(cfg["num_hidden_layers"]), n_heads=h,
                     n_kv=int(cfg.get("num_key_value_heads", h)),
                     d_ff=int(cfg["intermediate_size"]),
                     rope_theta=float(cfg["rope_theta"]),
                     eps=float(cfg["rms_norm_eps"]))
    if kind in DENSE_TYPES:
        return spec
    held, width = int(cfg["n_routed_experts"]), int(
        cfg["moe_intermediate_size"])
    return dataclasses.replace(
        spec, n_experts=held, n_router=int(cfg.get("router_experts", held)),
        first_expert=int(cfg.get("first_held_expert", 0)),
        top_k=int(cfg["num_experts_per_tok"]), d_expert=width,
        d_shared=int(cfg["n_shared_experts"]) * width,
        n_dense_layers=int(cfg["first_k_dense_replace"]))


def model_spec(model: str, n_layers: Optional[int] = None) -> LlamaSpec:
    """``"tiny"``, a registered dense-Llama config, or the path of a
    published ``config.json`` (:func:`spec_from_config`), depth cut to
    ``n_layers`` (widths always as given)."""
    if model == "tiny":
        if n_layers is not None:
            raise ValueError("the tiny model has a fixed depth")
        return TINY
    if model.endswith(".json"):
        with open(model) as f:
            spec = spec_from_config(json.load(f))
        if n_layers is None:
            return spec
        if not 1 <= n_layers <= spec.n_layers:
            raise ValueError(f"{model}: n_layers {n_layers} outside [1, "
                             f"{spec.n_layers}]")
        return dataclasses.replace(spec, n_layers=n_layers)
    return config_to_spec(get_config(model), n_layers)


def build_server(spec: LlamaSpec, params: Dict[str, np.ndarray],
                 cfg: ServerConfig, *, max_len: int = 96, n_pages: int = 48,
                 paged: bool = False, disk_dir: Optional[str] = None,
                 metrics=None, tracer=None) -> AsyncLLMServer:
    """An ``AsyncLLMServer`` over a relational engine for ``spec``.

    ``max_len`` is the context cap (prompt + generated tokens) and the KV
    cache tables' depth; ``n_pages`` sizes the admission page pool (a pool
    below ``max_batch``'s worst case keeps the preemption path honest
    under load).  ``paged`` streams weights through a pager at a quarter
    of the model's bytes, with its cold store under ``disk_dir``."""
    if paged:
        model_bytes = sum(a.size * a.dtype.itemsize for a in params.values())
        eng = RelationalEngine(spec, params, residency="paged",
                               budget_bytes=model_bytes // 4,
                               disk_dir=disk_dir, max_len=max_len,
                               metrics=metrics, tracer=tracer)
    else:
        eng = RelationalEngine(spec, params, max_len=max_len,
                               metrics=metrics, tracer=tracer)
    kvcfg = PagedKVConfig(n_layers=spec.n_layers, n_kv=spec.n_kv,
                          head_dim=spec.head_dim, page_size=PAGE_SIZE,
                          n_pages=n_pages,
                          max_pages_per_seq=-(-max_len // PAGE_SIZE))
    kv = PagedKVCache(kvcfg, max_seqs=max(8, cfg.max_batch))
    return AsyncLLMServer(eng, kv, cfg, metrics=metrics, tracer=tracer)
