"""Build the served relational path from a model name.

The one construction path behind ``examples/serve_http.py`` and
``chip_smoke.py``: a registry name (or ``"tiny"``) and a layer count give a
``LlamaSpec``; a spec and its weights give an ``AsyncLLMServer`` over a
``RelationalEngine``.  Also places JAX's persistent compile cache for both
entry points.
"""

from __future__ import annotations

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


def model_spec(model: str, n_layers: Optional[int] = None) -> LlamaSpec:
    """``"tiny"`` or a registered dense-Llama config, depth cut to
    ``n_layers`` (widths always as registered)."""
    if model == "tiny":
        if n_layers is not None:
            raise ValueError("the tiny model has a fixed depth")
        return TINY
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
