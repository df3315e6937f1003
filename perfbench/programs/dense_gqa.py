"""How the program under test builds the dense GQA decoder: its
``LlamaSpec``, read from a configuration's published keys.

The program's relational graph builds this block for the configurations
whose ``model_type`` is in ``BUILDS``.  Any other ``model_type`` is refused
here, before a weight is drawn, and never built as this block.
"""

from __future__ import annotations

BUILDS = ("llama", "mistral")


def spec_from_config(cfg: dict):
    from repro.core.llama_graph import LlamaSpec
    kind = cfg.get("model_type")
    if kind not in BUILDS:
        raise ValueError(f"model_type {kind!r}: the relational path builds "
                         f"the dense GQA decoder for {', '.join(BUILDS)}")
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    head_dim = cfg.get("head_dim")
    if d % h or (head_dim is not None and int(head_dim) != d // h):
        raise ValueError(f"head_dim {head_dim}: the relational path derives "
                         f"it as hidden_size / num_attention_heads "
                         f"({d} / {h})")
    return LlamaSpec(vocab=int(cfg["vocab_size"]), d_model=d,
                     n_layers=int(cfg["num_hidden_layers"]), n_heads=h,
                     n_kv=int(cfg["num_key_value_heads"]),
                     d_ff=int(cfg["intermediate_size"]),
                     rope_theta=float(cfg["rope_theta"]),
                     eps=float(cfg["rms_norm_eps"]))
