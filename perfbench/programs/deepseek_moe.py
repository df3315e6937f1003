"""How the program under test builds DeepSeekMoE: its ``LlamaSpec`` with
the sparse-expert fields, read from a configuration's published keys by
the program's own reader (``repro.serving.boot.spec_from_config``).

The reader is imported only when a spec is asked for, in ``run.build``
before a weight is drawn: a program without it, or a ``model_type`` not in
``BUILDS``, fails there and never builds another block.
"""

from __future__ import annotations

BUILDS = ("deepseek",)


def spec_from_config(cfg: dict):
    kind = cfg.get("model_type")
    if kind not in BUILDS:
        raise ValueError(f"model_type {kind!r}: the relational path builds "
                         f"DeepSeekMoE for {', '.join(BUILDS)}")
    from repro.serving.boot import spec_from_config as read
    return read(cfg)
