"""Operations and bytes a step of the dense GQA decoder needs.

Counted from the configuration's shapes and the batch's real context
lengths, whatever implements the step, so that a roofline share or an MFU
reads the same for any program.  What is needed, not what a program
happens to do: a decode tick reads every layer matrix and the output head
once, only the embedding rows it looks up, and the cached keys and values
at the sequences' live lengths; attention costs ``4 * head_dim`` operations
per head, layer and attended key (scores and weighted values).  Norm gains
and the rotary tables are left out as negligible.
"""

from __future__ import annotations

from typing import Tuple

from perfbench.flops import dtype_bytes
from perfbench.models.dense_gqa import dims


def layer_params(m: dict) -> int:
    """Matrix parameters of one layer."""
    qo = 2 * m["d"] * m["h"] * m["dh"]
    kv = 2 * m["d"] * m["kv"] * m["dh"]
    return qo + kv + 3 * m["d"] * m["ff"]


def matrix_params(cfg: dict) -> int:
    """Layer matrices and the output head: what a decode tick reads."""
    m = dims(cfg)
    return m["layers"] * layer_params(m) + m["vocab"] * m["d"]


def kv_bytes_per_token(cfg: dict) -> int:
    m = dims(cfg)
    return 2 * m["layers"] * m["kv"] * m["dh"] * dtype_bytes(cfg)


def attention_flops(cfg: dict, keys: int) -> int:
    """One query position attending over ``keys`` cached positions."""
    m = dims(cfg)
    return 4 * m["layers"] * m["h"] * m["dh"] * keys


def decode_tick(cfg: dict, batch: int, live_keys: int) -> Tuple[int, int]:
    """(operations, bytes) of one tick advancing ``batch`` sequences whose
    context lengths, the new token included, sum to ``live_keys``."""
    m = dims(cfg)
    db = dtype_bytes(cfg)
    flops = 2 * batch * matrix_params(cfg) + attention_flops(cfg, live_keys)
    nbytes = (matrix_params(cfg) * db + batch * m["d"] * db
              + kv_bytes_per_token(cfg) * live_keys)
    return flops, nbytes


def decode_token_flops(cfg: dict, keys: int) -> int:
    """One generated token at context length ``keys``."""
    return 2 * matrix_params(cfg) + attention_flops(cfg, keys)


def prefill_flops(cfg: dict, tokens: int) -> int:
    """A prompt of ``tokens`` under a causal mask, and the one row of
    logits that its first generated token needs."""
    m = dims(cfg)
    causal_keys = tokens * (tokens + 1) // 2
    return (2 * tokens * m["layers"] * layer_params(m)
            + attention_flops(cfg, causal_keys) + 2 * m["vocab"] * m["d"])
