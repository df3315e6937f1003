"""Operations and bytes a step of DeepSeekMoE needs, on a chip that holds
a share of the routed experts.

Counted from the configuration's shapes and the batch's real context
lengths, whatever implements the step.  The routed experts are counted as
expectations under uniform routing, which the random weights of the
benchmark give: a token's top-k lands on each of the router's experts with
probability ``k / router_experts``, so

* a token's routed work here is its expected held experts,
  ``k * held / router_experts`` SwiGLUs (6 * 16 / 64 = 1.5);
* a decode tick of ``batch`` tokens reads each held expert that any of its
  tokens is routed to, ``held * (1 - (1 - k / router_experts) ** batch)``
  experts a layer in expectation.

Everything else is counted as the dense decoder's counts are: a tick reads
every other layer matrix (attention, the leading dense SwiGLU, the router,
the shared experts) and the output head once, only the embedding rows it
looks up, and the cached keys and values at the sequences' live lengths;
attention costs ``4 * head_dim`` operations per head, layer and attended
key.  Norm gains and the rotary tables are left out as negligible.
"""

from __future__ import annotations

from typing import Tuple

from perfbench.flops import dtype_bytes
from perfbench.models.deepseek_moe import dims


def attention_params(m: dict) -> int:
    return 2 * m["d"] * m["h"] * m["dh"] + 2 * m["d"] * m["kv"] * m["dh"]


def expert_params(m: dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * m["d"] * m["fe"]


def moe_fixed_params(m: dict) -> int:
    """What every token of an expert layer reads besides its routed
    experts: the router and the shared experts."""
    return m["router"] * m["d"] + 3 * m["d"] * m["fs"]


def routed_per_token(m: dict) -> float:
    """Expected held experts a token is routed to."""
    return m["k"] * m["held"] / m["router"]


def held_touched(m: dict, batch: int) -> float:
    """Expected distinct held experts that ``batch`` tokens are routed to,
    in one expert layer."""
    return m["held"] * (1.0 - (1.0 - m["k"] / m["router"]) ** batch)


def moe_layers(m: dict) -> int:
    return m["layers"] - m["dense"]


def token_params(m: dict) -> float:
    """Matrix parameters one token multiplies through its layers, the
    output head left out."""
    return (m["layers"] * attention_params(m) + m["dense"] * 3 * m["d"]
            * m["ff"] + moe_layers(m) * (moe_fixed_params(m)
                                         + routed_per_token(m)
                                         * expert_params(m)))


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
    head = m["vocab"] * m["d"]
    flops = (2 * batch * (token_params(m) + head)
             + attention_flops(cfg, live_keys))
    read = (m["layers"] * attention_params(m) + m["dense"] * 3 * m["d"]
            * m["ff"] + moe_layers(m) * (moe_fixed_params(m)
                                         + held_touched(m, batch)
                                         * expert_params(m)) + head)
    nbytes = (read * db + batch * m["d"] * db
              + kv_bytes_per_token(cfg) * live_keys)
    return round(flops), round(nbytes)


def decode_token_flops(cfg: dict, keys: int) -> int:
    """One generated token at context length ``keys``."""
    m = dims(cfg)
    return round(2 * (token_params(m) + m["vocab"] * m["d"])
                 + attention_flops(cfg, keys))


def prefill_flops(cfg: dict, tokens: int) -> int:
    """A prompt of ``tokens`` under a causal mask, and the one row of
    logits that its first generated token needs."""
    m = dims(cfg)
    causal_keys = tokens * (tokens + 1) // 2
    return round(2 * tokens * token_params(m)
                 + attention_flops(cfg, causal_keys)
                 + 2 * m["vocab"] * m["d"])
