"""Operations and bytes a step needs, and the roofline they give.

The counts of each architecture are in ``counts/<reference>.py``, found by
the configuration's ``reference``, whose docstring says what is counted: a
configuration whose architecture has no counts is an error.  Here are only
what every architecture shares: the bytes of the configuration's
``torch_dtype`` and the roofline.
"""

from __future__ import annotations

from typing import Tuple

from perfbench import spec

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def dtype_bytes(cfg: dict) -> int:
    return DTYPE_BYTES[cfg["torch_dtype"]]


def counts(cfg: dict):
    return spec.architecture("counts", cfg["reference"])


def decode_tick(cfg: dict, batch: int, live_keys: int) -> Tuple[int, int]:
    """(operations, bytes) of one tick advancing ``batch`` sequences whose
    context lengths, the new token included, sum to ``live_keys``."""
    return counts(cfg).decode_tick(cfg, batch, live_keys)


def decode_token_flops(cfg: dict, keys: int) -> int:
    """One generated token at context length ``keys``."""
    return counts(cfg).decode_token_flops(cfg, keys)


def prefill_flops(cfg: dict, tokens: int) -> int:
    """A prompt of ``tokens`` and the one row of logits that its first
    generated token needs."""
    return counts(cfg).prefill_flops(cfg, tokens)


def least_time_s(flops: int, nbytes: int, peak: dict) -> float:
    """The roofline: the larger of compute at the bf16 peak and bytes at
    the HBM bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
