"""Whether what the timed path served is right.

Once the window has closed and the program is freed, a sample of the
requests the server finished, drawn from the seed and holding the
longest, is run through the plain reference (``models/<reference>.py``),
once over each prompt with the tokens that were served for it.  Three
numbers are compared, each with its limit from the configuration file:

``logit_gap``
    the widest gap by which a served token's reference logit lies below
    the reference's best at that position.  Zero where every served token
    is the reference's greedy choice; a near tie that rounding flipped
    reads a little above zero.
``logit_err``
    the largest ``|program - reference|`` over the logits the timed path
    produced for those tokens (prefill final position, then each batched
    decode tick's row), as the wrapped callbacks caught them.
``stream_faults``
    finished or failed streams of the whole run whose ``token_index`` is
    not 0, 1, 2, ... or that ended with an error event (limit 0).

The control, the reference computed at the next precision down from the
configuration's float32, one bf16 pass (``calibrate.py``), reads the same
two logit numbers from its own logits at the same positions.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional, Tuple

import numpy as np

from perfbench import client
from perfbench.serve import prompt_key


def draw_sample(requests: List[dict], seed: int, n: int) -> List[dict]:
    """``n`` finished requests: the longest, and others drawn from
    ``seed`` (in the order the requests were recorded)."""
    done = [r for r in requests if client.complete(r)]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = random.Random(seed)
    return [longest] + rng.sample(rest, min(n - 1, len(rest)))


def stream_faults(requests: List[dict]) -> int:
    bad = 0
    for r in requests:
        if r["status"] != 200:
            continue  # refused at admission: counted as failed, not wrong
        if r["error"] is not None or r["index"] != list(range(len(r["index"]))):
            bad += 1
        elif r["done"] and len(r["tokens"]) != r["max_tokens"]:
            bad += 1
    return bad


def layout(sample: List[dict], max_len: int) -> Tuple[np.ndarray, np.ndarray]:
    """Reference inputs: each prompt with its served tokens (the last
    needs no input), padded to ``max_len``; and the positions whose
    logits chose each served token, padded by repeating the last."""
    width = max(len(r["tokens"]) for r in sample)
    tokens = np.zeros((len(sample), max_len), np.int32)
    rows = np.zeros((len(sample), width), np.int32)
    for i, r in enumerate(sample):
        seq = list(r["prompt"]) + list(r["tokens"][:-1])
        tokens[i, :len(seq)] = seq
        p, n = len(r["prompt"]), len(r["tokens"])
        rows[i] = np.minimum(np.arange(width), n - 1) + p - 1
    return tokens, rows


def reference(cfg: dict, model, weights, sample: List[dict], max_len: int,
              mode: str = "highest") -> List[np.ndarray]:
    """Reference logits ``[n_tokens, vocab]`` for each sampled request."""
    tokens, rows = layout(sample, max_len)
    out = model.logits_at(cfg, weights, tokens, rows, mode)
    return [out[i, :len(r["tokens"])] for i, r in enumerate(sample)]


def program_rows(recorder, sample: List[dict]) -> List[Optional[np.ndarray]]:
    out = []
    for r in sample:
        rows = recorder.rows.get(prompt_key(r["prompt"]))
        out.append(np.stack(rows) if rows and len(rows) == len(r["tokens"])
                   else None)
    return out


def logit_numbers(sample: List[dict], ref: List[np.ndarray],
                  chosen: List[Optional[np.ndarray]],
                  logits: List[Optional[np.ndarray]]) -> Dict[str, float]:
    """``logit_gap`` of the ``chosen`` tokens and ``logit_err`` of the
    ``logits``, both against ``ref``; a missing row reads infinite."""
    gap, err = 0.0, 0.0
    for r, ref_i, tok_i, lg_i in zip(sample, ref, chosen, logits):
        if tok_i is None or lg_i is None or lg_i.shape != ref_i.shape:
            return {"logit_gap": float("inf"), "logit_err": float("inf")}
        best = ref_i.max(axis=1)
        picked = ref_i[np.arange(len(tok_i)), tok_i]
        gap = max(gap, float(np.max(best - picked)))
        err = max(err, float(np.max(np.abs(lg_i - ref_i))))
    return {"logit_gap": gap, "logit_err": err}


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """Every number at or under its limit; a number that is missing or
    not finite fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok = ok and bool(good)
        checks[name] = {"value": (float(v) if v is not None
                                  and np.isfinite(v) else None),
                        "limit": limit}
    return ok, checks
