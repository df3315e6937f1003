"""CPU tests of the DeepSeekMoE architecture's parts in the harness: the
program's spec read from the configuration, the refusal of a
``model_type`` the program does not build, the counts against numbers
worked by hand, a rehearsal of ``deepseek-moe-16b.batch`` that comes out
correct, and the bf16 control that does not."""

import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import correct, flops, serve, spec  # noqa: E402
from repro.core.llama_graph import LlamaSpec  # noqa: E402

CELL = "deepseek-moe-16b.batch"


def config(name, **changes):
    return {**spec.load_json(ROOT / "perfbench" / "configs" / f"{name}.json"),
            **changes}


@pytest.mark.parametrize("name,want", [
    ("deepseek-moe-16b", LlamaSpec(
        vocab=102400, d_model=2048, n_layers=5, n_heads=16, n_kv=16,
        d_ff=10944, rope_theta=1e4, eps=1e-6, n_experts=16, n_router=64,
        first_expert=0, top_k=6, d_expert=1408, d_shared=2816,
        n_dense_layers=1)),
    ("tiny-deepseek_moe", LlamaSpec(
        vocab=512, d_model=128, n_layers=3, n_heads=4, n_kv=4, d_ff=256,
        rope_theta=1e4, eps=1e-6, n_experts=8, n_router=16, first_expert=4,
        top_k=4, d_expert=64, d_shared=64, n_dense_layers=1)),
])
def test_spec_from_config_by_hand(name, want):
    got = serve.program_spec(config(name))
    assert got == want
    assert got.moe_layers == tuple(range(1, want.n_layers))


@pytest.mark.parametrize("changes,match", [
    ({"model_type": "deepseek_v2"}, "'deepseek_v2'"),
    ({"model_type": "llama"}, "'llama'.*DeepSeekMoE"),
    ({"norm_topk_prob": True}, "norm_topk_prob"),
    ({"scoring_func": "sigmoid"}, "scoring_func"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
])
def test_what_the_program_does_not_build_is_refused(changes, match):
    with pytest.raises(ValueError, match=match):
        serve.program_spec(config("deepseek-moe-16b", **changes))


class StandInReference:
    def __init__(self):
        self.calls = []

    def make_weights(self, cfg, seed):
        self.calls.append(seed)
        raise AssertionError("weights drawn for a configuration the "
                             "program does not build")


def test_build_refuses_an_unknown_model_type_before_drawing_weights(
        monkeypatch):
    from perfbench import run as bench
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cell = spec.load_cell(CELL, rehearse=True)
    cell.config = {**cell.config, "model_type": "deepseek_v2"}
    model = StandInReference()
    with pytest.raises(ValueError, match="deepseek_v2"):
        bench.build(cell, model, 5, compiles=None)
    assert model.calls == []


# -- the counts, worked by hand -------------------------------------------
#
# Per layer: attention 4 * 2048^2 = 16,777,216; the dense SwiGLU
# 3 * 2048 * 10944 = 67,239,936; one expert 3 * 2048 * 1408 = 8,650,752;
# router and shared experts 64 * 2048 + 3 * 2048 * 2816 = 17,432,576.  A
# token: 5 attention + 1 dense + 4 * (17,432,576 + 1.5 experts)
# = 272,760,832 parameters, and the head 102400 * 2048 = 209,715,200.
# Attention: 4 * 5 * 16 * 128 = 40,960 operations per attended key.  KV:
# 2 * 5 * 16 * 128 * 4 = 81,920 bytes per cached token.

TOKEN, HEAD, ATTN, KV = 272_760_832, 209_715_200, 40_960, 81_920
FIXED = 5 * 16_777_216 + 67_239_936 + 4 * 17_432_576 + HEAD


def test_decode_tick_counts_by_hand():
    cfg = config("deepseek-moe-16b")
    touched16 = 16 * (1 - (58 / 64) ** 16)   # 12.688 held experts a layer
    assert flops.decode_tick(cfg, 16, 16 * 544) == (
        2 * 16 * (TOKEN + HEAD) + ATTN * 16 * 544,
        round(4 * (FIXED + 4 * touched16 * 8_650_752) + 16 * 2048 * 4
              + KV * 16 * 544))
    # one token reads its 1.5 expected held experts
    assert flops.decode_tick(cfg, 1, 513) == (
        2 * (TOKEN + HEAD) + ATTN * 513,
        4 * (FIXED + 4 * 1.5 * 8_650_752) + 2048 * 4 + KV * 513)


def test_token_and_prefill_counts_by_hand():
    cfg = config("deepseek-moe-16b")
    assert flops.decode_token_flops(cfg, 600) == \
        2 * (TOKEN + HEAD) + ATTN * 600
    # 545.5 MFLOP a prompt token, 44.6% of it in the expert layers
    assert flops.prefill_flops(cfg, 512) == \
        2 * 512 * TOKEN + ATTN * 512 * 513 // 2 + 2 * HEAD


# -- whole runs ------------------------------------------------------------


def test_rehearsal_of_the_cell_is_correct():
    env = {k: v for k, v in __import__("os").environ.items()
           if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        CELL, "--seed", str(2 ** 33 + 41), "--seconds", "3",
                        "--trace", "0", "--rehearse"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    import json
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"output_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bf16_control_is_not_correct(seed):
    import numpy as np
    cfg = config("tiny-deepseek_moe")
    model = spec.architecture("models", "deepseek_moe")
    w = model.make_weights(cfg, seed)
    rng = np.random.default_rng(seed)
    sample = [{"prompt": rng.integers(0, 512, int(p)).tolist(),
               "tokens": rng.integers(0, 512, int(o)).tolist()}
              for p, o in zip(rng.choice([8, 16], 4), rng.integers(2, 9, 4))]
    max_len = int(cfg["serve"]["max_len"])
    ref = correct.reference(cfg, model, w, sample, max_len)
    same = correct.logit_numbers(sample, ref, [x.argmax(1) for x in ref], ref)
    assert correct.judge({**same, "stream_faults": 0}, cfg["correct"])[0]
    low = correct.reference(cfg, model, w, sample, max_len, "bf16")
    numbers = correct.logit_numbers(sample, ref, [x.argmax(1) for x in low],
                                    low)
    ok, checks = correct.judge({**numbers, "stream_faults": 0},
                               cfg["correct"])
    assert not ok, checks
