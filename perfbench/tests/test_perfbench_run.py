"""CPU tests of whole runs of the benchmark: the refusal to run without a
TPU, the rehearsal on the tiny configuration, the control (the reference
at lower precision in the program's place) and faults planted in the
timed path, each of which has to come out as not correct."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import correct, flops, serve, spec  # noqa: E402

RUN = ROOT / "perfbench" / "run.py"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def cpu_env():
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def last_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_refuses_to_run_without_a_tpu():
    p = subprocess.run([sys.executable, str(RUN), "--workload",
                        "mistral-7b.batch", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=cpu_env(),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_refuses_to_run_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = cpu_env()
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        "mistral-7b.batch", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


def test_every_cell_finds_its_files():
    bench = spec.load_json(ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        moved = {m["name"]: m["moves"] for m in bench["per_layer"]}
        reported = {m.name for m in cell.end_to_end}
        for m in cell.per_layer:
            assert moved[m.name] in reported
        assert spec.generator(cell.traffic["kind"]).run
        # the architecture's parts, each found by the configuration's
        # reference: the program's spec, the counts, the reference, the
        # rehearsal's tiny configuration
        ref = cell.config["reference"]
        assert serve.program_spec(cell.config).n_layers == \
            cell.config["num_hidden_layers"]
        assert flops.counts(cell.config).decode_tick
        assert spec.architecture("models", ref).make_weights
        tiny = spec.rehearsal_config(ref)
        assert tiny["reference"] == ref
        assert spec.load_cell(w["name"], rehearse=True).config == tiny


def check_line(line, cell, trace):
    assert KEYS <= set(line) and line["rehearsal"] is True
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    names = {m.name for m in (cell.per_layer if trace else cell.end_to_end)}
    assert set(line["metrics"]) == names or trace
    assert set(line["metrics"]) <= names


def test_rehearsal_of_a_traced_closed_loop_run():
    p = subprocess.run([sys.executable, str(RUN), "--workload",
                        "mistral-7b.batch", "--seed", str(2 ** 33 + 17),
                        "--seconds", "3", "--trace", "1", "--rehearse"],
                       cwd=ROOT, env=cpu_env(), capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = last_line(p.stdout)
    check_line(line, spec.load_cell("mistral-7b.batch"), True)
    assert "decode_batch_mean.batch" in line["metrics"]
    assert "prefill_share.batch" in line["metrics"]
    assert "breakdown" in line
    assert line["device"]["window_s"] > 0
    assert "check logit_err" in p.stderr.splitlines()[-2]


# -- the control -------------------------------------------------------------


def served_sample(cfg, seed, n=4):
    """Prompts of the tiny mix's lengths, each with tokens as if served."""
    rng = np.random.default_rng(seed)
    vocab = int(cfg["vocab_size"])
    return [{"prompt": rng.integers(0, vocab, int(p)).tolist(),
             "tokens": rng.integers(0, vocab, int(o)).tolist()}
            for p, o in zip(rng.choice([8, 16], n), rng.integers(2, 9, n))]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_at_lower_precision_is_not_correct(seed):
    cfg = spec.load_json(ROOT / "perfbench" / "configs" / "tiny.json")
    model = spec.load_module(ROOT / "perfbench" / "models" / "dense_gqa.py",
                             "perfbench_model_dense_gqa")
    w = model.make_weights(cfg, seed)
    sample = served_sample(cfg, seed)
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


# -- faults planted in the timed path ---------------------------------------


def alter_decode(dec_cls, monkeypatch):
    orig = dec_cls.decode

    def decode(self, seq_ids, last_tokens):
        toks = orig(self, seq_ids, last_tokens)
        return [(toks[0] + 1) % self.engine.spec.vocab] + list(toks[1:])
    monkeypatch.setattr(dec_cls, "decode", decode)


def alter_prefill(dec_cls, monkeypatch):
    orig = dec_cls.prefill_ex

    def prefill_ex(self, prompt, seq_id):
        tok, cached = orig(self, prompt, seq_id)
        return (tok + 1) % self.engine.spec.vocab, cached
    monkeypatch.setattr(dec_cls, "prefill_ex", prefill_ex)


def half_batch(dec_cls, monkeypatch):
    """Decode only the first half of the batch; the rest keep their last
    token and borrow the first row's logits."""
    orig = dec_cls.decode

    def decode(self, seq_ids, last_tokens):
        k = max(1, len(seq_ids) // 2)
        toks = list(orig(self, seq_ids[:k], last_tokens[:k]))
        rows = self.last_logits
        self.last_logits = np.concatenate(
            [rows, np.repeat(rows[:1], len(seq_ids) - k, axis=0)])
        return toks + list(last_tokens[k:])
    monkeypatch.setattr(dec_cls, "decode", decode)


@pytest.mark.parametrize("fault", [alter_decode, alter_prefill, half_batch])
def test_fault_in_the_timed_path_is_not_correct(fault, monkeypatch, capsys):
    from perfbench import run as bench
    from repro.serving.engine import BatchedDecoder
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    fault(BatchedDecoder, monkeypatch)
    rc = bench.main(["--workload", "mistral-7b.batch", "--seed", "23",
                     "--seconds", "3", "--trace", "0", "--rehearse"])
    assert rc == 0
    line = last_line(capsys.readouterr().out)
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["logit_gap"]["value"] is None or \
        line["checks"]["logit_gap"]["value"] > \
        line["checks"]["logit_gap"]["limit"]
