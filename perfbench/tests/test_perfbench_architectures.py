"""CPU tests of how the harness finds an architecture's parts by the
configuration's ``reference``: the program's spec (``programs/``), read
by ``model_type``; the counts (``counts/``, in
``test_perfbench_metrics.py``); and the rehearsal's tiny configuration.
A part that is missing, or a ``model_type`` the program does not build,
is an error before any weight is drawn."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import serve, spec  # noqa: E402
from repro.core.llama_graph import LlamaSpec  # noqa: E402


def config(name, **changes):
    return {**spec.load_json(ROOT / "perfbench" / "configs" / f"{name}.json"),
            **changes}


@pytest.mark.parametrize("name,want", [
    ("mistral-7b", LlamaSpec(vocab=32768, d_model=4096, n_layers=6,
                             n_heads=32, n_kv=8, d_ff=14336,
                             rope_theta=1e6, eps=1e-5)),
    ("deepseek-7b", LlamaSpec(vocab=102400, d_model=4096, n_layers=2,
                              n_heads=32, n_kv=32, d_ff=11008,
                              rope_theta=1e4, eps=1e-6)),
    ("tiny", LlamaSpec(vocab=512, d_model=128, n_layers=2, n_heads=4,
                       n_kv=2, d_ff=256, rope_theta=1e4, eps=1e-5)),
])
def test_spec_from_config_by_hand(name, want):
    assert serve.program_spec(config(name)) == want


def test_a_model_type_the_program_does_not_build_is_refused():
    with pytest.raises(ValueError, match="'deepseek_v2'.*llama, mistral"):
        serve.program_spec(config("deepseek-7b", model_type="deepseek_v2"))
    # no default: a configuration must state its model_type
    cfg = config("tiny")
    del cfg["model_type"]
    with pytest.raises(ValueError, match="model_type None"):
        serve.program_spec(cfg)


@pytest.mark.parametrize("head_dim", [64, 96])
def test_a_head_dim_the_program_would_not_derive_is_refused(head_dim):
    # the program derives 128 = 4096 / 32; a head_dim of 128 stays accepted
    assert serve.program_spec(config("mistral-7b", head_dim=128)).head_dim \
        == 128
    with pytest.raises(ValueError, match=f"head_dim {head_dim}"):
        serve.program_spec(config("mistral-7b", head_dim=head_dim))


def test_an_architecture_without_a_program_module_is_an_error():
    with pytest.raises(FileNotFoundError, match="programs/no_such_arch"):
        serve.program_spec(config("tiny", reference="no_such_arch"))


class StandInReference:
    """A reference module that records whether weights were drawn."""

    def __init__(self):
        self.calls = []

    def make_weights(self, cfg, seed):
        self.calls.append(("make_weights", seed))
        raise AssertionError("weights drawn for a configuration the "
                             "program does not build")


@pytest.mark.parametrize("changes,error", [
    ({"model_type": "deepseek_v2"}, ValueError),
    ({"reference": "no_such_arch"}, FileNotFoundError),
])
def test_build_refuses_before_drawing_weights(changes, error, monkeypatch):
    from perfbench import run as bench
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cell = spec.load_cell("mistral-7b.batch", rehearse=True)
    cell.config = {**cell.config, **changes}
    model = StandInReference()
    with pytest.raises(error):
        bench.build(cell, model, 5, compiles=None)
    assert model.calls == []


def write(where, name, reference):
    (where / name).write_text(json.dumps(
        {"name": name[:-5], "reference": reference}))


def test_the_rehearsal_takes_the_tiny_configuration_of_its_architecture(
        tmp_path):
    write(tmp_path, "tiny.json", "dense_gqa")
    write(tmp_path, "tiny-x.json", "x")
    write(tmp_path, "mistral-7b.json", "x")  # not a tiny configuration
    assert spec.rehearsal_config("x", tmp_path)["name"] == "tiny-x"
    assert spec.rehearsal_config("dense_gqa", tmp_path)["name"] == "tiny"
    with pytest.raises(LookupError, match="'y'"):
        spec.rehearsal_config("y", tmp_path)
    write(tmp_path, "tiny-x2.json", "x")
    with pytest.raises(LookupError, match="2 tiny configurations.*'x'"):
        spec.rehearsal_config("x", tmp_path)
