"""End-to-end system tests: the paper's relational engine and the direct
engine produce identical generations from identical weights, across
in-memory and disk+mem (paged) residencies."""

import numpy as np
import pytest

from repro.core.bridge import llama_params_to_tree, spec_to_config
from repro.core.llama_graph import LlamaSpec, init_llama_params
from repro.serving.engine import DirectEngine, RelationalEngine

SPEC = LlamaSpec(vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv=2,
                 d_ff=64, rope_theta=10000.0)


@pytest.fixture(scope="module")
def weights():
    return init_llama_params(SPEC, seed=3)


@pytest.fixture(scope="module")
def direct_tokens(weights):
    cfg = spec_to_config(SPEC)
    eng = DirectEngine(cfg, llama_params_to_tree(weights, SPEC),
                       residency="in_memory", max_len=32)
    res = eng.generate([5, 9, 2, 7], max_new_tokens=6)
    return res.tokens


def test_relational_inmemory_matches_direct(weights, direct_tokens):
    """The compiled SQL-equivalent pipeline is the same model."""
    eng = RelationalEngine(SPEC, weights, chunk_size=8,
                           residency="in_memory", max_len=32)
    res = eng.generate([5, 9, 2, 7], max_new_tokens=6)
    assert res.tokens == direct_tokens
    assert res.ttft_s > 0 and res.tpot_s > 0


def test_relational_paged_matches_direct(weights, direct_tokens, tmp_path):
    """Disk+mem mode (memmap cold store + bounded working set) is
    semantics-preserving (§4.3)."""
    eng = RelationalEngine(SPEC, weights, chunk_size=8, residency="paged",
                           budget_bytes=64 * 1024,
                           disk_dir=str(tmp_path / "db"), max_len=32)
    res = eng.generate([5, 9, 2, 7], max_new_tokens=6)
    assert res.tokens == direct_tokens
    assert res.pager_stats["misses"] > 0          # it really paged
    assert res.pager_stats["evictions"] > 0       # budget enforced


def test_direct_paged_matches(weights, direct_tokens, tmp_path):
    cfg = spec_to_config(SPEC)
    eng = DirectEngine(cfg, llama_params_to_tree(weights, SPEC),
                       residency="paged", budget_bytes=48 * 1024,
                       disk_dir=str(tmp_path / "db2"), max_len=32)
    res = eng.generate([5, 9, 2, 7], max_new_tokens=6)
    assert res.tokens == direct_tokens


@pytest.mark.parametrize("name,n_layers", [("llama3-8b", 4),
                                           ("llama3-8b", None),
                                           ("llama3-tiny", None)])
def test_config_to_spec_round_trips(name, n_layers):
    """A registered config builds the relational spec at its published
    widths, depth cut only, and maps back through spec_to_config."""
    from repro.configs import get_config
    from repro.core.bridge import config_to_spec
    cfg = get_config(name)
    spec = config_to_spec(cfg, n_layers)
    assert spec.n_layers == (n_layers or cfg.n_layers)
    back = spec_to_config(spec)
    for f in ("d_model", "n_heads", "n_kv", "head_dim", "d_ff", "vocab",
              "rope_theta", "eps"):
        assert getattr(back, f) == getattr(cfg, f), f
    assert back.n_layers == spec.n_layers
    if name == "llama3-8b":
        assert (spec.d_model, spec.n_heads, spec.n_kv, spec.head_dim,
                spec.d_ff, spec.vocab, spec.rope_theta) == \
            (4096, 32, 8, 128, 14336, 128256, 5e5)


def test_config_to_spec_rejects_other_families():
    from repro.configs import get_config
    from repro.core.bridge import config_to_spec
    with pytest.raises(ValueError, match="qk_norm"):
        config_to_spec(get_config("qwen3-14b"))
    with pytest.raises(ValueError, match="n_layers"):
        config_to_spec(get_config("llama3-8b"), 33)


def test_chunk_size_only_affects_speed(weights, direct_tokens):
    """Tab. 1's knob: every chunk size yields identical generations."""
    for cs in (4, 8, 16):
        eng = RelationalEngine(SPEC, weights, chunk_size=cs,
                               residency="in_memory", max_len=32)
        assert eng.generate([5, 9, 2, 7], 6).tokens == direct_tokens
