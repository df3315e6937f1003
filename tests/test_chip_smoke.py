"""``chip_smoke.py`` off the chip: without a TPU it refuses and prints no
result; ``--rehearse`` drives the whole served path (HTTP server, streaming
client, logits against the f32 reference) on the tiny registered Llama and
never reports ok.  Also where the entry points put JAX's compile cache."""

import importlib.util
import json
import pathlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.serving import boot

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def restore_compile_cache():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_refuses_without_tpu(smoke, capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_rehearsal_serves_and_matches_reference(smoke, capsys, tmp_path,
                                                monkeypatch,
                                                restore_compile_cache):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main(["--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[0].startswith("REHEARSAL")
    assert f"compile cache: {tmp_path}" in lines
    assert any(line.startswith("served 4 concurrent streams")
               for line in lines)
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["rehearsal"] is True


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_compile_cache):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = boot.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
