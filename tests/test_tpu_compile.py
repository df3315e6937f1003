"""Compile the Pallas kernels for a described TPU v5e at Llama-3-8B widths,
and the executor's grouped matmul of routed experts at DeepSeekMoE-16B's.

Nothing runs: the TPU compiler that ships with libtpu lowers each kernel
for a chip that is described, not attached, and the test checks that
Mosaic accepted it (a ``tpu_custom_call`` in the compiled text).  This
catches what interpret mode cannot: tiling that the chip refuses, or more
fast memory than a kernel may use.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and the test
workers all import this file.  Where it cannot be described, the tests
skip from the fixture.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.llama3_8b import CONFIG as LLAMA3_8B
from repro.core.executor import grouped_block, grouped_matmul
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_chunked_matmul_compiles_at_ffn_width(one_chip):
    """One prefill block of 256 tokens against the FFN up-projection."""
    c = LLAMA3_8B
    fn = functools.partial(ops.chunked_matmul, bm=128, bn=128, bk=128)
    text = _compiled_text(fn, ((256, c.d_model), jnp.float32),
                          ((c.d_ff, c.d_model), jnp.float32),
                          sharding=one_chip)
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_head_width(one_chip):
    c = LLAMA3_8B
    shape = ((1, c.n_heads, 2048, c.head_dim), jnp.float32)
    fn = functools.partial(ops.flash_attention, causal=True)
    text = _compiled_text(fn, shape, shape, shape, sharding=one_chip)
    assert "tpu_custom_call" in text


def test_paged_attention_compiles_at_page_geometry(one_chip):
    """A decode batch of 4 over 16-token pages of the 8 KV heads."""
    c = LLAMA3_8B
    B, page, n_pages, max_pages = 4, 16, 256, 32
    pool = ((n_pages, page, c.n_kv, c.head_dim), jnp.float32)
    text = _compiled_text(ops.paged_attention,
                          ((B, c.n_heads, c.head_dim), jnp.float32),
                          pool, pool, ((B, max_pages), jnp.int32),
                          ((B,), jnp.int32), sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [16 * 6, 512 * 6])
def test_grouped_matmul_compiles_at_expert_width(one_chip, rows):
    """The routed pairs of a decode tick of 16 and of a 512-token prefill
    (top-6) against 16 held experts of 2048 -> 1408: a loop over tiles of
    one expert each, never the dense form that multiplies every row by
    every expert, and no copy of an expert's weights."""
    g, n, k = 16, 1408, 2048
    fn = functools.partial(grouped_matmul, block=grouped_block(rows, g))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in (((rows, k), jnp.float32), ((rows,), jnp.int32),
                          ((g, n, k), jnp.float32))]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert " while(" in text
    assert f"f32[{g},{rows},{n}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < n * k * 4


def test_prefill_program_takes_the_weights_as_parameters(one_chip):
    """The tiny spec's cold prefill program for one length bucket compiles
    for a described v5e as ``jit_prefill``, with every weight table an
    argument: it holds no constant as large as the smallest table."""
    import re

    import numpy as np

    from repro.core import llama_graph as lg
    from repro.serving.engine import RelationalEngine
    spec = lg.LlamaSpec(vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv=2,
                        d_ff=64, rope_theta=10000.0)
    eng = RelationalEngine(spec, lg.init_llama_params(spec, seed=0),
                           chunk_size=8, max_len=24)
    bucket = eng._prefill_bucket(5)
    prefill, names = eng._prefill_tick(bucket, eng._prefill_pipe(bucket))

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    weights = jax.tree.map(shape, {n: eng.env_base[n] for n in names})
    inputs = jax.tree.map(shape, {
        "token_ids": lg.token_table(np.zeros(bucket, np.int32)),
        "freq_each_token": lg.rope_freq_table(np.arange(bucket),
                                              spec.head_dim,
                                              spec.rope_theta)})
    text = prefill.lower(weights, inputs, jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=one_chip)).compile().as_text()
    assert "jit_prefill" in text
    smallest = min(x.size for x in jax.tree.leaves(weights))
    constants = [int(np.prod([int(d) for d in dims.split(",") if d]))
                 for dims in re.findall(r"f32\[([0-9,]*)\][^ ]* constant\(",
                                        text)]
    assert max(constants, default=0) < smallest
