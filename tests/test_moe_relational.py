"""The sparse-expert layer (DeepSeekMoE) on the relational path, held to
the plain f32 reference (``perfbench/models/deepseek_moe.py``) at a tiny
size on seeded random weights.

Tolerances: the program and the reference compute the same f32 function
in a different order of summation (chunked joins against whole-row
einsums), which moves a logit by a few 1e-6 at these widths; 5e-5 leaves
room for that and for the length of the sequences.  A token sent through
an expert it was not routed to, a routed pair dropped, or one gate lost
moves the logits by 1e-2 or more here.
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.models import deepseek_moe as reference  # noqa: E402
from repro.core import llama_graph as lg  # noqa: E402
from repro.core.executor import (  # noqa: E402
    DenseTable, execute, grouped_block, grouped_matmul)
from repro.core.relational import RelSchema, Scan, TopK, SCALAR  # noqa: E402
from repro.obs.metrics import MetricsRegistry  # noqa: E402
from repro.serving.boot import spec_from_config  # noqa: E402
from repro.serving.engine import RelationalEngine  # noqa: E402

TOL = 5e-5
TINY = json.loads((ROOT / "perfbench" / "configs" /
                   "tiny-deepseek_moe.json").read_text())


def config(**changes):
    """A smaller toy than the rehearsal's, for speed: 3 layers (1 dense),
    a router 16 wide, 8 experts held from id 4, top-4, 1 shared."""
    return {**TINY, "hidden_size": 32, "intermediate_size": 64,
            "moe_intermediate_size": 16, "vocab_size": 64, **changes}


def weights(cfg, seed):
    return {k: np.asarray(v)
            for k, v in reference.make_weights(cfg, seed).items()}


def ref_logits(cfg, params, seq):
    """The reference's logits at every position of ``seq``."""
    toks = np.asarray([seq], np.int32)
    rows = np.arange(len(seq), dtype=np.int32)[None]
    return reference.logits_at(cfg, params, toks, rows)[0]


def engine(cfg, params, **kw):
    return RelationalEngine(spec_from_config(cfg), params, chunk_size=8,
                            max_len=32, **kw)


def eager(monkeypatch):
    """Dispatch the decode tick and the cold prefill op by op."""
    monkeypatch.setattr(RelationalEngine, "runs_compiled",
                        property(lambda self: False))


# -- the program against the reference ------------------------------------

PROMPTS = [[3, 17, 42, 5, 9], [1, 2, 3], [9, 8, 7, 6, 5, 4, 3], [11, 12]]


@pytest.mark.parametrize("path,row2col", [("compiled", "auto"),
                                          ("eager", "auto"),
                                          ("compiled", "col"),
                                          ("compiled", "off")])
def test_prefill_then_batched_decode_match_the_reference(path, row2col,
                                                         monkeypatch):
    if path == "eager":
        eager(monkeypatch)
    cfg = config()
    params = weights(cfg, 11)
    eng = engine(cfg, params, row2col=row2col)
    assert eng.decode_path == path
    dec = eng.batched_decoder(4, prefix_block=0)
    seqs, err = [], 0.0
    for i, p in enumerate(PROMPTS):
        tok = dec.prefill(p, i)
        logits = eng.start_session(p)["logits"]
        err = max(err, np.abs(logits - ref_logits(cfg, params, p)[-1]).max())
        seqs.append(list(p) + [tok])
    # batch membership changes between ticks (bucket 4, then 2 padded)
    for ids in ([0, 1, 2, 3], [0, 2, 3], [1, 3], [0, 1, 2, 3]):
        toks = dec.decode(ids, [seqs[i][-1] for i in ids])
        for row, i in enumerate(ids):
            want = ref_logits(cfg, params, seqs[i])[-1]
            err = max(err, np.abs(dec.last_logits[row] - want).max())
            assert toks[row] == int(np.argmax(dec.last_logits[row]))
            seqs[i].append(toks[row])
    assert err < TOL


def test_the_shares_add_up_to_the_uncut_layer(monkeypatch):
    """One expert layer cut four ways (16 experts, 4 held by each share):
    the routed parts of the four shares, with the shared expert counted
    once, give what the uncut reference gives for the whole layer.  The
    layer's parts are read from the session of an eager prefill, which
    keeps every table of its plan (a compiled one returns the caches)."""
    eager(monkeypatch)
    uncut = config(num_hidden_layers=1, first_k_dense_replace=0,
                   n_routed_experts=16, first_held_expert=0)
    params = weights(uncut, 5)
    prompt = [5, 9, 2, 7, 33, 61]

    def layer(cfg, p):
        env = engine(cfg, p).start_session(prompt)["env"]
        part = {n: np.asarray(env[n].cols["v"]).reshape(len(prompt), -1)
                for n in ("x_attn_res_L0", "moe_combine_L0",
                          "moe_shared_L0")}
        return part

    whole = layer(uncut, params)
    routed = np.zeros_like(whole["moe_combine_L0"])
    for s in range(4):
        share = config(num_hidden_layers=1, first_k_dense_replace=0,
                       n_routed_experts=4, first_held_expert=4 * s)
        p = dict(params)
        for w in ("Experts_W1_L0", "Experts_W2_L0", "Experts_W3_L0"):
            p[w] = params[w][4 * s:4 * s + 4]
        got = layer(share, p)
        np.testing.assert_array_equal(got["x_attn_res_L0"],
                                      whole["x_attn_res_L0"])
        np.testing.assert_allclose(got["moe_shared_L0"],
                                   whole["moe_shared_L0"], atol=1e-6)
        routed += got["moe_combine_L0"]

    # the uncut reference's FFN over the same input
    m = reference.dims(uncut)
    x = jnp.asarray(whole["x_attn_res_L0"])[None]
    h = reference._rmsnorm(x, params["FFN_Norm_L0"], m["eps"])
    with jax.default_matmul_precision("highest"):
        want_routed = reference._experts(m, "highest", params, 0, h)[0]
        want_shared = reference._swiglu(
            h, params["Shared_W1_L0"], params["Shared_W3_L0"],
            params["Shared_W2_L0"], "highest")[0]
    assert np.abs(want_routed).max() > 1e-2  # the experts do contribute
    np.testing.assert_allclose(routed, want_routed, atol=1e-5)
    np.testing.assert_allclose(routed + whole["moe_shared_L0"],
                               want_routed + want_shared, atol=1e-5)


def zero_router(params):
    """Every gate equal: each token's top-k are the k lowest ids."""
    return {k: (np.zeros_like(v) if k.startswith("Router_L") else v)
            for k, v in params.items()}


@pytest.mark.parametrize("k", [1, 3])
def test_no_token_is_dropped_when_all_route_to_the_same_experts(k):
    """Every token routed to the same held experts (experts 0..k-1, all of
    them held): no capacity limit drops one, even where one expert's rows
    span several tiles of the grouped matmul."""
    cfg = config(first_held_expert=0, num_experts_per_tok=k)
    params = zero_router(weights(cfg, 3))
    prompt = list(np.random.default_rng(0).integers(0, 64, 20))
    eng = engine(cfg, params, metrics=MetricsRegistry())
    assert grouped_block(len(prompt) * k, 8) < len(prompt)
    got = eng.start_session(prompt)["logits"]
    want = ref_logits(cfg, params, prompt)[-1]
    assert np.abs(got - want).max() < TOL
    load = eng.metrics.histogram("moe_expert_load_max", phase="prefill")
    assert load.count == 2 and load.sum == 2 * len(prompt)


def series(eng):
    """The prefill's routing series: pairs, then (count, sum) of the
    touched and load histograms."""
    m = eng.metrics
    return (m.counter("moe_routed_pairs_total", phase="prefill").value,
            *((h.count, h.sum) for h in (
                m.histogram("moe_experts_touched", phase="prefill"),
                m.histogram("moe_expert_load_max", phase="prefill"))))


@pytest.mark.parametrize("T", [5, 16])
def test_compiled_prefill_matches_eager_and_the_reference(T, monkeypatch):
    """The cold prefill compiled for its bucket (5 tokens pad to 8, 16
    fill 16) gives the eager prefill's logits, both within ``TOL`` of the
    reference, and its routing series count what the eager prefill of
    the prompt alone counts."""
    cfg = config()
    params = weights(cfg, 13)
    prompt = [int(t) for t in np.random.default_rng(T).integers(0, 64, T)]
    compiled = engine(cfg, params, metrics=MetricsRegistry())
    got = compiled.start_session(prompt)["logits"]
    assert set(compiled._prefill_ticks) == {(-(-T // 8) * 8, 1)}
    eager(monkeypatch)
    slow = engine(cfg, params, metrics=MetricsRegistry())
    want = slow.start_session(prompt)["logits"]
    assert not slow._prefill_ticks
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert np.abs(got - ref_logits(cfg, params, prompt)[-1]).max() < TOL
    assert series(compiled) == series(slow)
    assert series(compiled)[0] > 0


def test_the_series_count_the_real_rows_of_a_padded_bucket():
    """Every token on experts 0 and 1 (top-2 of an all-equal router): a
    5-token prompt in a bucket of 8 routes 2 pairs a real token in each
    of the two expert layers, and loads each expert with the 5 real
    tokens, not the 8 rows the program computed."""
    cfg = config(first_held_expert=0, num_experts_per_tok=2)
    eng = engine(cfg, zero_router(weights(cfg, 4)),
                 metrics=MetricsRegistry())
    eng.start_session([3, 17, 42, 5, 9])
    assert set(eng._prefill_ticks) == {(8, 1)}
    assert eng.metrics.counter("engine_prefill_padded_tokens_total").value \
        == 3
    assert series(eng) == (2 * 2 * 5, (2, 2 * 2), (2, 2 * 5))


# -- the relations ---------------------------------------------------------


@pytest.mark.parametrize("k", [1, 4, 6])
def test_the_topk_relation_is_lax_top_k(k):
    gates = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(k),
                                             (5, 3, 16)), axis=-1)
    scan = Scan("g", RelSchema(keys=(("t", 5), ("b", 3), ("e", 16)),
                               cols=(("p", SCALAR),)))
    node = TopK(input=scan, order_key="e", order_col="p", k=k)
    out = execute(node, {"g": DenseTable(keys=scan.table_schema.keys,
                                         cols={"p": gates},
                                         col_types={"p": SCALAR})})
    top, ids = jax.lax.top_k(gates, k)
    assert out.keys == (("t", 5), ("b", 3), ("k", k))
    np.testing.assert_array_equal(out.cols["e"], ids)
    np.testing.assert_array_equal(out.cols["s"], top)


def test_grouped_matmul_computes_each_row_against_its_group_only():
    rng = np.random.default_rng(1)
    m, k, g, n = 40, 12, 5, 7
    rows = rng.standard_normal((m, k)).astype(np.float32)
    w = rng.standard_normal((g, n, k)).astype(np.float32)
    # skewed: most rows on group 3, some on no group (-1, g), group 1 empty
    groups = rng.choice([0, 2, 3, 3, 3, 3, 4, -1, g], m).astype(np.int32)
    for block in (8, 16, 64):
        out = np.asarray(grouped_matmul(jnp.asarray(rows),
                                        jnp.asarray(groups),
                                        jnp.asarray(w), block))
        for i in range(m):
            want = (w[groups[i]] @ rows[i] if 0 <= groups[i] < g
                    else np.zeros(n, np.float32))
            np.testing.assert_allclose(out[i], want, rtol=1e-5, atol=1e-5)


def test_the_expert_layer_has_its_named_steps_and_sql():
    from repro.core.opmap import op_map
    from repro.core.sqlgen import generate_sql
    spec = spec_from_config(config())
    pipe = op_map(lg.build_decode_graph(spec, cache_len=16, batch=2),
                  chunk_size=8)
    names = {s.name for s in pipe.steps}
    for L in (1, 2):
        for part in ("router", "gates", "topk", "held", "expert_w1",
                     "expert_w3", "expert_w2", "combine", "shared"):
            assert f"moe_{part}_L{L}" in names
    assert "GLU_W1_L0" in pipe.weight_schemas
    assert "GLU_W1_L1" not in pipe.weight_schemas
    assert pipe.outputs == ["logits", "moe_held_L1", "moe_held_L2"]
    sql = generate_sql(pipe)
    assert "ROW_NUMBER() OVER (PARTITION BY seq ORDER BY" in sql
    assert "R.e = L.e" in sql


def test_the_compiled_tick_names_the_expert_steps():
    cfg = config()
    eng = engine(cfg, weights(cfg, 2))
    dec = eng.batched_decoder(2, prefix_block=0)
    toks = [dec.prefill(p, i) for i, p in enumerate(PROMPTS[:2])]
    dec.decode([0, 1], toks)
    tick, names = eng._decode_tick(2, eng._batched_decode_pipe(2))
    views = dec.pool.gather_views([0, 1])
    inputs = {"token_ids": lg.token_table(np.asarray(toks, np.int32),
                                          key="seq"),
              "freq_each_token": lg.rope_freq_table(
                  dec.pool.positions[:2], eng.spec.head_dim,
                  eng.spec.rope_theta, key="seq")}
    text = tick.lower({n: eng.env_base[n] for n in names}, views,
                      dec.pool.tables, inputs, np.asarray([0, 1], np.int32),
                      dec.pool.positions[:2]).as_text(debug_info=True)
    for part in ("router", "topk", "held", "expert_w1", "expert_w2",
                 "combine", "shared"):
        assert f"moe_{part}_L1" in text


# -- the routing series ----------------------------------------------------


def test_the_series_count_a_hand_worked_batch():
    cfg = config()
    eng = engine(cfg, weights(cfg, 2), metrics=MetricsRegistry())
    # two expert layers, three tokens of which the last is padding
    routes = [np.array([[0, -1, 2], [2, -1, -1], [0, 1, -1]], np.float32),
              np.array([[-1, -1, -1], [5, 6, 7], [5, 5, 5]], np.float32)]
    eng.observe_routes(routes, "decode", rows=2)
    m = eng.metrics
    assert m.counter("moe_routed_pairs_total", phase="decode").value == 6
    touched = m.histogram("moe_experts_touched", phase="decode")
    load = m.histogram("moe_expert_load_max", phase="decode")
    assert (touched.count, touched.sum) == (2, 2 + 3)
    assert (load.count, load.sum) == (2, 2 + 1)


def test_the_series_count_what_the_served_path_routes(monkeypatch):
    """All tokens on experts 0 and 1 (top-2 of an all-equal router):
    each expert layer routes 2 pairs a real token, touches 2 experts and
    loads each with every token; the logits and the routes come to the
    host in one transfer a plan."""
    cfg = config(first_held_expert=0, num_experts_per_tok=2)
    eng = engine(cfg, zero_router(weights(cfg, 4)),
                 metrics=MetricsRegistry())
    dec = eng.batched_decoder(4, prefix_block=0)
    toks = [dec.prefill(p, i) for i, p in enumerate(PROMPTS[:3])]
    m = eng.metrics
    prefill_tokens = sum(len(p) for p in PROMPTS[:3])
    assert m.counter("moe_routed_pairs_total", phase="prefill").value == \
        2 * 2 * prefill_tokens
    gets = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: gets.append(1) or real(x))
    dec.decode([0, 1, 2], toks)  # bucket 4: one padded row
    assert len(gets) == 1
    assert m.counter("moe_routed_pairs_total", phase="decode").value == \
        2 * 2 * 3
    touched = m.histogram("moe_experts_touched", phase="decode")
    load = m.histogram("moe_expert_load_max", phase="decode")
    assert (touched.count, touched.sum) == (2, 4)
    assert (load.count, load.sum) == (2, 6)
