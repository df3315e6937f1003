"""Unit tests for the relational core: chunked tables, operator mapping,
executor semantics, optimisation passes, SQL generation."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.chunked import ChunkedTensor, rechunk
from repro.core import relational as ra
from repro.core.executor import DenseTable, execute, table_from_chunked
from repro.core.graph import Graph, infer_shapes
from repro.core.opmap import op_map
from repro.core.passes import (constant_fold, dead_code_elim,
                               eliminate_shape_ops, fuse_projections)
from repro.core.relational import (
    Collect, Filter, GroupAgg, Join, Project, Scan, Unnest, add, call, col,
    const, div, floordiv, key, mod, mul, resolve, sub, SCALAR, VEC,
)
from repro.core.sqlgen import SQLGenerator, generate_sql


def _table(name, arr, cs=8):
    return table_from_chunked(ChunkedTensor.from_dense(name, arr,
                                                       chunk_size=cs))


class TestChunked:
    def test_roundtrip(self):
        x = np.random.default_rng(0).standard_normal((5, 20)).astype(np.float32)
        ct = ChunkedTensor.from_dense("t", x, chunk_size=8)
        assert ct.data.shape == (5, 3, 8)  # padded to 3 chunks
        np.testing.assert_array_equal(np.asarray(ct.to_dense()), x)

    def test_rechunk(self):
        x = np.arange(48, dtype=np.float32).reshape(3, 16)
        ct = ChunkedTensor.from_dense("t", x, chunk_size=8)
        ct2 = rechunk(ct, 4)
        assert ct2.data.shape == (3, 4, 4)
        np.testing.assert_array_equal(np.asarray(ct2.to_dense()), x)

    def test_ddl_and_insert(self):
        x = np.ones((2, 4), np.float32)
        ct = ChunkedTensor.from_dense("w", x, chunk_size=4)
        ddl = ct.schema.ddl()
        assert "CREATE TABLE w" in ddl and "FLOAT[4]" in ddl
        ins = ct.insert_sql()
        assert ins.count("INSERT INTO w") == 2

    def test_table_rows_match_paper_format(self):
        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        ct = ChunkedTensor.from_dense("w", x, chunk_size=2)
        rows = ct.as_table_rows()
        # rows are (i, c, w_i^{(c)})
        i, c, vec = rows[0]
        assert (i, c) == (0, 0)
        np.testing.assert_array_equal(vec, [0.0, 1.0])


class TestExecutor:
    def test_matmul_join_groupagg(self):
        rng = np.random.default_rng(1)
        x = rng.standard_normal((6, 16)).astype(np.float32)
        w = rng.standard_normal((10, 16)).astype(np.float32)
        xt, wt = _table("x", x), _table("w", w)
        plan = GroupAgg(
            input=Join(left=Scan("x", xt.schema()), right=Scan("w", wt.schema()),
                       on=[("chunk_id", key("chunk_id"))]),
            group_keys=["row_id", "row_id_r"],
            aggs=[("s", "SUM", call("dot", col("chunk"), col("chunk_r")))])
        # rename right row key to avoid collision
        wt2 = DenseTable(keys=(("row_id_r", 10), ("chunk_id", 2)),
                         cols=wt.cols, col_types=wt.col_types)
        plan = GroupAgg(
            input=Join(left=Scan("x", xt.schema()),
                       right=Scan("w", wt2.schema()),
                       on=[("chunk_id", key("chunk_id"))]),
            group_keys=["row_id", "row_id_r"],
            aggs=[("s", "SUM", call("dot", col("chunk"), col("chunk_r")))])
        out = execute(plan, {"x": xt, "w": wt2})
        np.testing.assert_allclose(np.asarray(out.cols["s"]), x @ w.T,
                                   rtol=1e-5, atol=1e-5)

    def test_gqa_style_join_expr(self):
        """Join with right key = left_key // g (paper Tab. 2 GQA join)."""
        q = np.random.default_rng(2).standard_normal((4, 8)).astype(np.float32)
        kv = np.random.default_rng(3).standard_normal((2, 8)).astype(np.float32)
        qt = DenseTable(keys=(("h", 4),), cols={"q": jnp.asarray(q)},
                        col_types={"q": VEC(8)})
        kt = DenseTable(keys=(("hk", 2),), cols={"k": jnp.asarray(kv)},
                        col_types={"k": VEC(8)})
        plan = GroupAgg(
            input=Join(left=Scan("q", qt.schema()), right=Scan("k", kt.schema()),
                       on=[("hk", floordiv(key("h"), const(2)))]),
            group_keys=["h"],
            aggs=[("s", "SUM", call("dot", col("q"), col("k")))])
        out = execute(plan, {"q": qt, "k": kt})
        want = np.array([q[h] @ kv[h // 2] for h in range(4)])
        np.testing.assert_allclose(np.asarray(out.cols["s"]), want, rtol=1e-5)

    def test_filter_masks_with_identity(self):
        t = DenseTable(keys=(("t", 3), ("tp", 3)),
                       cols={"s": jnp.ones((3, 3))},
                       col_types={"s": SCALAR})
        plan = Filter(input=Scan("t", t.schema()),
                      predicate=("<=", key("tp"), key("t")),
                      masked_value=0.0)
        out = execute(plan, {"t": t})
        np.testing.assert_array_equal(np.asarray(out.cols["s"]),
                                      np.tril(np.ones((3, 3))))

    def test_unnest_collect_roundtrip(self):
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        t = DenseTable(keys=(("r", 3),), cols={"v": jnp.asarray(x)},
                       col_types={"v": VEC(4)})
        u = Unnest(input=Scan("t", t.schema()), vec_col="v")
        c = Collect(input=u, fold_key="e", scalar_col="x", vec_col="v")
        out = execute(c, {"t": t})
        np.testing.assert_array_equal(np.asarray(out.cols["v"]), x)

    def test_project_key_split_merge(self):
        x = np.arange(24, dtype=np.float32)
        t = DenseTable(keys=(("i", 24),), cols={"v": jnp.asarray(x)},
                       col_types={"v": SCALAR})
        split = Project(input=Scan("t", t.schema()),
                        keys=[("a", 4, floordiv(key("i"), const(6))),
                              ("b", 6, mod(key("i"), const(6)))],
                        exprs=[("v", None, col("v"))])
        out = execute(split, {"t": t})
        np.testing.assert_array_equal(np.asarray(out.cols["v"]),
                                      x.reshape(4, 6))
        merge = Project(input=split,
                        keys=[("i", 24, add(mul(key("a"), const(6)),
                                            key("b")))],
                        exprs=[("v", None, col("v"))])
        out2 = execute(merge, {"t": t})
        np.testing.assert_array_equal(np.asarray(out2.cols["v"]), x)

    def test_value_join_embedding(self):
        ids = DenseTable(keys=(("t", 3),),
                         cols={"s": jnp.asarray([2, 0, 1])},
                         col_types={"s": SCALAR})
        vocab = _table("vocab", np.eye(3, 8, dtype=np.float32), cs=8)
        plan = Project(
            input=Join(left=Scan("ids", ids.schema()),
                       right=Scan("vocab", vocab.schema()),
                       on=[("row_id", col("s"))]),
            keys=None, exprs=[("v", None, col("chunk"))])
        out = execute(plan, {"ids": ids, "vocab": vocab})
        arr = np.asarray(out.cols["v"])[:, 0, :]
        np.testing.assert_array_equal(arr, np.eye(3, 8)[[2, 0, 1]])


    def test_scan_matches_stored_columns_by_name(self):
        """A table whose columns are stored in another order than its
        schema's (as a dict flattened in sorted-key order under ``jit``
        rebuilds them) is read by name; only a real rename pairs stored
        columns with the schema's by position."""
        fr = jnp.asarray([[1.0, 2.0]])
        fi = jnp.asarray([[3.0, 4.0]])
        schema = DenseTable(keys=(("t", 1),), cols={"fr": fr, "fi": fi},
                            col_types={"fr": VEC(2), "fi": VEC(2)}).schema()
        stored = DenseTable(keys=(("t", 1),), cols={"fi": fi, "fr": fr},
                            col_types={"fi": VEC(2), "fr": VEC(2)})
        out = execute(Scan("freq", schema), {"freq": stored})
        assert tuple(out.cols) == ("fr", "fi")
        np.testing.assert_array_equal(np.asarray(out.cols["fr"]), fr)
        np.testing.assert_array_equal(np.asarray(out.cols["fi"]), fi)
        renamed = DenseTable(keys=(("u", 1),), cols={"a": fr, "b": fi},
                             col_types={"a": VEC(2), "b": SCALAR})
        out = execute(Scan("freq", schema), {"freq": renamed})
        assert out.key_names == ("t",)
        np.testing.assert_array_equal(np.asarray(out.cols["fr"]), fr)
        np.testing.assert_array_equal(np.asarray(out.cols["fi"]), fi)
        assert out.col_types == {"fr": VEC(2), "fi": SCALAR}

    def test_dense_table_keeps_column_order_through_jit(self):
        """A DenseTable is a pytree: through ``jax.jit`` it keeps its keys,
        its column order and its column types."""
        import jax
        t = DenseTable(keys=(("t", 2),),
                       cols={"fr": jnp.ones((2, 3)), "fi": jnp.zeros((2, 3))},
                       col_types={"fr": VEC(3), "fi": VEC(3)})
        out = jax.jit(lambda x: x)(t)
        assert isinstance(out, DenseTable)
        assert out.keys == t.keys and tuple(out.cols) == ("fr", "fi")
        assert out.col_types == t.col_types
        np.testing.assert_array_equal(np.asarray(out.cols["fr"]), 1.0)
        np.testing.assert_array_equal(np.asarray(out.cols["fi"]), 0.0)
        doubled = jax.jit(lambda x: jax.tree_util.tree_map(
            lambda a: a * 2 + 1, x))(t)
        assert tuple(doubled.cols) == ("fr", "fi")
        np.testing.assert_array_equal(np.asarray(doubled.cols["fi"]), 1.0)


class TestPasses:
    def _proj_chain(self):
        t = DenseTable(keys=(("i", 4),), cols={"v": jnp.arange(4.0)},
                       col_types={"v": SCALAR})
        inner = Project(input=Scan("t", t.schema()), keys=None,
                        exprs=[("a", None, mul(col("v"), const(2.0)))])
        outer = Project(input=inner, keys=None,
                        exprs=[("b", None, add(col("a"), const(1.0)))])
        return t, outer

    def test_fuse_projections(self):
        t, outer = self._proj_chain()
        fused = fuse_projections(outer)
        assert isinstance(fused.input, Scan)  # π∘π collapsed
        out = execute(fused, {"t": t})
        np.testing.assert_array_equal(np.asarray(out.cols["b"]),
                                      np.arange(4.0) * 2 + 1)

    def test_constant_fold_and_dce(self):
        g = Graph(name="g")
        g.constants["two"] = 2.0
        g.constants["three"] = 3.0
        g.add("mul", ["two", "three"], output="six")
        g.add("identity", ["x"], output="y")
        g.add("identity", ["y"], output="z")
        g.inputs = ["x"]
        g.outputs = ["z"]
        n_folded = constant_fold(g)
        assert n_folded == 1 and g.constants["six"] == 6.0
        removed = eliminate_shape_ops(g)
        assert removed == 2 and g.outputs == ["x"]
        assert dead_code_elim(g) == 0


class TestSQLGen:
    def test_matmul_sql_shape(self):
        """The emitted SQL for a linear op matches the paper's §2.1 pattern:
        JOIN on chunk index + SUM(dot) + GROUP BY free dims."""
        g = Graph(name="lin")
        g.inputs = ["ids"]
        g.annotate("ids", (("t", 4),))
        g.annotate("vocab", (("tok", 16), ("d", 8)))
        g.initializers["vocab"] = None
        g.initializers["W"] = None
        g.annotate("W", (("j", 8), ("d", 8)))
        x = g.add("embedding", ["vocab", "ids"])
        g.add("linear", [x, "W"], out_features=8, output="y")
        g.outputs = ["y"]
        infer_shapes(g)
        pipe = op_map(g, chunk_size=8)
        sql = generate_sql(pipe, dialect="duckdb")
        assert "JOIN" in sql and "GROUP BY" in sql
        assert "list_dot_product" in sql
        assert "collect_as_array" in sql
        assert "CREATE TABLE W" in sql
        # ANSI dialect also renders
        sql2 = generate_sql(pipe, dialect="ansi")
        assert "dot(" in sql2

    def test_param_placeholder(self):
        from repro.core.relational import Param
        from repro.core.relational import RelSchema
        sch = RelSchema(keys=(("t", 4),), cols=(("s", SCALAR),))
        gen = SQLGenerator.__new__(SQLGenerator)
        gen.dialect = "duckdb"
        out = gen.render_expr(add(key("t"), Param("cache_position")), sch)
        assert ":cache_position" in out
