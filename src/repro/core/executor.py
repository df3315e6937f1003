"""Vectorised columnar executor — runs relational plans on JAX/XLA.

This is the "database engine" half of the TPU adaptation (DESIGN.md §2):
DuckDB's vectorised interpreter is replaced by a dense-key columnar engine
whose physical operators lower to XLA:

  Scan           → array lookup in the environment
  Project        → elementwise VPU ops + reshape/transpose key remaps
  Join (dense)   → address arithmetic: gather along the joined key axes
  GroupAgg       → axis reduction
  Filter         → predicate mask (identity element supplied by the plan)
  Unnest/Collect → reshapes between key axes and the vector payload axis
  TopK           → ``jax.lax.top_k`` along the ordered key

Physical optimisation (the "query optimiser"): a ``GroupAgg(Join(L, R))``
whose aggregate is ``SUM`` of a product/dot of one column from each side is
executed as a fused contraction (``jnp.einsum``) — the relational join never
materialises, mirroring how a vectorised DB pipelines a hash join into an
aggregation without materialising the cross product.  On TPU this is the
MatMul-goes-to-MXU path; ``kernels/chunked_matmul`` is the hand-scheduled
version of the same plan.  The same aggregate over a *value* join on the
weight table's leading key (a routed-expert join: each row names the
expert it is routed to) runs as a grouped matmul: rows sorted by that key,
each group against its own slice of the table, rows that name no slice
dropped (:func:`grouped_matmul`).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import relational as ra
from repro.core.relational import (
    BinOp, Call, Col, Collect, Const, Expr, Filter, GroupAgg, Join, Key,
    KeyParam, Param, Project, RelNode, RelSchema, Scan, TopK, Unnest, SCALAR,
    is_vec, resolve,
)

NEG_INF = -1e30

# Precision of every contraction the executor emits.  The tables are f32
# and the model they encode is the f32 one the tests check; a TPU would
# otherwise contract f32 operands at DEFAULT precision (bf16 passes) and
# serve a different function from the one checked on the CPU.  A payload
# in another dtype brings its own precision and tolerance.
CONTRACT_PRECISION = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class DenseTable:
    """A relation over dense integer key domains.

    ``cols[name]`` has shape ``[*key_sizes]`` (scalar column) or
    ``[*key_sizes, w]`` (vector column).
    """

    keys: Tuple[Tuple[str, int], ...]
    cols: Dict[str, jnp.ndarray]
    col_types: Dict[str, str]

    @property
    def key_names(self):
        return tuple(k for k, _ in self.keys)

    @property
    def key_sizes(self):
        return tuple(s for _, s in self.keys)

    def col(self, name: str) -> jnp.ndarray:
        return self.cols[name]

    def schema(self, name: str = "t") -> RelSchema:
        return RelSchema(keys=self.keys,
                         cols=tuple((c, self.col_types[c]) for c in self.cols))


# A DenseTable is a pytree whose leaves are its columns, in stored order.
# Keys, column order and column types ride in the static aux data, so a
# table passed through ``jax.jit`` comes back with its columns in the
# order it went in (a plain ``dict`` would be flattened in sorted-key
# order).
jax.tree_util.register_pytree_node(
    DenseTable,
    lambda t: (tuple(t.cols.values()),
               (t.keys, tuple(t.cols), tuple(t.col_types.items()))),
    lambda aux, leaves: DenseTable(keys=aux[0],
                                   cols=dict(zip(aux[1], leaves)),
                                   col_types=dict(aux[2])),
)


def table_from_chunked(ct) -> DenseTable:
    """Wrap a ChunkedTensor as a DenseTable (zero-copy)."""
    return DenseTable(
        keys=ct.schema.key_cols,
        cols={ct.schema.vec_col: ct.data},
        col_types={ct.schema.vec_col: ra.VEC(ct.schema.chunk_size)},
    )


def scalar_table(name: str, key_cols, array, col="s") -> DenseTable:
    return DenseTable(keys=tuple(key_cols), cols={col: array},
                      col_types={col: SCALAR})


def col_table_from_dense(arr, col_chunk: int, d_key: str = "d",
                         chunk_key: str = "c", vec_col: str = "chunk"
                         ) -> DenseTable:
    """Build a COL_CHUNK weight table from a dense matrix ``W ∈ R^{m×n}``:
    transposed keys ``(d ∈ [n), c ∈ [⌈m/cs'⌉))`` with the vector chunking
    the *output* dimension (planner ROW2COL physical layout).  Non-divisor
    chunk sizes zero-pad the output tail (the planner itself only picks
    divisors, but stored tables follow the §2.1 padding convention)."""
    data = _col_chunk_data(jnp.asarray(arr), col_chunk)
    n, n_chunks, _ = data.shape
    return DenseTable(
        keys=((d_key, n), (chunk_key, n_chunks)),
        cols={vec_col: data},
        col_types={vec_col: ra.VEC(col_chunk)},
    )


# The layout conversions run as one compiled program each: run op by op,
# the reshape, pad and transpose would each hold a full copy of the table
# at once (three lm_head-sized buffers at Llama-3-8B widths).
@partial(jax.jit, static_argnums=1)
def _col_chunk_data(arr, col_chunk: int):
    """``[m, …]`` → ``[n, ⌈m/cs'⌉, cs']``, ``n`` the trailing size."""
    arr = arr.reshape(arr.shape[0], -1)
    m, n = arr.shape
    n_chunks = max(1, -(-m // col_chunk))
    pad = n_chunks * col_chunk - m
    if pad:
        arr = jnp.pad(arr, ((0, pad), (0, 0)))
    return arr.T.reshape(n, n_chunks, col_chunk)


@partial(jax.jit, static_argnums=1)
def _colh_chunk_data(arr, col_chunk: int):
    """``[H, dh, …]`` → ``[H, n, ⌈dh/cs'⌉, cs']``, ``n`` the trailing
    size."""
    arr = arr.reshape(arr.shape[0], arr.shape[1], -1)
    H, dh, n = arr.shape
    n_chunks = max(1, -(-dh // col_chunk))
    pad = n_chunks * col_chunk - dh
    if pad:
        arr = jnp.pad(arr, ((0, 0), (0, pad), (0, 0)))
    return arr.transpose(0, 2, 1).reshape(H, n, n_chunks, col_chunk)


def transpose_chunked_table(table: DenseTable, col_chunk: int,
                            d_key: str = "d", chunk_key: str = "c"
                            ) -> DenseTable:
    """ROW_CHUNK → COL_CHUNK: re-express a row-chunked weight table
    ``(j, c, chunk[cs])`` as its transposed column-layout twin.  This is the
    executor-side realisation of the planner's ROW2COL data conversion (the
    SQL side is ``LayoutPlan.conversion_sql``)."""
    if len(table.keys) != 2 or len(table.cols) != 1:
        raise ValueError(f"not a 2-key chunked weight table: {table.keys}")
    vec_col, arr = next(iter(table.cols.items()))
    if not is_vec(table.col_types[vec_col]):
        raise ValueError(f"column {vec_col} is not a vector column")
    # [m, nch, cs] flattens to [m, nch·cs] inside the conversion
    return col_table_from_dense(arr, col_chunk, d_key=d_key,
                                chunk_key=chunk_key, vec_col=vec_col)


def colh_table_from_dense(arr, col_chunk: int, head_key: str = "h",
                          d_key: str = "d", chunk_key: str = "c",
                          vec_col: str = "chunk") -> DenseTable:
    """Build a COL_CHUNK_HEADS weight table from a dense per-head projection
    ``W ∈ R^{H×dh×n}``: the head key stays a block key, the per-head output
    (head_dim) is transposed against the input features and chunked —
    keys ``(h ∈ [H), d ∈ [n), c ∈ [⌈dh/cs'⌉))``, data
    ``[H, n, ⌈dh/cs'⌉, cs']`` (non-divisor sizes zero-pad the tail).
    """
    data = _colh_chunk_data(jnp.asarray(arr), col_chunk)
    H, n, n_chunks, _ = data.shape
    return DenseTable(
        keys=((head_key, H), (d_key, n), (chunk_key, n_chunks)),
        cols={vec_col: data},
        col_types={vec_col: ra.VEC(col_chunk)},
    )


def transpose_head_chunked_table(table: DenseTable, col_chunk: int,
                                 d_key: str = "d", chunk_key: str = "c"
                                 ) -> DenseTable:
    """ROW_CHUNK → COL_CHUNK_HEADS: re-express a per-head row-chunked weight
    table ``(h, r, c, chunk[cs])`` as its head-blocked column twin
    ``(h, d, c', chunk[cs'])`` — the executor side of the planner's
    head-blocked ROW2COL conversion."""
    if len(table.keys) != 3 or len(table.cols) != 1:
        raise ValueError(f"not a 3-key per-head weight table: {table.keys}")
    hname = table.keys[0][0]
    vec_col, arr = next(iter(table.cols.items()))
    if not is_vec(table.col_types[vec_col]):
        raise ValueError(f"column {vec_col} is not a vector column")
    # [H, dh, nch, cs] flattens to [H, dh, nch·cs] inside the conversion
    return colh_table_from_dense(arr, col_chunk, head_key=hname,
                                 d_key=d_key, chunk_key=chunk_key,
                                 vec_col=vec_col)


def permute_table_keys(table: DenseTable, key_order) -> DenseTable:
    """Re-key a DenseTable to a new physical key order (name-based axis
    transpose) — the executor realisation of a planner cache-layout choice.
    Vector columns keep their trailing payload axis."""
    key_order = tuple(key_order)
    if key_order == table.key_names:
        return table
    if set(key_order) != set(table.key_names):
        raise ValueError(f"key order {key_order} does not permute "
                         f"{table.key_names}")
    perm = [table.key_names.index(k) for k in key_order]
    sizes = dict(table.keys)
    cols, col_types = {}, {}
    for c, arr in table.cols.items():
        axes = perm + ([len(perm)] if is_vec(table.col_types[c]) else [])
        cols[c] = jnp.transpose(arr, axes)
        col_types[c] = table.col_types[c]
    return DenseTable(keys=tuple((k, sizes[k]) for k in key_order),
                      cols=cols, col_types=col_types)


def rechunk_chunked_table(table: DenseTable, chunk_size: int,
                          true_width: int = 0) -> DenseTable:
    """Re-chunk a chunked table ``(…, c, vec[cs])`` to a new physical chunk
    size — the executor realisation of a planner per-table chunk-size
    decision (SQL side: the table is simply loaded at the new DDL width).

    The trailing chunk-key axis and vector payload are merged back to the
    logical width (``true_width`` strips existing padding when given) and
    re-split at ``chunk_size``, zero-padding the new tail if it does not
    divide.  Leading keys are untouched.
    """
    if len(table.cols) != 1:
        raise ValueError("rechunk expects a single-vector-column table")
    (cname, nch) = table.keys[-1]
    vec_col, arr = next(iter(table.cols.items()))
    if not is_vec(table.col_types[vec_col]):
        raise ValueError(f"column {vec_col} is not a vector column")
    cs = arr.shape[-1]
    width = true_width or nch * cs
    flat = arr.reshape(*arr.shape[:-2], nch * cs)[..., :width]
    n2 = max(1, -(-width // chunk_size))
    pad = n2 * chunk_size - width
    if pad:
        pad_width = [(0, 0)] * (flat.ndim - 1) + [(0, pad)]
        flat = jnp.pad(flat, pad_width)
    data = flat.reshape(*flat.shape[:-1], n2, chunk_size)
    return DenseTable(
        keys=table.keys[:-1] + ((cname, n2),),
        cols={vec_col: data},
        col_types={vec_col: ra.VEC(chunk_size)},
    )


# ---------------------------------------------------------------------------
# Plan-op classification (observability: statement↔op provenance)
# ---------------------------------------------------------------------------

# relational node type → op class.  The same vocabulary names the DB-side
# operators (repro.obs.profile.OPERATOR_CLASSES) so JAX-side step spans
# and DuckDB per-operator profiles attribute to comparable classes.
OP_CLASSES = {
    Scan: "scan",
    Project: "project",
    Join: "join",
    GroupAgg: "aggregate",
    Filter: "filter",
    Unnest: "unnest",
    Collect: "collect",
    TopK: "sort",
}


def iter_plan_nodes(root: RelNode):
    """Every distinct node of a relational plan, root first.

    Plans are DAGs (fused/sharded plans share whole subtrees); tracking
    visited ids keeps the walk linear in distinct nodes — the naive tree
    walk re-visited shared subtrees exponentially, which made per-step
    ``plan_provenance`` dominate traced serving ticks.
    """
    stack = [root]
    seen = {id(root)}
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (Project, Filter, Unnest, Collect, GroupAgg,
                             TopK)):
            kids = (node.input,)
        elif isinstance(node, Join):
            kids = (node.left, node.right)
        else:
            kids = ()
        for kid in kids:
            if id(kid) not in seen:
                seen.add(id(kid))
                stack.append(kid)


def classify_plan_node(node: RelNode) -> str:
    return OP_CLASSES.get(type(node), "other")


def plan_provenance(root: RelNode) -> Tuple[Tuple[str, ...],
                                            Tuple[str, ...]]:
    """(op classes, scanned base tables) of a plan — the provenance tag
    the SQL generator stamps on each emitted statement so DB profiles
    can be attributed back to relational ops (repro.obs)."""
    ops, tables = set(), set()
    for node in iter_plan_nodes(root):
        ops.add(classify_plan_node(node))
        if isinstance(node, Scan):
            tables.add(node.table)
    return tuple(sorted(ops)), tuple(sorted(tables))


# ---------------------------------------------------------------------------
# Expression evaluation
# ---------------------------------------------------------------------------


def _key_axis(table: DenseTable, name: str) -> int:
    return table.key_names.index(name)


def _eval_key_expr(expr: Expr, key_names, key_sizes, scalars=None,
                   table: Optional[DenseTable] = None) -> jnp.ndarray:
    """Evaluate an integer expression over key columns (and over the
    scalar columns of ``table``, where one is given).

    Returns an array broadcastable against ``[*key_sizes]`` (aranges are
    reshaped into their key's axis position, so e.g. ``h // 4`` stays O(H)).
    """
    nk = len(key_names)
    scalars = scalars or {}

    def rec(e: Expr):
        if isinstance(e, Key):
            ax = key_names.index(e.name)
            shape = [1] * nk
            shape[ax] = key_sizes[ax]
            return jnp.arange(key_sizes[ax], dtype=jnp.int32).reshape(shape)
        if isinstance(e, Col) and table is not None:
            return table.cols[e.name]
        if isinstance(e, Const):
            return jnp.asarray(int(e.value), dtype=jnp.int32)
        if isinstance(e, Param):
            return jnp.asarray(scalars[e.name], dtype=jnp.int32)
        if isinstance(e, KeyParam):
            # per-key parameter vector: bound value has one entry per row
            # of the key domain, broadcast into that key's axis
            ax = key_names.index(e.key)
            shape = [1] * nk
            shape[ax] = key_sizes[ax]
            return jnp.asarray(scalars[e.name], dtype=jnp.int32).reshape(
                shape)
        if isinstance(e, BinOp):
            l, r = rec(e.lhs), rec(e.rhs)
            return {
                "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply,
                "//": jnp.floor_divide, "%": jnp.mod,
            }[e.op](l, r)
        raise TypeError(f"not a key expression: {e!r}")

    return rec(expr)


_UNARY = {
    "exp": jnp.exp,
    "neg": jnp.negative,
    "sqrt": jnp.sqrt,
    "rsqrt": lambda x: jax.lax.rsqrt(x),
    "sigmoid": jax.nn.sigmoid,
    "silu": jax.nn.silu,
    "gelu": partial(jax.nn.gelu, approximate=True),
    "square": jnp.square,
    "identity": lambda x: x,
}


def _eval_expr(expr: Expr, table: DenseTable) -> Tuple[jnp.ndarray, bool]:
    """Evaluate a projection/aggregate expression.

    Returns ``(array, is_vec)``; scalar arrays have shape ``[*key_sizes]``
    (broadcastable), vector arrays carry a trailing payload axis.
    """
    if isinstance(expr, Col):
        return table.cols[expr.name], is_vec(table.col_types[expr.name])
    if isinstance(expr, Key):
        return _eval_key_expr(expr, table.key_names, table.key_sizes).astype(
            jnp.float32), False
    if isinstance(expr, Const):
        return jnp.asarray(expr.value), False
    if isinstance(expr, BinOp):
        (lv, lvec), (rv, rvec) = _eval_expr(expr.lhs, table), _eval_expr(
            expr.rhs, table)
        if lvec and not rvec:
            rv = rv[..., None] if jnp.ndim(rv) else rv
        if rvec and not lvec:
            lv = lv[..., None] if jnp.ndim(lv) else lv
        fn = {"+": jnp.add, "-": jnp.subtract, "*": jnp.multiply,
              "/": jnp.divide, "//": jnp.floor_divide, "%": jnp.mod,
              "max": jnp.maximum, "min": jnp.minimum}[expr.op]
        return fn(lv, rv), lvec or rvec
    if isinstance(expr, Call):
        if expr.fn == "dot":
            # an elementwise product and a sum, both exact f32 ops on every
            # backend: no matmul unit, so no matmul precision applies
            a, _ = _eval_expr(expr.args[0], table)
            b, _ = _eval_expr(expr.args[1], table)
            return jnp.sum(a * b, axis=-1), False
        if expr.fn == "vsum":
            a, _ = _eval_expr(expr.args[0], table)
            return jnp.sum(a, axis=-1), False
        if expr.fn == "scale":
            a, av = _eval_expr(expr.args[0], table)
            s, _ = _eval_expr(expr.args[1], table)
            return a * s, av
        if expr.fn == "concat":
            parts = [_eval_expr(a, table)[0] for a in expr.args]
            return jnp.concatenate(parts, axis=-1), True
        if expr.fn == "first_half":
            a, _ = _eval_expr(expr.args[0], table)
            return a[..., : a.shape[-1] // 2], True
        if expr.fn == "second_half":
            a, _ = _eval_expr(expr.args[0], table)
            return a[..., a.shape[-1] // 2:], True
        if expr.fn == "nf4_dequant":
            # NF4 codebook lookup (repro.quant): integer codes -> the 16
            # normalised NormalFloat levels; the scale multiply is an
            # ordinary vec x scalar BinOp around this call
            from repro.quant.codecs import nf4_dequant_levels
            a, _ = _eval_expr(expr.args[0], table)
            return nf4_dequant_levels(a), True
        if expr.fn in _UNARY:
            a, av = _eval_expr(expr.args[0], table)
            return _UNARY[expr.fn](a), av
        raise NotImplementedError(f"intrinsic {expr.fn}")
    raise TypeError(expr)


# ---------------------------------------------------------------------------
# Key-remap (Project.keys) structural compiler: split / merge / permute
# ---------------------------------------------------------------------------


def _apply_key_remap(arr: jnp.ndarray, in_keys, out_defs, has_vec: bool):
    """Realise an integer key remapping as reshape/transpose.

    ``out_defs``: list of (name, size, Expr) where each Expr is one of
      Key(k)                      — rename / permute
      Key(k) // n                 — high part of a split
      Key(k) % n                  — low part of a split
      Key(a) * n + Key(b)         — merge (a outer, b inner, n = size of b)
    This is the paper's "integer-based remapping via a single projection".
    """
    in_names = [k for k, _ in in_keys]
    in_sizes = [s for _, s in in_keys]

    # --- split pass: input axes referenced via // and % get reshaped apart
    split_spec: Dict[str, Optional[int]] = {}
    for _, _, e in out_defs:
        for sub in _iter_exprs(e):
            if isinstance(sub, BinOp) and sub.op in ("//", "%") and isinstance(
                    sub.lhs, Key) and isinstance(sub.rhs, Const):
                n = int(sub.rhs.value)
                prev = split_spec.get(sub.lhs.name)
                if prev is not None and prev != n:
                    raise ValueError(
                        f"inconsistent split factors for key {sub.lhs.name}")
                split_spec[sub.lhs.name] = n

    mid_names, mid_shape = [], []
    for name, size in zip(in_names, in_sizes):
        if name in split_spec:
            n = split_spec[name]
            mid_names += [f"{name}::hi", f"{name}::lo"]
            mid_shape += [size // n, n]
        else:
            mid_names.append(name)
            mid_shape.append(size)
    arr = arr.reshape(*mid_shape, *(arr.shape[len(in_sizes):]))

    # --- map each output def to the intermediate axes it consumes
    def axes_for(e: Expr):
        if isinstance(e, Key):
            return [mid_names.index(e.name)]
        if isinstance(e, BinOp) and e.op == "//":
            return [mid_names.index(f"{e.lhs.name}::hi")]
        if isinstance(e, BinOp) and e.op == "%":
            return [mid_names.index(f"{e.lhs.name}::lo")]
        if isinstance(e, BinOp) and e.op == "+":
            # Key(a)*n + <inner>; inner may itself be a split part
            mul = e.lhs
            assert isinstance(mul, BinOp) and mul.op == "*", (
                f"unsupported merge expr {e!r}")
            return axes_for(mul.lhs) + axes_for(e.rhs)
        raise ValueError(f"unsupported key remap expr {e!r}")

    perm, out_group_sizes = [], []
    for _, size, e in out_defs:
        axes = axes_for(e)
        perm += axes
        out_group_sizes.append(size)
    tail = list(range(len(mid_shape), arr.ndim))
    arr = arr.transpose(*perm, *tail)
    arr = arr.reshape(*out_group_sizes, *(arr.shape[len(perm):]))
    return arr


def _iter_exprs(e: Expr):
    yield e
    if isinstance(e, BinOp):
        yield from _iter_exprs(e.lhs)
        yield from _iter_exprs(e.rhs)
    elif isinstance(e, Call):
        for a in e.args:
            yield from _iter_exprs(a)


# ---------------------------------------------------------------------------
# Join: gather right-side columns along joined key axes
# ---------------------------------------------------------------------------


def _gather_right(left: DenseTable, right: DenseTable, on, rcol: str):
    """Gather a right column into the joined table's key space.

    Result axes: [*left_keys, *surviving_right_keys] (+payload).
    """
    joined = dict(on)  # right_key -> Expr over left keys / left columns
    l_sizes = left.key_sizes
    surv = [(k, s) for k, s in right.keys if k not in joined]
    out_rank = len(l_sizes) + len(surv)

    idx_arrays = []
    surv_pos = 0
    for k, s in right.keys:
        if k in joined:
            e = joined[k]
            idx = _join_index(e, left)
            # reshape/broadcast to [*l_sizes, *1s]
            idx = jnp.broadcast_to(idx, l_sizes)
            idx = idx.reshape(l_sizes + (1,) * len(surv))
        else:
            shape = [1] * out_rank
            shape[len(l_sizes) + surv_pos] = s
            idx = jnp.arange(s, dtype=jnp.int32).reshape(shape)
            surv_pos += 1
        idx_arrays.append(idx)

    rarr = right.cols[rcol]
    if is_vec(right.col_types[rcol]):
        return rarr[tuple(idx_arrays) + (slice(None),)]
    return rarr[tuple(idx_arrays)]


def _join_index(e: Expr, left: DenseTable) -> jnp.ndarray:
    """Index expression for a join condition: over left keys or left columns."""
    if isinstance(e, Col):  # value join, e.g. vocab.token = ids.tok
        return left.cols[e.name].astype(jnp.int32)
    return _eval_key_expr(e, left.key_names, left.key_sizes)


# ---------------------------------------------------------------------------
# Fused GroupAgg(Join) → contraction
# ---------------------------------------------------------------------------


def _try_fused_join_agg(node: GroupAgg, env, memo, scalars=None,
                        tracer=None):
    """Recognise γ_{G, SUM(f(l_col, r_col))}(L ⋈ R) and run it as einsum.

    Conditions: single SUM aggregate whose expression is ``dot(a, b)``,
    ``mul(a, b)`` or ``scale(dot(a, b), c)`` with ``a`` from the left input
    and ``b`` from the right; every join condition references at most one
    left key.  Returns None when the pattern does not apply.
    """
    if not isinstance(node.input, Join) or len(node.aggs) != 1:
        return None
    out_col, fn, expr = node.aggs[0]
    if fn != "SUM":
        return None
    scale_const = None
    if isinstance(expr, Call) and expr.fn == "scale" and isinstance(
            expr.args[1], Const):
        scale_const = expr.args[1].value
        expr = expr.args[0]
    if isinstance(expr, Call) and expr.fn == "dot":
        contract_payload = True
        a, b = expr.args
    elif isinstance(expr, BinOp) and expr.op == "*":
        contract_payload = False
        a, b = expr.lhs, expr.rhs
    else:
        return None
    if not (isinstance(a, Col) and isinstance(b, Col)):
        return None

    join = node.input
    left = execute(join.left, env, memo, scalars, tracer)
    right = execute(join.right, env, memo, scalars, tracer)
    ls, rs = left.schema(), right.schema()
    if a.name in left.cols and b.name in right.cols:
        lcol, rcol = a.name, b.name
    elif b.name in left.cols and a.name in right.cols:
        lcol, rcol = b.name, a.name
    else:
        return None

    # join conditions must bind each right key to exactly one left key (or be
    # a value join, which the fused path does not handle)
    joined: Dict[str, str] = {}
    for rkey, e in join.on:
        keys_in = [s for s in _iter_exprs(e) if isinstance(s, Key)]
        if isinstance(e, Col) or len(keys_in) != 1:
            return None
        joined[rkey] = keys_in[0].name

    # gather right along joined axes so its axes are named by left keys
    rarr = right.cols[rcol]
    raxes = []
    for ax, (rkey, size) in enumerate(right.keys):
        if rkey in joined:
            e = dict(join.on)[rkey]
            if not isinstance(e, Key):  # non-trivial map, e.g. h // g
                idx = _eval_key_expr(
                    e, left.key_names, left.key_sizes)
                # the expression depends on exactly one left key; flatten it
                lname = joined[rkey]
                lax = left.key_names.index(lname)
                idx1d = jnp.ravel(
                    jnp.broadcast_to(
                        idx, tuple(1 if i != lax else left.key_sizes[i]
                                   for i in range(len(left.key_sizes)))))
                rarr = jnp.take(rarr, idx1d, axis=ax)
            raxes.append(joined[rkey])
        else:
            raxes.append(rkey)

    lvec = is_vec(left.col_types[lcol])
    rvec = is_vec(right.col_types[rcol])
    larr = left.cols[lcol]

    # assign einsum letters
    letters = {}

    def letter(name):
        if name not in letters:
            letters[name] = chr(ord("a") + len(letters))
        return letters[name]

    l_sub = "".join(letter(k) for k in left.key_names) + (
        letter("__w") if lvec else "")
    r_sub = "".join(letter(k) for k in raxes) + (letter("__w") if rvec else "")
    out_names = list(node.group_keys)
    out_vec = (lvec or rvec) and not contract_payload
    o_sub = "".join(letter(k) for k in out_names) + (
        letter("__w") if out_vec else "")
    res = jnp.einsum(f"{l_sub},{r_sub}->{o_sub}", larr, rarr,
                     precision=CONTRACT_PRECISION)
    if scale_const is not None:
        res = res * scale_const

    out_schema = resolve(node)
    return DenseTable(
        keys=out_schema.keys,
        cols={out_col: res},
        col_types={out_col: out_schema.col_type(out_col)},
    )


def grouped_block(rows: int, groups: int) -> int:
    """Rows of one tile of :func:`grouped_matmul`: the power of two (8 to
    256) nearest above an even share of ``rows`` over ``groups``, so that
    a group of average size takes one tile and so reads its weights once."""
    b = 8
    while b < 256 and b * groups < rows:
        b *= 2
    return b


@partial(jax.jit, static_argnums=3)
def grouped_matmul(rows, groups, weights, block: int):
    """``out[m] = weights[groups[m]] @ rows[m]`` for every row whose group
    lies in ``[0, G)``, and zero for the others (an inner join drops
    them); ``rows`` is ``[M, K]``, ``weights`` ``[G, N, K]``.

    The rows are sorted by group and cut into tiles of ``block`` rows that
    each lie in one group; a loop over the tiles there are multiplies each
    by its group's ``[N, K]`` slice.  A group no row names is never read,
    and no row is multiplied by a group it does not name.  Shapes are
    static (``M``, ``G`` and ``block`` fix them), so the whole runs inside
    a compiled program."""
    m, k = rows.shape
    g, n, _ = weights.shape
    gid = jnp.where((groups >= 0) & (groups < g), groups, g)
    order = jnp.argsort(gid, stable=True)
    counts = jnp.bincount(gid, length=g + 1)[:g]
    ends = jnp.cumsum(counts)
    tiles = (counts + block - 1) // block
    tile_ends = jnp.cumsum(tiles)
    # tile t belongs to the first group whose tiles end after it
    t = jnp.arange(-(-m // block) + g)
    tg = jnp.minimum(jnp.searchsorted(tile_ends, t, side="right"), g - 1)
    first = ends[tg] - counts[tg] + (t - tile_ends[tg] + tiles[tg]) * block
    last = ends[tg]
    # sorted rows, then one tile of zeros so that no slice is clamped
    xs = jnp.concatenate([rows[order], jnp.zeros((block, k), rows.dtype)])
    lanes = jnp.arange(block)

    def tile(i, out):
        r0 = first[i]
        x = jax.lax.dynamic_slice_in_dim(xs, r0, block)
        y = jnp.einsum("bk,nk->bn", x, weights[tg[i]],
                       precision=CONTRACT_PRECISION)
        keep = (r0 + lanes < last[i])[:, None]
        cur = jax.lax.dynamic_slice_in_dim(out, r0, block)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(keep, y.astype(out.dtype), cur), r0, 0)

    out = jax.lax.fori_loop(0, tile_ends[-1], tile,
                            jnp.zeros((m + block, n), rows.dtype))
    return jnp.zeros((m, n), rows.dtype).at[order].set(out[:m])


def _try_grouped_join_agg(node: GroupAgg, env, memo, scalars=None,
                          tracer=None):
    """Recognise γ_{G, SUM(dot(a, b))}(L ⋈ R) whose join binds R's leading
    key to a column of L (a value join, the routed expert of each row) and
    R's other joined keys to keys of L, and run it as a grouped matmul.

    The rows are L's keys that the join does not contract; each takes the
    slice of R its value names, and a row whose value names no slice of R
    finds no partner and drops out (an inner join), so no row meets a
    slice it does not name.  Returns None when the pattern does not
    apply."""
    if not isinstance(node.input, Join) or len(node.aggs) != 1:
        return None
    out_col, fn, expr = node.aggs[0]
    if fn != "SUM" or not (isinstance(expr, Call) and expr.fn == "dot"):
        return None
    a, b = expr.args
    if not (isinstance(a, Col) and isinstance(b, Col)):
        return None
    join = node.input
    values = [(rk, e) for rk, e in join.on if isinstance(e, Col)]
    keyed = [(rk, e) for rk, e in join.on if not isinstance(e, Col)]
    if len(values) != 1 or not all(isinstance(e, Key) for _, e in keyed):
        return None
    left = execute(join.left, env, memo, scalars, tracer)
    right = execute(join.right, env, memo, scalars, tracer)
    vkey, vcol = values[0][0], values[0][1].name
    if right.key_names[0] != vkey or vcol not in left.cols:
        return None
    if a.name in left.cols and b.name in right.cols:
        lcol, rcol = a.name, b.name
    elif b.name in left.cols and a.name in right.cols:
        lcol, rcol = b.name, a.name
    else:
        return None
    if not (is_vec(left.col_types[lcol]) and is_vec(right.col_types[rcol])):
        return None
    contract = [e.name for _, e in keyed]
    rows_keys = [k for k in left.key_names if k not in contract]
    surv = [k for k in right.key_names[1:] if k not in dict(keyed)]
    if set(node.group_keys) != set(rows_keys) | set(surv):
        return None

    lsizes = dict(left.keys)
    laxes = [left.key_names.index(k) for k in rows_keys + contract]
    larr = left.cols[lcol]
    larr = jnp.transpose(
        jnp.broadcast_to(larr, left.key_sizes + larr.shape[-1:]),
        laxes + [len(laxes)])
    m = int(np.prod([lsizes[k] for k in rows_keys], dtype=np.int64))
    larr = larr.reshape(m, -1)
    # the routed value is constant along the contracted keys
    gid = jnp.transpose(jnp.broadcast_to(left.cols[vcol], left.key_sizes),
                        laxes)
    gid = gid.reshape((m, -1))[:, 0].astype(jnp.int32)

    rsizes = dict(right.keys)
    rkey_of = {e.name: rk for rk, e in keyed}
    rax = [0] + [right.key_names.index(k) for k in surv] + [
        right.key_names.index(rkey_of[k]) for k in contract]
    rarr = jnp.transpose(right.cols[rcol], rax + [len(rax)])
    n = int(np.prod([rsizes[k] for k in surv], dtype=np.int64))
    rarr = rarr.reshape(rsizes[vkey], n, -1)
    if rarr.shape[-1] != larr.shape[-1]:
        return None

    res = grouped_matmul(larr, gid, rarr,
                         grouped_block(m, rsizes[vkey]))
    got = rows_keys + surv
    res = res.reshape(tuple(lsizes[k] for k in rows_keys)
                      + tuple(rsizes[k] for k in surv))
    out_schema = resolve(node)
    res = jnp.transpose(res, [got.index(k) for k in out_schema.key_names])
    return DenseTable(keys=out_schema.keys, cols={out_col: res},
                      col_types={out_col: out_schema.col_type(out_col)})


# ---------------------------------------------------------------------------
# Main interpreter
# ---------------------------------------------------------------------------


def execute(node: RelNode, env: Dict[str, DenseTable],
            memo: Optional[Dict[int, DenseTable]] = None,
            scalars: Optional[Dict] = None,
            tracer=None) -> DenseTable:
    """Execute a relational plan against ``env`` (table name → DenseTable).

    Scan nodes are never memoised (cache tables mutate between pipeline
    steps); every other node is memoised by identity so shared subplans
    across steps evaluate once.

    ``tracer`` (an ``Optional[repro.obs.trace.TraceRecorder]``) records
    one ``cat="op"`` span per executed plan node.  JAX dispatch is
    asynchronous, so per-op spans measure dispatch/build time — step-level
    wall time comes from ``run_pipeline``'s ``cat="step"`` spans, which
    block on the step's outputs.  With ``tracer=None`` (the default) the
    only overhead is this ``None`` check — do not trace under ``jit``.
    """
    if memo is None:
        memo = {}
    if isinstance(node, Scan):
        if node.table not in env:
            raise KeyError(f"table {node.table!r} not bound in environment")
        t = env[node.table]
        s = node.table_schema
        if t.key_names != s.key_names or tuple(t.cols) != s.col_names:
            if t.key_sizes != tuple(sz for _, sz in s.keys):
                raise ValueError(
                    f"table {node.table!r}: stored key sizes {t.key_sizes} "
                    f"!= schema {s.keys}")
            # the schema's columns stored in another order are matched by
            # name; only a real rename (other names) pairs by position
            stored = (s.col_names if set(t.cols) == set(s.col_names)
                      else tuple(t.cols))
            cols = {n: t.cols[o] for n, o in zip(s.col_names, stored)}
            col_types = {n: t.col_types[o]
                         for n, o in zip(s.col_names, stored)}
            t = DenseTable(keys=s.keys, cols=cols, col_types=col_types)
        return t
    if id(node) in memo:
        return memo[id(node)]
    if tracer is None:
        out = _execute(node, env, memo, scalars)
    else:
        # spans inherit the ambient TraceContext (request ids) inside
        # TraceRecorder.span; direct Scan inputs ride along so a
        # request-scoped dump shows which stored tables each op read
        kids = ((node.left, node.right) if isinstance(node, Join)
                else (getattr(node, "input", None),))
        tables = sorted({c.table for c in kids if isinstance(c, Scan)})
        with tracer.span(classify_plan_node(node), cat="op",
                         node=type(node).__name__,
                         **({"tables": tables} if tables else {})):
            out = _execute(node, env, memo, scalars, tracer)
    memo[id(node)] = out
    return out


def _execute(node: RelNode, env, memo, scalars=None,
             tracer=None) -> DenseTable:

    if isinstance(node, Project):
        t = execute(node.input, env, memo, scalars, tracer)
        schema = resolve(node)
        cols, col_types = {}, {}
        for (cname, _, e), (_, ctype) in zip(node.exprs, schema.cols):
            arr, vec = _eval_expr(e, t)
            full = t.key_sizes + ((arr.shape[-1],) if vec else ())
            arr = jnp.broadcast_to(arr, full) if arr.shape != full else arr
            if node.keys is not None:
                arr = _apply_key_remap(arr, t.keys, node.keys, vec)
            cols[cname] = arr
            col_types[cname] = ctype
        return DenseTable(keys=schema.keys, cols=cols, col_types=col_types)

    if isinstance(node, Join):
        left = execute(node.left, env, memo, scalars, tracer)
        right = execute(node.right, env, memo, scalars, tracer)
        schema = resolve(node)
        out_cols, out_types = {}, {}
        surv = [(k, s) for k, s in right.keys if k not in dict(node.on)]
        pad = (1,) * len(surv)
        for cname in left.cols:
            arr = left.cols[cname]
            vec = is_vec(left.col_types[cname])
            if vec:
                arr = arr.reshape(left.key_sizes + pad + (arr.shape[-1],))
            else:
                arr = jnp.broadcast_to(arr, left.key_sizes).reshape(
                    left.key_sizes + pad)
            out_cols[cname] = arr
            out_types[cname] = left.col_types[cname]
        for cname in right.cols:
            oname = cname if cname not in out_cols else cname + "_r"
            out_cols[oname] = _gather_right(left, right, node.on, cname)
            out_types[oname] = right.col_types[cname]
        # broadcast everything to the full key space lazily: keep as-is; the
        # consumers (_eval_expr / reductions) broadcast correctly.
        return DenseTable(keys=schema.keys, cols=out_cols, col_types=out_types)

    if isinstance(node, GroupAgg):
        fused = (_try_fused_join_agg(node, env, memo, scalars, tracer)
                 or _try_grouped_join_agg(node, env, memo, scalars, tracer))
        if fused is not None:
            return fused
        t = execute(node.input, env, memo, scalars, tracer)
        schema = resolve(node)
        consumed = [i for i, (k, _) in enumerate(t.keys)
                    if k not in node.group_keys]
        cols, col_types = {}, {}
        for (out, fn, e), (_, ctype) in zip(node.aggs, schema.cols):
            arr, vec = _eval_expr(e, t)
            full = t.key_sizes + ((arr.shape[-1],) if vec else ())
            arr = jnp.broadcast_to(arr, full)
            red = {"SUM": jnp.sum, "MAX": jnp.max, "MIN": jnp.min,
                   "AVG": jnp.mean}[fn]
            cols[out] = red(arr, axis=tuple(consumed))
            col_types[out] = ctype
        return DenseTable(keys=schema.keys, cols=cols, col_types=col_types)

    if isinstance(node, Filter):
        t = execute(node.input, env, memo, scalars, tracer)
        op, lhs, rhs = node.predicate
        l = _eval_key_expr(lhs, t.key_names, t.key_sizes, scalars, t)
        r = _eval_key_expr(rhs, t.key_names, t.key_sizes, scalars, t)
        mask = {"<=": jnp.less_equal, "<": jnp.less, "==": jnp.equal,
                ">=": jnp.greater_equal, ">": jnp.greater}[op](l, r)
        mask = jnp.broadcast_to(mask, t.key_sizes)
        cols, col_types = {}, {}
        for c, arr in t.cols.items():
            vec = is_vec(t.col_types[c])
            m = mask[..., None] if vec else mask
            full = t.key_sizes + ((arr.shape[-1],) if vec else ())
            arr = jnp.broadcast_to(arr, full)
            cols[c] = jnp.where(m, arr, node.masked_value)
            col_types[c] = t.col_types[c]
        return DenseTable(keys=t.keys, cols=cols, col_types=col_types)

    if isinstance(node, Unnest):
        t = execute(node.input, env, memo, scalars, tracer)
        schema = resolve(node)
        varr = t.cols[node.vec_col]
        cols = {node.elem_col: varr}
        col_types = {node.elem_col: SCALAR}
        for c, arr in t.cols.items():
            if c == node.vec_col:
                continue
            cols[c] = jnp.broadcast_to(
                arr[..., None], t.key_sizes + (varr.shape[-1],))
            col_types[c] = t.col_types[c]
        return DenseTable(keys=schema.keys, cols=cols, col_types=col_types)

    if isinstance(node, TopK):
        t = execute(node.input, env, memo, scalars, tracer)
        schema = resolve(node)
        ax = t.key_names.index(node.order_key)
        vals = jnp.moveaxis(
            jnp.broadcast_to(t.cols[node.order_col], t.key_sizes), ax, -1)
        top, ids = jax.lax.top_k(vals, node.k)
        return DenseTable(keys=schema.keys,
                          cols={node.id_col: ids, node.value_col: top},
                          col_types=dict(schema.cols))

    if isinstance(node, Collect):
        t = execute(node.input, env, memo, scalars, tracer)
        schema = resolve(node)
        ax = t.key_names.index(node.fold_key)
        arr = jnp.broadcast_to(t.cols[node.scalar_col], t.key_sizes)
        arr = jnp.moveaxis(arr, ax, -1)
        cols = {node.vec_col: arr}
        col_types = {node.vec_col: schema.col_type(node.vec_col)}
        for c, a in t.cols.items():
            if c == node.scalar_col:
                continue
            # other scalar columns must be constant along the folded key;
            # take index 0 (used for carrying row ids through collects)
            cols[c] = jnp.take(jnp.broadcast_to(a, t.key_sizes), 0, axis=ax)
            col_types[c] = t.col_types[c]
        return DenseTable(keys=schema.keys, cols=cols, col_types=col_types)

    raise TypeError(node)
