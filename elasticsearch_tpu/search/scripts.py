"""Script engine: a safe, compilable expression language over doc values.

Plays the role of the reference's ScriptService + lang-expression plugin
(core/script/ScriptService.java:227; plugins/lang-expression — the engine
BASELINE.json's configs name for script_score): expressions compile once and
evaluate **vectorized over all docs** as jnp ops — no per-doc interpreter.

Grammar: Python expression syntax restricted to arithmetic/comparison ops,
math functions, and the ES script bindings:

    doc['field'] / doc['field'].value → the field's doc-values column
                              (0 where a document has no value)
    doc['field'].empty        → whether a document has no value
    doc['geo'].lat / .lon     → a geo_point field's latitude / longitude
    _score                    → the query score vector
    params.x / params['x']    → request-supplied constants
    cosineSimilarity(params.qv, 'field')   → vector similarity (+ dotProduct)
    log/log10/sqrt/abs/exp/min/max/pow/sigmoid/floor/ceil

Compiled via the Python ``ast`` module with a strict whitelist (the sandbox
the reference gets from Lucene expressions' closed grammar).
"""

from __future__ import annotations

import ast as _pyast
from typing import Any, Callable

import jax.numpy as jnp

from elasticsearch_tpu.common.errors import QueryParsingError, IllegalArgumentError

_ALLOWED_BINOPS = {
    _pyast.Add: lambda a, b: a + b,
    _pyast.Sub: lambda a, b: a - b,
    _pyast.Mult: lambda a, b: a * b,
    _pyast.Div: lambda a, b: a / b,
    _pyast.Mod: lambda a, b: a % b,
    _pyast.Pow: lambda a, b: a ** b,
}
_ALLOWED_CMPOPS = {
    _pyast.Gt: lambda a, b: a > b, _pyast.GtE: lambda a, b: a >= b,
    _pyast.Lt: lambda a, b: a < b, _pyast.LtE: lambda a, b: a <= b,
    _pyast.Eq: lambda a, b: a == b, _pyast.NotEq: lambda a, b: a != b,
}

def _fold(fn, args):
    from functools import reduce
    if len(args) == 1:
        return args[0]
    return reduce(fn, args)


_FUNCS: dict[str, Callable] = {
    "log": jnp.log, "ln": jnp.log, "log10": jnp.log10, "sqrt": jnp.sqrt,
    "abs": jnp.abs, "exp": jnp.exp, "floor": jnp.floor, "ceil": jnp.ceil,
    # variadic like the builtins, elementwise like jnp (folded pairwise)
    "min": lambda *a: _fold(jnp.minimum, a),
    "max": lambda *a: _fold(jnp.maximum, a),
    "pow": jnp.power,
    "sigmoid": lambda x, k=1.0, a=1.0: x ** a / (x ** a + k ** a),
    "saturation": lambda x, k: x / (x + k),
}


class ScriptError(QueryParsingError):
    """A script the engine cannot evaluate: the request's own error, which
    Elasticsearch answers as a ``script_exception`` (400) — never a device
    fault, so the compiled paths re-raise it rather than fall back, and
    it does not count against the plane breaker."""
    error_type = "script_exception"


class ScriptContext:
    """Per-segment evaluation context handed to compiled scripts."""

    def __init__(self, get_numeric_column, get_vector_column, scores,
                 params: dict, variables: dict | None = None,
                 get_geo_column=None):
        self.get_numeric_column = get_numeric_column   # field → ([N] f32, exists)
        self.get_vector_column = get_vector_column     # field → ([N, D] f32, exists)
        # field → ([N] lat, [N] lon, exists), None for a field that is
        # not a geo_point
        self.get_geo_column = get_geo_column
        self.scores = scores                           # [N] f32
        self.params = params
        # bare-name bindings (bucket_script/bucket_selector buckets_path
        # values) — resolved before the _score special name
        self.variables = variables or {}


class CompiledScript:
    def __init__(self, source: str):
        self.source = source
        try:
            tree = _pyast.parse(source, mode="eval")
        except SyntaxError as e:
            raise QueryParsingError(f"script compile error: {e}") from None
        self._tree = tree

    def evaluate(self, ctx: ScriptContext):
        return _eval(self._tree.body, ctx)

    def vector_fields(self) -> set | None:
        """Plan-time scan: the vector fields this script's accessors
        (cosineSimilarity / dotProduct) read. Drives traced-input
        tree-shaking — a numeric-only script must not force multi-GB
        vector columns into the compiled program. Returns the (possibly
        empty) set of constant field names, or None when a field argument
        is not a literal (caller must assume all vector columns)."""
        out: set = set()
        for node in _pyast.walk(self._tree):
            if isinstance(node, _pyast.Call) and \
                    isinstance(node.func, _pyast.Name) and \
                    node.func.id in ("cosineSimilarity", "dotProduct"):
                if len(node.args) == 2 and \
                        isinstance(node.args[1], _pyast.Constant) and \
                        isinstance(node.args[1].value, str):
                    out.add(node.args[1].value)
                else:
                    return None
        return out

    def uses_vectors(self) -> bool:
        fields = self.vector_fields()
        return fields is None or bool(fields)


def _eval(node: _pyast.AST, ctx: ScriptContext) -> Any:  # noqa: C901
    if isinstance(node, _pyast.Constant):
        if isinstance(node.value, (int, float, str)):
            return node.value
        raise QueryParsingError(f"script constant not allowed: {node.value!r}")
    if isinstance(node, _pyast.Name):
        if node.id in ctx.variables:
            return ctx.variables[node.id]
        if node.id == "_score":
            return ctx.scores
        raise QueryParsingError(f"unknown script variable [{node.id}]")
    if isinstance(node, _pyast.BinOp):
        op = _ALLOWED_BINOPS.get(type(node.op))
        if op is None:
            raise QueryParsingError("operator not allowed in script")
        return op(_eval(node.left, ctx), _eval(node.right, ctx))
    if isinstance(node, _pyast.UnaryOp):
        if isinstance(node.op, _pyast.USub):
            return -_eval(node.operand, ctx)
        if isinstance(node.op, _pyast.Not):
            return jnp.logical_not(_eval(node.operand, ctx))
        raise QueryParsingError("unary operator not allowed in script")
    if isinstance(node, _pyast.Compare):
        left = _eval(node.left, ctx)
        result = None
        for cmp_op, comp in zip(node.ops, node.comparators):
            op = _ALLOWED_CMPOPS.get(type(cmp_op))
            if op is None:
                raise QueryParsingError("comparison not allowed in script")
            right = _eval(comp, ctx)
            piece = op(left, right)
            result = piece if result is None else \
                jnp.logical_and(result, piece)
            left = right
        return result
    if isinstance(node, _pyast.BoolOp):
        fold = jnp.logical_and if isinstance(node.op, _pyast.And) \
            else jnp.logical_or
        out = _eval(node.values[0], ctx)
        for v in node.values[1:]:
            out = fold(out, _eval(v, ctx))
        return out
    if isinstance(node, _pyast.IfExp):
        cond = _eval(node.test, ctx)
        return jnp.where(cond, _eval(node.body, ctx), _eval(node.orelse, ctx))
    if isinstance(node, _pyast.Subscript):
        # doc['field'] and params['x']
        base = node.value
        key_node = node.slice
        if isinstance(key_node, _pyast.Constant):
            key = key_node.value
        else:
            raise QueryParsingError("subscript must be a literal")
        if isinstance(base, _pyast.Name) and base.id == "doc":
            # a bare doc['f'] is its value, as Lucene expressions bind it
            return _doc_attribute(ctx, str(key), "value")
        if isinstance(base, _pyast.Name) and base.id == "params":
            return _param(ctx, str(key))
        raise QueryParsingError("only doc[...] / params[...] subscripts allowed")
    if isinstance(node, _pyast.Attribute):
        if isinstance(node.value, _pyast.Name) and node.value.id == "params":
            return _param(ctx, node.attr)
        field = _doc_field(node.value)
        if field is not None:
            return _doc_attribute(ctx, field, node.attr)
        raise QueryParsingError(f"unknown attribute [{node.attr}]")
    if isinstance(node, _pyast.Call):
        if not isinstance(node.func, _pyast.Name):
            raise QueryParsingError("only plain function calls allowed")
        fname = node.func.id
        if fname in ("cosineSimilarity", "dotProduct"):
            if len(node.args) != 2:
                raise QueryParsingError(f"{fname} expects (query_vector, 'field')")
            qv = _eval(node.args[0], ctx)
            fld = node.args[1]
            if not (isinstance(fld, _pyast.Constant) and isinstance(fld.value, str)):
                raise QueryParsingError(f"{fname} field must be a string literal")
            vecs, exists = ctx.get_vector_column(fld.value)
            q = jnp.asarray(qv, dtype=jnp.float32)
            if fname == "cosineSimilarity":
                qn = q / jnp.sqrt((q * q).sum() + 1e-12)
                # vecs rows are L2-normalized at reader build
                return jnp.where(exists, vecs @ qn, 0.0)
            return jnp.where(exists, vecs @ q, 0.0)
        fn = _FUNCS.get(fname)
        if fn is None:
            raise QueryParsingError(f"unknown script function [{fname}]")
        args = [_eval(a, ctx) for a in node.args]
        return fn(*args)
    raise QueryParsingError(
        f"script syntax not allowed: {type(node).__name__}")


def _doc_field(node: _pyast.AST) -> str | None:
    """The field name of a ``doc['f']`` node, None for any other node."""
    if isinstance(node, _pyast.Subscript) and \
            isinstance(node.value, _pyast.Name) and node.value.id == "doc" \
            and isinstance(node.slice, _pyast.Constant):
        return str(node.slice.value)
    return None


def _doc_attribute(ctx: ScriptContext, field: str, attr: str):
    """``doc[field].<attr>``: the numeric value (0 where a document has
    none) and ``empty``, or a geo_point's ``lat`` / ``lon``."""
    if attr in ("lat", "lon"):
        geo = ctx.get_geo_column(field) if ctx.get_geo_column else None
        if geo is None:
            raise ScriptError(
                f"field [{field}] is not a geo_point: no [{attr}]")
        lat, lon, exists = geo
        return jnp.where(exists, lat if attr == "lat" else lon, 0.0)
    if attr == "value":
        col, exists = ctx.get_numeric_column(field)
        return jnp.where(exists, col, 0.0)
    if attr == "empty":
        _, exists = ctx.get_numeric_column(field)
        return ~exists
    raise QueryParsingError(f"unknown attribute [{attr}]")


def _param(ctx: ScriptContext, key: str):
    if key not in ctx.params:
        raise IllegalArgumentError(f"missing script param [{key}]")
    return ctx.params[key]


_SCRIPT_CACHE: dict[str, CompiledScript] = {}


def compile_script(source: str) -> CompiledScript:
    """Compile+cache (reference: ScriptService compilation cache,
    core/script/ScriptService.java:269-310)."""
    cs = _SCRIPT_CACHE.get(source)
    if cs is None:
        cs = CompiledScript(source)
        _SCRIPT_CACHE[source] = cs
    return cs
