"""function_score evaluation over doc-values columns.

Reference: core/index/query/functionscore/* executed via
core/common/lucene/search/function/{FunctionScoreQuery,
FiltersFunctionScoreQuery, FieldValueFactorFunction, ScriptScoreFunction}
(BASELINE.md config 3). Each function maps a doc-values column to a per-doc
factor; score_mode combines multiple functions, boost_mode combines with the
query score — all dense elementwise ops fused into the scoring program.
"""

from __future__ import annotations

import jax.numpy as jnp

from elasticsearch_tpu.utils.hashing import murmur3_hash32


def field_value_factor(values, exists, factor: float = 1.0,
                       modifier: str = "none", missing: float | None = None):
    """FieldValueFactorFunction.java: modifier(factor * value). A
    document without a value takes ``missing``; with no ``missing`` it
    takes 0 (where the reference raises "Missing value for field")."""
    v = jnp.where(exists, values, missing if missing is not None else 0.0)
    v = v.astype(jnp.float32) * factor
    if modifier == "none":
        out = v
    elif modifier == "log":
        out = jnp.log10(v)
    elif modifier == "log1p":
        out = jnp.log10(v + 1.0)
    elif modifier == "log2p":
        out = jnp.log10(v + 2.0)
    elif modifier == "ln":
        out = jnp.log(v)
    elif modifier == "ln1p":
        out = jnp.log1p(v)
    elif modifier == "ln2p":
        out = jnp.log(v + 2.0)
    elif modifier == "square":
        out = v * v
    elif modifier == "sqrt":
        out = jnp.sqrt(v)
    elif modifier == "reciprocal":
        out = 1.0 / v
    else:
        raise ValueError(f"unknown field_value_factor modifier [{modifier}]")
    return out


def decay(values, exists, origin: float, scale: float, offset: float,
          decay_value: float, kind: str):
    """gauss/exp/linear decay (DecayFunctionParser.java). All args in the
    value's native units (numbers, millis for dates, meters for geo)."""
    dist = jnp.maximum(jnp.abs(values - origin) - offset, 0.0)
    if kind == "gauss":
        sigma2 = -(scale ** 2) / (2.0 * jnp.log(decay_value))
        out = jnp.exp(-(dist ** 2) / (2.0 * sigma2))
    elif kind == "exp":
        lam = jnp.log(decay_value) / scale
        out = jnp.exp(lam * dist)
    elif kind == "linear":
        s = scale / (1.0 - decay_value)
        out = jnp.maximum((s - dist) / s, 0.0)
    else:
        raise ValueError(f"unknown decay function [{kind}]")
    return jnp.where(exists, out.astype(jnp.float32), 1.0)


def random_score(n: int, seed: int, doc_base: int = 0):
    """RandomScoreFunction: deterministic per (seed, doc id) — uses the same
    murmur-style mixing idea, vectorized."""
    ids = jnp.arange(n, dtype=jnp.uint32) + jnp.uint32(doc_base)
    h = ids * jnp.uint32(0xCC9E2D51) + jnp.uint32(murmur3_hash32(str(seed)) & 0xFFFFFFFF)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    return (h.astype(jnp.float32) / jnp.float32(2**32))


def weight_factor(n: int, weight: float):
    return jnp.full(n, weight, dtype=jnp.float32)


def combine_functions(factors: list, masks: list, score_mode: str,
                      weights: list | None = None):
    """score_mode over per-function factors (function filters pre-applied
    as masks). A doc matched by NO function keeps the combined factor at
    1.0 in EVERY mode — FiltersFunctionScoreQuery.innerScore initializes
    factor = 1.0 and its per-mode guards (±inf for max/min, weightSum ==
    0 for sum/avg) leave it untouched when nothing matched. `weights`
    (per-function scalars, default 1) feed avg's weighted denominator
    (reference: weightSum accumulates WeightFactorFunction weights)."""
    if not factors:
        return None
    if score_mode == "first":
        # first MATCHING function wins (not the first listed one)
        out = jnp.ones_like(factors[0])
        chosen = jnp.zeros(factors[0].shape, bool)
        for f, m in zip(factors, masks):
            take = m & ~chosen
            out = jnp.where(take, f, out)
            chosen = chosen | m
        return out
    if score_mode == "multiply":
        out = None
        for f, m in zip(factors, masks):
            f = jnp.where(m, f, 1.0)
            out = f if out is None else out * f
        return out
    if score_mode in ("sum", "avg"):
        tot, wsum = None, None
        ws = weights if weights is not None else [1.0] * len(factors)
        for f, m, w in zip(factors, masks, ws):
            f = jnp.where(m, f, 0.0)
            c = jnp.where(m, w, 0.0).astype(jnp.float32)
            tot = f if tot is None else tot + f
            wsum = c if wsum is None else wsum + c
        out = tot if score_mode == "sum" else tot / jnp.maximum(wsum, 1e-9)
        return jnp.where(wsum > 0, out, 1.0)
    if score_mode in ("max", "min"):
        red = jnp.maximum if score_mode == "max" else jnp.minimum
        out, any_m = None, None
        for f, m in zip(factors, masks):
            fill = -jnp.inf if score_mode == "max" else jnp.inf
            f = jnp.where(m, f, fill)
            out = f if out is None else red(out, f)
            any_m = m if any_m is None else (any_m | m)
        # fall back to 1.0 only where NO function matched — a matched
        # function legitimately producing ±inf must keep it (the
        # reference's guard compares against the sentinel it seeded,
        # not against infiniteness of the result)
        return jnp.where(any_m, out, 1.0)
    raise ValueError(f"unknown score_mode [{score_mode}]")


def apply_boost_mode(query_scores, factor, boost_mode: str, max_boost: float = None):
    """boost_mode combines the query score with the function factor
    (FunctionScoreQuery.java)."""
    if max_boost is not None:
        factor = jnp.minimum(factor, max_boost)
    if boost_mode == "multiply":
        return query_scores * factor
    if boost_mode == "replace":
        return factor
    if boost_mode == "sum":
        return query_scores + factor
    if boost_mode == "avg":
        return (query_scores + factor) / 2.0
    if boost_mode == "max":
        return jnp.maximum(query_scores, factor)
    if boost_mode == "min":
        return jnp.minimum(query_scores, factor)
    raise ValueError(f"unknown boost_mode [{boost_mode}]")
