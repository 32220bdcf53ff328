"""Data kind ``geonames``: a GeoNames-``allCountries``-shaped places corpus
made from ``--seed``, Rally's ``geonames`` scoring operations over it, and
their plain reference and lower-precision control.

A place is one document of the dump's whole record, as Rally's track
indexes it: ``geonameid``, ``name``, ``asciiname``, ``alternatenames``,
``feature_class``, ``feature_code``, ``country_code``, ``cc2``,
``admin1_code`` .. ``admin4_code``, ``population``, ``elevation``, ``dem``,
``timezone`` and ``location`` (a geo_point, ``[lon, lat]``). Names are
tokens of a bounded Zipf vocabulary (``zipf_text``'s generator);
population is 0 for most places and a log-normal tail for the rest;
places gather round cluster centres, with a uniform background, in
degrees rounded to 5 decimals as the dump gives them, and a centre fixes
its places' country, first-order division and time zone. Each place's
``_source`` is its JSON, made in bulk (numpy over byte buffers).

The three operations are Rally's, each a ``function_score`` over
``match_all``, size 10 (:func:`request_body`):

* ``field_value_function_score`` — ``field_value_factor`` of
  ``population``, factor 1.2, modifier ``log2p``;
* ``decay_geo_gauss_function_score`` — ``gauss`` on ``location``, scale
  500km, offset 0km, decay 0.1, from an origin drawn per request from the
  populated places;
* ``expression`` — the lang-expression ``script_score``
  ``abs(ln(abs(doc['population']) + 1) + doc['location'].lon +
  doc['location'].lat) * _score``.

Only :func:`install` touches the program (and :func:`generate` asks it,
before any work, whether its packed loader takes the doc-values
columns). The reference (:class:`Reference`) works in float64 on the host
from columns made again from the seed, and takes nothing from the
program.
"""

from __future__ import annotations

import json

import numpy as np

from benchmarks.corpora import zipf_text as base
from benchmarks.corpora import zipf_text_positions as positions

term_name = base.term_name

FVF = "field_value_function_score"
GAUSS = "decay_geo_gauss_function_score"
EXPRESSION = "expression"
OPS = (FVF, GAUSS, EXPRESSION)

FACTOR, MODIFIER = 1.2, "log2p"
SCALE, OFFSET, DECAY = "500km", "0km", 0.1
SCALE_M, OFFSET_M = 500_000.0, 0.0
SCRIPT = ("abs(ln(abs(doc['population']) + 1) + doc['location'].lon "
          "+ doc['location'].lat) * _score")
#: the distance the program's geo decay computes and the reference
#: repeats: haversine on a sphere of the mean Earth radius (IUGG), metres
EARTH_RADIUS_M = 6371008.8

#: GeoNames feature classes and, for each, codes of its own (weights by
#: Zipf rank, the first the most common)
FEATURE_CODES = {
    "A": ["ADM4", "ADM3", "ADM2", "ADMD", "ADM1", "PCLI", "ADM5"],
    "H": ["STM", "LK", "RSV", "SPNG", "WLL", "BAY", "STMI", "PND", "CNL"],
    "L": ["LCTY", "AREA", "PRK", "RESV", "CMN", "GRAZ", "FLD"],
    "P": ["PPL", "PPLX", "PPLL", "PPLA4", "PPLA3", "PPLA2", "PPLQ", "PPLA",
          "PPLC"],
    "R": ["RD", "TRL", "RR", "RJCT", "ST"],
    "S": ["FRM", "SCH", "CH", "BLDG", "HTL", "CMTY", "RSTN", "HSP", "MUS",
          "AIRP"],
    "T": ["MT", "HLL", "PK", "VAL", "PASS", "CAPE", "ISL", "RDGE", "SLP"],
    "U": ["SMU", "RDGU", "CNYU", "HLLU"],
    "V": ["FRST", "GRSLD", "WD", "SCRB", "VIN"],
}
CLASSES = tuple(FEATURE_CODES)
#: time-zone areas (tz database), each zone named ``<area>/Tz<k>``
TZ_AREAS = ("Africa", "America", "Antarctica", "Asia", "Atlantic",
            "Australia", "Europe", "Indian", "Pacific")
#: the text fields whose values are codes of a small set: the program
#: analyzes each distinct value once (:func:`install`)
CODE_FIELDS = ("feature_class", "feature_code", "country_code", "cc2",
               "admin1_code", "admin2_code", "admin3_code", "admin4_code",
               "dem", "timezone")
#: every field of a document, in the order its JSON gives them
FIELDS = ("geonameid", "name", "asciiname", "alternatenames",
          "feature_class", "feature_code", "country_code", "cc2",
          "admin1_code", "admin2_code", "admin3_code", "admin4_code",
          "population", "elevation", "dem", "timezone", "location")


# ---------------------------------------------------------------------------
# byte buffers. A ragged column is (flat uint8 bytes, bytes a row); a block
# is (bytes [rows, width], bytes a row, right): a row's bytes are the
# block's first ones, or its last where ``right``
# ---------------------------------------------------------------------------

def _starts(lens: np.ndarray) -> np.ndarray:
    out = np.zeros(lens.shape[0] + 1, np.int64)
    np.cumsum(lens, out=out[1:])
    return out


def _take(flat: np.ndarray, starts: np.ndarray, lens: np.ndarray) -> tuple:
    """Row i ← ``flat[starts[i]:starts[i] + lens[i]]`` → a ragged column."""
    lens = np.asarray(lens, np.int64)
    off = _starts(lens)
    src = np.repeat(np.asarray(starts, np.int64) - off[:-1], lens) + \
        np.arange(off[-1], dtype=np.int64)
    return flat[src], lens


def _joined(blocks: list) -> tuple:
    """Row-wise concatenation of blocks → a ragged column."""
    mat = np.concatenate([b[0] for b in blocks], axis=1)
    keep = np.concatenate([
        (np.arange(m.shape[1])[None, :] >= m.shape[1] - ln[:, None])
        if right else (np.arange(m.shape[1])[None, :] < ln[:, None])
        for m, ln, right in blocks], axis=1)
    return mat[keep], sum(b[1] for b in blocks).astype(np.int64)


def _const(text: str, present: np.ndarray) -> tuple:
    """``text`` in each row where ``present``, nothing elsewhere."""
    b = np.frombuffer(text.encode(), np.uint8)
    return (np.broadcast_to(b, (present.shape[0], b.shape[0])),
            np.where(present, b.shape[0], 0), False)


def _ints(v: np.ndarray, present: np.ndarray) -> tuple:
    """Decimal digits of int64 ``v`` where ``present``, '-' before a
    negative value (a right-aligned block)."""
    v = np.asarray(v, np.int64)
    a = np.abs(v)
    width = len(str(int(a.max()))) + 1 if a.size else 2
    p10 = 10 ** np.arange(width - 1, dtype=np.int64)
    mat = np.empty((v.shape[0], width), np.uint8)
    mat[:, 1:] = 48 + (a[:, None] // p10[::-1][None, :]) % 10
    nd = 1 + (a[:, None] >= p10[None, 1:]).sum(axis=1) + (v < 0)
    # a negative value's '-' sits next to its first digit
    rows = np.nonzero(v < 0)[0]
    mat[rows, width - nd[rows]] = ord("-")
    return mat, np.where(present, nd, 0), True


def _fixed5(x: np.ndarray, present: np.ndarray) -> list:
    """``x`` with 5 decimals (``-12.34560``) where ``present`` (blocks)."""
    k = np.round(np.asarray(x, np.float64) * 1e5).astype(np.int64)
    a = np.abs(k)
    frac = (48 + (a[:, None] // (10 ** np.arange(4, -1, -1))[None, :])
            % 10).astype(np.uint8)
    return [_const("-", present & (k < 0)), _ints(a // 100_000, present),
            _const(".", present), (frac, np.where(present, 5, 0), False)]


def _table(table: list) -> tuple:
    """A value table as bytes ``[values + 1, width]`` and lengths; the
    last row is no value."""
    width = max([1] + [len(t) for t in table])
    tmat = np.zeros((len(table) + 1, width), np.uint8)
    tlen = np.zeros(len(table) + 1, np.int64)
    for r, t in enumerate(table):
        tmat[r, :len(t)] = np.frombuffer(t.encode(), np.uint8)
        tlen[r] = len(t)
    return tmat, tlen


def _strings(table: tuple, ids: np.ndarray) -> tuple:
    """The value of a :func:`_table` that ``ids`` names in each row,
    nothing where ``ids`` < 0 (a block)."""
    tmat, tlen = table
    row = np.where(ids >= 0, ids, tlen.shape[0] - 1)
    return tmat[row], tlen[row], False


def _ragged_block(flat: np.ndarray, starts: np.ndarray,
                  lens: np.ndarray) -> tuple:
    """Rows of a ragged column (``starts`` into ``flat``) as a block."""
    width = max(int(lens.max()) if lens.size else 1, 1)
    at = starts[:, None] + np.arange(width)[None, :]
    mat = flat[np.minimum(at, max(flat.shape[0] - 1, 0))] if flat.size \
        else np.zeros(at.shape, np.uint8)
    return mat, lens, False


def _token_bytes(tids: np.ndarray, seps: np.ndarray) -> np.ndarray:
    """Term names (``t%06d``, 7 bytes) each followed by its separator
    byte → ``[n, 8]`` uint8."""
    out = np.empty((tids.shape[0], 8), np.uint8)
    out[:, 0] = ord("t")
    for d in range(6):
        out[:, 6 - d] = 48 + (tids // 10 ** d) % 10
    out[:, 7] = seps
    return out


def _names(tokens: np.ndarray) -> tuple:
    """Position-indexed name tokens ``[n, L]`` (-1 past the length) →
    each row's name, its terms joined by spaces (a block)."""
    n, width = tokens.shape
    lens = (tokens >= 0).sum(axis=1).astype(np.int64)
    mat = _token_bytes(np.maximum(tokens, 0).reshape(-1),
                       np.full(n * width, 32, np.uint8)).reshape(n, -1)
    return mat, np.maximum(8 * lens - 1, 0), False


def _json_str(block, key: str, present: np.ndarray,
              first: bool = False) -> list:
    sep = "{" if first else ","
    return [_const(f'{sep}"{key}":"', present), block,
            _const('"', present)]


def decode(flat: np.ndarray, lens: np.ndarray, rows=None) -> list:
    """A ragged column's rows (``rows``, or all of them) as str."""
    off = _starts(lens)
    rows = np.arange(lens.shape[0]) if rows is None else rows
    text = flat.tobytes().decode("ascii")
    return [text[off[r]:off[r + 1]] for r in rows]


# ---------------------------------------------------------------------------
# generation: names on the device, places on the host (numpy, in bulk)
# ---------------------------------------------------------------------------

def require_packed_doc_values() -> None:
    """A program whose packed loader takes no numeric, geo or keyword
    column cannot hold 11.4M places: ``_bulk`` JSON of that many
    documents outlasts any set-up. Say so at once and leave (a parent
    commit from before the columns fails cleanly)."""
    import inspect
    from elasticsearch_tpu.index.segment import Segment
    params = inspect.signature(Segment.from_packed_text).parameters
    if not {"numeric", "geo", "keyword", "text"} <= set(params):
        raise SystemExit(
            "this program's Segment.from_packed_text takes no numeric= / "
            "geo= / keyword= / text= columns: the places cannot be loaded")


def _rng(seed: int, *stream: int):
    return np.random.default_rng([seed % 2 ** 64, *stream])


def _zipf(rng, n: int, k: int, s: float = 1.0) -> np.ndarray:
    """``n`` ranks in ``[0, k)``, P(rank r) ∝ (r + 1)^-s."""
    w = np.arange(1, k + 1, dtype=np.float64) ** -s
    return np.minimum(np.searchsorted(np.cumsum(w) / w.sum(),
                                      rng.random(n)), k - 1)


def code_tables(spec: dict) -> dict:
    """The value tables of the coded fields, the same on every seed:
    country codes (two letters), the time zones (``<area>/Tz<k>``) and
    the decimal codes of the divisions."""
    fixed = np.random.default_rng(0)
    letters = [a + b for a in "ABCDEFGHIJKLMNOPQRSTUVWXYZ"
               for b in "ABCDEFGHIJKLMNOPQRSTUVWXYZ"]
    countries = [letters[i] for i in fixed.permutation(len(letters))[
        :int(spec["countries"])]]
    zones = [f"{TZ_AREAS[i % len(TZ_AREAS)]}/Tz{i:03d}"
             for i in range(int(spec["time_zones"]))]
    return {"country": countries, "timezone": zones,
            "admin1": [f"{i:02d}" for i in range(1, 100)],
            "admin2": [str(i) for i in range(int(spec["admin2_codes"]))],
            "admin3": [str(10000 + i)
                       for i in range(int(spec["admin3_codes"]))],
            "admin4": [str(1000000 + i)
                       for i in range(int(spec["admin4_codes"]))],
            "feature_class": list(CLASSES),
            "feature_code": [c for k in CLASSES for c in FEATURE_CODES[k]]}


def cluster_centres(spec: dict, seed: int) -> dict:
    """The centres the clustered places gather round, from the seed:
    latitude uniform by area between ``lat_min`` and ``lat_max``,
    longitude uniform, a spread (degrees) log-uniform in ``spread``, a
    weight by Zipf rank (exponent ``cluster_s``), and the country, first
    division and time zone of its places."""
    rng = _rng(seed, 2)
    k = int(spec["clusters"])
    lo, hi = np.radians(spec["lat_min"]), np.radians(spec["lat_max"])
    lat = np.degrees(np.arcsin(rng.uniform(np.sin(lo), np.sin(hi), k)))
    lon = rng.uniform(-180.0, 180.0, k)
    spread = np.exp(rng.uniform(*np.log(spec["spread"]), k))
    w = np.arange(1, k + 1, dtype=np.float64) ** -float(spec["cluster_s"])
    country = _zipf(rng, k, int(spec["countries"]))
    return {"lat": lat, "lon": lon, "spread": spread, "p": w / w.sum(),
            "country": country,
            "admin1": rng.integers(0, 99, k),
            "timezone": (country * 7 + rng.integers(0, 3, k))
            % int(spec["time_zones"])}


def place_columns(spec: dict, seed: int, si: int, n_real: int,
                  centres: dict) -> dict:
    """One segment's places → ``population`` (int64), ``lat`` / ``lon``
    (float64 degrees, 5 decimals), ``exists`` (the real rows) and
    ``cluster`` (the centre a place gathers round, -1 for the uniform
    background); rows from ``n_real`` on are pad rows, of no value. The
    same seed and segment give the same columns."""
    rng = _rng(seed, 3, si)
    rows = int(spec["segment_rows"])
    c = rng.choice(len(centres["p"]), rows, p=centres["p"])
    sd = centres["spread"][c]
    lat = centres["lat"][c] + rng.standard_normal(rows) * sd
    lon = centres["lon"][c] + rng.standard_normal(rows) * sd / np.maximum(
        np.cos(np.radians(centres["lat"][c])), 0.2)
    bg = rng.random(rows) < float(spec["background_share"])
    lat[bg] = np.degrees(np.arcsin(rng.uniform(-1.0, 1.0, int(bg.sum()))))
    lon[bg] = rng.uniform(-180.0, 180.0, int(bg.sum()))
    lat = np.round(np.clip(lat, -90.0, 90.0), 5)
    lon = np.round((lon + 180.0) % 360.0 - 180.0, 5)
    populated = rng.random(rows) < float(spec["populated_share"])
    tail = np.floor(np.exp(np.log(spec["pop_median"]) + float(
        spec["pop_sigma"]) * rng.standard_normal(rows)))
    pop = np.where(populated, np.clip(tail, 1, spec["pop_max"]), 0).astype(
        np.int64)
    exists = np.arange(rows) < n_real
    return {"population": np.where(exists, pop, 0),
            "lat": np.where(exists, lat, 0.0),
            "lon": np.where(exists, lon, 0.0), "exists": exists,
            "cluster": np.where(bg, -1, c)}


def geonameids(spec: dict, si: int) -> np.ndarray:
    """The places' ``geonameid`` of segment ``si``: rising with the dump's
    row, with gaps (1 + 11 row // 10), the same on every seed."""
    g = si * int(spec["segment_rows"]) + np.arange(
        int(spec["segment_rows"]), dtype=np.int64)
    return 1 + g * 11 // 10


def place_attributes(spec: dict, seed: int, si: int, places: dict,
                     centres: dict, tables: dict) -> dict:
    """The rest of one segment's record, by row: value ids into
    :func:`code_tables` (-1 where the dump leaves the field empty),
    ``elevation`` (and whether it is given), ``dem`` (metres, -9999 at
    sea) and the alternate names (``alt_k`` names a row, ``alt_tokens``
    1-3 terms a name, ``alt_tids`` the terms in row order)."""
    rng = _rng(seed, 4, si)
    rows = int(spec["segment_rows"])
    real = places["exists"]
    c = places["cluster"]
    incl = c >= 0
    populated = places["population"] > 0
    n_countries = int(spec["countries"])
    country = np.where(incl, centres["country"][np.maximum(c, 0)],
                       _zipf(rng, rows, n_countries))
    admin1 = np.where(incl & (rng.random(rows) < 0.8),
                      centres["admin1"][np.maximum(c, 0)],
                      rng.integers(0, 99, rows))
    admin1 = np.where(rng.random(rows) < float(spec["admin1_share"]),
                      admin1, -1)
    tz = np.where(incl, centres["timezone"][np.maximum(c, 0)],
                  (country * 7) % int(spec["time_zones"]))
    cls_w = np.array([float(spec["class_shares"][k]) for k in CLASSES])
    cls = rng.choice(len(CLASSES), rows, p=cls_w / cls_w.sum())
    cls = np.where(populated, np.where(rng.random(rows) < 0.9,
                                       CLASSES.index("P"),
                                       CLASSES.index("A")), cls)
    first = np.cumsum([0] + [len(FEATURE_CODES[k]) for k in CLASSES])
    n_codes = np.diff(first)[cls]
    code = first[cls] + np.minimum(_zipf(rng, rows, 10),
                                   n_codes - 1)

    def coded(share: str, table: str) -> np.ndarray:
        ids = _zipf(rng, rows, len(tables[table]), 0.8)
        return np.where(rng.random(rows) < float(spec[share]), ids, -1)
    cc2 = np.where(rng.random(rows) < float(spec["cc2_share"]),
                   _zipf(rng, rows, n_countries), -1)
    elev_given = rng.random(rows) < float(spec["elevation_share"])
    elevation = np.round(np.abs(rng.standard_normal(rows)) * 900.0
                         ).astype(np.int64)
    dem = np.where(rng.random(rows) < float(spec["sea_share"]), -9999,
                   np.round(np.abs(rng.standard_normal(rows)) * 600.0)
                   ).astype(np.int64)
    # alternate names: more of them for a populated place
    has_alt = rng.random(rows) < np.where(
        populated, float(spec["alt_share_populated"]),
        float(spec["alt_share"]))
    k = np.floor(np.exp(np.log(spec["alt_names_median"]) + float(
        spec["alt_names_sigma"]) * rng.standard_normal(rows)))
    alt_k = np.where(has_alt & real, np.clip(k, 1, spec["alt_names_max"]),
                     0).astype(np.int64)
    n_alt = int(alt_k.sum())
    alt_tokens = 1 + _zipf(rng, n_alt, 3, 1.5)
    zc = base.zipf_constants(int(spec["vocab"]), float(spec["zipf_s"]))
    u = rng.random(int(alt_tokens.sum()))
    head = np.searchsorted(zc["head_cdf"].astype(np.float64), u,
                           side="right") + 1
    v = (u - zc["head_mass"]) / (1.0 - zc["head_mass"])
    tail = np.clip(np.round(np.power(np.maximum(
        zc["a"] + v * (zc["b"] - zc["a"]), 1e-300), zc["inv"])),
        base.HEAD + 1, int(spec["vocab"]) - 1)
    alt_tids = np.where(u < zc["head_mass"], head, tail).astype(np.int64)

    def real_only(ids):
        return np.where(real, ids, -1)
    return {"geonameid": geonameids(spec, si),
            "feature_class": real_only(cls), "feature_code": real_only(code),
            "country_code": real_only(country), "cc2": real_only(cc2),
            "admin1_code": real_only(admin1),
            "admin2_code": real_only(coded("admin2_share", "admin2")),
            "admin3_code": real_only(coded("admin3_share", "admin3")),
            "admin4_code": real_only(coded("admin4_share", "admin4")),
            "timezone": real_only(tz), "elevation": elevation,
            "elevation_given": elev_given & real, "dem": dem,
            "alt_k": alt_k, "alt_tokens": alt_tokens, "alt_tids": alt_tids}


def value_tables(tables: dict) -> dict:
    """Coded field → the table its value ids index."""
    return {"feature_class": tables["feature_class"],
            "feature_code": tables["feature_code"],
            "country_code": tables["country"], "cc2": tables["country"],
            "admin1_code": tables["admin1"], "admin2_code": tables["admin2"],
            "admin3_code": tables["admin3"], "admin4_code": tables["admin4"],
            "timezone": tables["timezone"]}


def _alternatenames(attrs: dict) -> tuple:
    """Each row's alternate names: terms joined by spaces within a name,
    names by commas."""
    k, nt, tids = attrs["alt_k"], attrs["alt_tokens"], attrs["alt_tids"]
    name_end = _starts(nt)[1:]                   # token index past a name
    seps = np.full(tids.shape[0], 32, np.uint8)
    seps[name_end - 1] = ord(",")
    flat = _token_bytes(tids, seps).reshape(-1)
    row_tokens = np.zeros(k.shape[0], np.int64)
    some = k > 0
    if some.any():
        # the rows' names lie in row order, so a row's tokens are a run
        row_tokens[some] = np.add.reduceat(nt, _starts(k)[:-1][some])
    first_tok = _starts(row_tokens)[:-1]
    return _take(flat, 8 * first_tok, np.maximum(8 * row_tokens - 1, 0))


#: alternate names up to this many bytes share one block; longer ones
#: (a few rows) are joined apart, so a block stays narrow
ALT_BLOCK = 96


def _source_rows(seg: dict, vt: dict, alt: tuple, idx: np.ndarray) -> tuple:
    """The ``_source`` JSON of rows ``idx`` → a ragged column."""
    def cut(key):
        return seg[key][idx]
    real = cut("exists")
    name = _names(cut("tokens"))
    alt_starts, alt_lens = alt[1][idx], alt[2][idx]
    has_alt = alt_lens > 0
    blocks = [_const('{"geonameid":', real), _ints(cut("geonameid"), real)]
    blocks += _json_str(name, "name", real)
    blocks += _json_str(name, "asciiname", real)
    blocks += _json_str(_ragged_block(alt[0], alt_starts, alt_lens),
                        "alternatenames", has_alt)
    for f in ("feature_class", "feature_code", "country_code", "cc2",
              "admin1_code", "admin2_code", "admin3_code", "admin4_code"):
        ids = cut(f)
        blocks += _json_str(_strings(vt[f], ids), f, ids >= 0)
    blocks += [_const(',"population":', real),
               _ints(cut("population"), real)]
    eg = cut("elevation_given")
    blocks += [_const(',"elevation":', eg), _ints(cut("elevation"), eg)]
    blocks += _json_str(_ints(cut("dem"), real), "dem", real)
    blocks += _json_str(_strings(vt["timezone"], cut("timezone")),
                        "timezone", real)
    blocks += [_const(',"location":[', real), *_fixed5(cut("lon"), real),
               _const(",", real), *_fixed5(cut("lat"), real),
               _const("]}", real)]
    return _joined(blocks)


def _source_chunk(seg: dict, vt: dict, alt: tuple, lo: int,
                  hi: int) -> tuple:
    """Rows ``[lo, hi)`` → (flat bytes, starts, ends): rows of short
    alternate names first, then the others."""
    rows = np.arange(lo, hi)
    short = alt[2][lo:hi] <= ALT_BLOCK
    parts = [_source_rows(seg, vt, alt, rows[m]) if m.any() else
             (np.zeros(0, np.uint8), np.zeros(0, np.int64))
             for m in (short, ~short)]
    flat = np.concatenate([parts[0][0], parts[1][0]])
    lens = np.empty(hi - lo, np.int64)
    lens[short], lens[~short] = parts[0][1], parts[1][1]
    starts = np.empty(hi - lo, np.int64)
    starts[short] = _starts(parts[0][1])[:-1]
    starts[~short] = parts[0][0].shape[0] + _starts(parts[1][1])[:-1]
    return flat, starts, starts + lens


def sources(seg: dict, tables: dict) -> tuple:
    """One segment's ``_source`` of every row, JSON → (bytes, starts,
    ends): row i's document is ``bytes[starts[i]:ends[i]]``, a pad row
    has none. A field the dump leaves empty is left out of the document.
    Made in chunks of rows, by a few threads."""
    import os
    from concurrent.futures import ThreadPoolExecutor
    vt = {f: _table(t) for f, t in value_tables(tables).items()}
    flat, lens = _alternatenames(seg)
    alt = (flat, _starts(lens)[:-1], lens)
    rows = seg["exists"].shape[0]
    step = 1 << 15
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        parts = list(pool.map(lambda lo: _source_chunk(
            seg, vt, alt, lo, min(lo + step, rows)), range(0, rows, step)))
    base = _starts(np.array([p[0].shape[0] for p in parts], np.int64))
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] + b for p, b in zip(parts, base)]),
            np.concatenate([p[2] + b for p, b in zip(parts, base)]))


def segment_docs(spec: dict, si: int) -> int:
    """Real rows of segment ``si``: full segments, the last one partly."""
    rows = int(spec["segment_rows"])
    return int(min(rows, max(0, int(spec["docs"]) - si * rows)))


def _name_spec(spec: dict) -> dict:
    return {"vocab": spec["vocab"], "zipf_s": spec["zipf_s"],
            "len_median": spec["name_len_median"],
            "len_sigma": spec["name_len_sigma"], "min_len": 1,
            "max_len": spec["name_max_len"]}


def generate(config: dict, seed: int, log=lambda m: None) -> dict:
    """The places of ``config["corpus"]`` from ``seed`` → per segment the
    ``name`` columns (``zipf_text``'s layout, with positions), the doc
    values, the coded fields' value ids and the ``_source`` of every
    row, on the host."""
    import time
    import jax
    require_packed_doc_values()
    spec = config["corpus"]
    rows, n_seg = int(spec["segment_rows"]), int(spec["segments"])
    if segment_docs(spec, n_seg - 1) <= 0 or \
            segment_docs(spec, n_seg) > 0:
        raise ValueError("docs do not fill the segments' last one")
    nspec = _name_spec(spec)
    names = base._segment_program(rows, nspec)
    tokens = positions._token_program(rows, nspec)
    centres = cluster_centres(spec, seed)
    tables = code_tables(spec)
    vocab = int(spec["vocab"])
    t_start = time.perf_counter()

    def host_part(si: int, seg: dict) -> dict:
        t1 = time.perf_counter()
        places = place_columns(spec, seed, si, seg["n"], centres)
        seg.update(places)
        seg.update(place_attributes(spec, seed, si, places, centres,
                                    tables))
        t2 = time.perf_counter()
        seg["source"] = sources(seg, tables)
        log(f"segment {si}: {seg['n']} places: doc values {t2 - t1:.2f} s, "
            f"sources {time.perf_counter() - t2:.2f} s "
            f"({seg['source'][0].nbytes / 2**20:.0f} MiB), done at "
            f"{time.perf_counter() - t_start:.2f} s")
        return seg

    # the names are made on the device, a segment at a time; the rest of
    # a segment's places on the host, by threads, while the next names
    # are made
    from concurrent.futures import ThreadPoolExecutor
    pending, df = [], np.zeros(vocab, np.int64)
    with ThreadPoolExecutor(3) as pool:
        for si in range(n_seg):
            n = segment_docs(spec, si)
            key = base.seed_key(seed, si)
            ut, tf, ln = (np.asarray(a) for a in jax.block_until_ready(
                names(key)))
            ut, tf = ut.reshape(rows, -1), tf.reshape(rows, -1)
            tk = np.asarray(tokens(key)).reshape(rows, -1)
            # the loader's rows are the names longest first (pad rows,
            # which hold no name, last): the order the packed segment
            # stores, so it moves no column
            order = np.concatenate([np.argsort(-ln[:n].astype(np.int64),
                                               kind="stable"),
                                    np.arange(n, rows)])
            ut, tf, ln, tk = (a[order] for a in (ut, tf, ln, tk))
            ut[n:], tf[n:], ln[n:], tk[n:] = -1, 0.0, 0, -1
            seg_df = np.bincount(ut[ut >= 0], minlength=vocab)
            df += seg_df
            pending.append(pool.submit(host_part, si, {
                "uterms": ut, "utf": tf, "doc_len": ln, "tokens": tk,
                "df": seg_df, "n": n}))
        segs = [f.result() for f in pending]
    return {"kind": "geonames", "segments": segs, "df": df,
            "n_docs": int(spec["docs"]), "rows": rows, "vocab": vocab,
            "spec": spec, "seed": seed, "tables": tables}


def stats(corpus: dict) -> dict:
    """What the roofline counts from: the places (documents)."""
    return {"places": int(corpus["n_docs"])}


# ---------------------------------------------------------------------------
# into the system under test (the only function that imports the program)
# ---------------------------------------------------------------------------

def mapping(config: dict) -> dict:
    return config["index"]


def coded_values(corpus: dict, seg: dict, field: str) -> tuple:
    """A coded field's (value table, value id a row, -1 for none) in one
    segment; ``dem``'s table is its metres as decimal strings."""
    if field == "dem":
        real = seg["exists"]
        uniq, inv = np.unique(seg["dem"][real], return_inverse=True)
        ids = np.full(real.shape[0], -1, np.int64)
        ids[real] = inv
        return [str(int(v)) for v in uniq], ids
    return value_tables(corpus["tables"])[field], seg[field]


def keyword_values(corpus: dict, seg: dict, field: str) -> tuple:
    """The values of ``<field>.raw`` in one segment → (sorted distinct
    values, value rank a row with -1 for none)."""
    rows = seg["exists"].shape[0]
    if field in ("name", "asciiname"):
        real = seg["exists"]
        tokens = seg["tokens"][real]
        # -1 pads a row's tail, so rows sort as their names do
        order = np.lexsort(tokens.T[::-1])
        ranked = tokens[order]
        new = np.ones(ranked.shape[0], bool)
        new[1:] = (ranked[1:] != ranked[:-1]).any(axis=1)
        ords = np.full(rows, -1, np.int64)
        ords[np.nonzero(real)[0][order]] = np.cumsum(new) - 1
        return decode(*_joined([_names(ranked[new])])), ords
    if field == "alternatenames":
        flat, lens = _alternatenames(seg)
        have = np.nonzero(lens > 0)[0]
        values = decode(flat, lens, have)
        vocab = sorted(set(values))
        rank = {v: i for i, v in enumerate(vocab)}
        ords = np.full(rows, -1, np.int64)
        ords[have] = [rank[v] for v in values]
        return vocab, ords
    table, ids = coded_values(corpus, seg, field)
    used = np.unique(ids[ids >= 0])
    order = sorted(range(len(used)), key=lambda j: table[used[j]])
    rank = np.full(max(len(table), 1), -1, np.int64)
    rank[used[order]] = np.arange(len(used))
    return [table[used[j]] for j in order], np.where(
        ids >= 0, rank[np.maximum(ids, 0)], -1)


def _analyzed_column(analyzer, table: list, ids: np.ndarray,
                     cache: dict) -> dict:
    """A text column of a coded field: each value of ``table`` that a row
    uses analyzed once (``cache``: value → tokens, kept across
    segments), by the program's own analyzer, and its terms gathered to
    the rows → the packed columns of ``Segment.from_packed_text``'s
    ``text``."""
    used = np.unique(ids[ids >= 0])
    for v in used:
        if table[v] not in cache:
            cache[table[v]] = analyzer.analyze(table[v])
    analyzed = {int(v): cache[table[v]] for v in used}
    terms = sorted({t.term for toks in analyzed.values() for t in toks})
    tid = {t: i for i, t in enumerate(terms)}
    nv = max(len(table), 1)
    width = max([1] + [t.position + 1 for toks in analyzed.values()
                       for t in toks])
    slots = max([1] + [len({t.term for t in toks})
                       for toks in analyzed.values()])
    v_tokens = np.full((nv + 1, width), -1, np.int32)
    v_uterms = np.full((nv + 1, slots), -1, np.int32)
    v_utf = np.zeros((nv + 1, slots), np.float32)
    v_len = np.zeros(nv + 1, np.int32)
    for v, toks in analyzed.items():
        counts: dict = {}
        for t in toks:
            if v_tokens[v, t.position] < 0:
                v_tokens[v, t.position] = tid[t.term]
            counts[tid[t.term]] = counts.get(tid[t.term], 0) + 1
        for u, (term, c) in enumerate(sorted(counts.items())):
            v_uterms[v, u], v_utf[v, u] = term, c
        v_len[v] = len(toks)
    row = np.where(ids >= 0, ids, nv)             # row nv: no value
    uterms = v_uterms[row]
    df = np.bincount(uterms[uterms >= 0], minlength=len(terms))
    return {"terms": terms, "tokens": v_tokens[row], "uterms": uterms,
            "utf": v_utf[row], "doc_len": v_len[row], "df": df}


def _segment_columns(corpus: dict, seg: dict, ms, name_cols: dict,
                     analyzed: dict) -> tuple:
    """One segment's packed ``text`` and ``keyword`` columns beside
    ``name``: ``asciiname`` (the same terms), the coded fields' text
    analyzed by the index's own analyzers, the ``.raw`` keyword doc
    values (``ignore_above`` as the mapping sets it)."""
    text = {"asciiname": name_cols}
    for f in CODE_FIELDS:
        text[f] = _analyzed_column(ms.field_mapper(f).analyzer,
                                   *coded_values(corpus, seg, f),
                                   analyzed.setdefault(f, {}))
    keyword, names_raw = {}, None
    for f in ("name",) + FIELDS[2:]:
        fm = ms.field_mapper(f"{f}.raw")
        if fm is None:
            continue
        if f in ("name", "asciiname"):
            # the same values: sorted once, one tuple for both columns
            if names_raw is None:
                vocab, ords = keyword_values(corpus, seg, f)
                names_raw = (tuple(vocab), ords)
            vocab, ords = names_raw
        else:
            vocab, ords = keyword_values(corpus, seg, f)
        limit = fm.params.get("ignore_above")
        if limit is not None:
            over = np.array([len(v) > int(limit) for v in vocab], bool)
            if over.any():
                keep = np.nonzero(~over)[0]
                rank = np.full(len(vocab) + 1, -1, np.int64)
                rank[keep] = np.arange(len(keep))
                vocab = [vocab[k] for k in keep]
                ords = rank[np.where(ords >= 0, ords, len(rank) - 1)]
        keyword[f"{f}.raw"] = (vocab, ords[:, None])
    return text, keyword


def install(corpus: dict, node, index: str, log=lambda m: None) -> None:
    """Every segment through ``Segment.from_packed_text``: ``name`` from
    the generator's columns, the other text and keyword columns of
    :func:`_segment_columns` (made for a few segments at once, by
    threads), ``geonameid`` / ``population`` / ``elevation``,
    ``location`` and the ``_source`` of every place."""
    import os
    import time
    from concurrent.futures import ThreadPoolExecutor
    from elasticsearch_tpu.index.segment import PackedSources, Segment
    svc = node.indices_service.indices[index]
    engine, ms = svc.engine(0), svc.mapper_service
    # one tuple: every segment's name and asciiname columns share it
    names = tuple(term_name(i) for i in range(corpus["vocab"]))
    rows = corpus["rows"]
    analyzed: dict = {}
    name_cols = [{"terms": names, "tokens": seg["tokens"],
                  "uterms": seg["uterms"], "utf": seg["utf"],
                  "doc_len": seg["doc_len"], "df": seg["df"]}
                 for seg in corpus["segments"]]
    t0 = time.perf_counter()
    with ThreadPoolExecutor(min(4, os.cpu_count() or 1)) as pool:
        columns = pool.map(
            lambda si: _segment_columns(corpus, corpus["segments"][si], ms,
                                        name_cols[si], analyzed),
            range(len(corpus["segments"])))
        for si, (seg, (text, keyword)) in enumerate(
                zip(corpus["segments"], columns)):
            base_id, t1, n = si * rows, time.perf_counter(), seg["n"]
            engine.install_segment(Segment.from_packed_text(
                0, "name", **name_cols[si], num_docs=n,
                ids=[str(base_id + i) for i in range(n)]
                + [""] * (rows - n),
                sources=PackedSources(*seg["source"]),
                numeric={
                    "population": (seg["population"].astype(np.float64),
                                   seg["exists"]),
                    "geonameid": (seg["geonameid"].astype(np.float64),
                                  seg["exists"]),
                    "elevation": (seg["elevation"].astype(np.float64),
                                  seg["elevation_given"])},
                geo={"location": (seg["lat"], seg["lon"], seg["exists"])},
                keyword=keyword, text=text), track_versions=False)
            log(f"install segment {si}: columns ready at {t1 - t0:.2f} s, "
                f"segment {time.perf_counter() - t1:.2f} s")


# ---------------------------------------------------------------------------
# queries: (operation, origin) — the origin is the gauss decay's alone
# ---------------------------------------------------------------------------

def op_multiset(weights: dict, n: int) -> list:
    """``n`` operations in the proportions of ``weights`` (operation →
    share), in :data:`OPS` order — the same multiset on every seed."""
    w = np.array([float(weights.get(op, 0.0)) for op in OPS])
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[np.argmax(w)] += n - counts.sum()
    return [op for op, c in zip(OPS, counts) for _ in range(c)]


def query_pool(corpus: dict, params: dict, rng, fixed) -> list:
    """``params["pool"]`` entries ``[operation, origin]``: which entry
    runs which operation is a permutation of the ``params["ops"]``
    multiset that the mix (``fixed``) gives, the same on every seed; each
    entry's origin is a populated place's coordinates drawn from the seed
    (``rng``), ``"lat, lon"`` with 5 decimals."""
    n = int(params["pool"])
    ops = [OPS[i] for i in fixed.permutation(
        [OPS.index(op) for op in op_multiset(params["ops"], n)])]
    lat = np.concatenate([s["lat"][s["population"] > 0]
                          for s in corpus["segments"]])
    lon = np.concatenate([s["lon"][s["population"] > 0]
                          for s in corpus["segments"]])
    pick = rng.choice(len(lat), n, replace=len(lat) < n)
    return [[op, f"{lat[i]:.5f}, {lon[i]:.5f}"]
            for op, i in zip(ops, pick)]


def request_body(entry: list, size: int) -> dict:
    op, origin = entry
    if op == FVF:
        fs = {"field_value_factor": {"field": "population",
                                     "factor": FACTOR,
                                     "modifier": MODIFIER}}
    elif op == GAUSS:
        fs = {"gauss": {"location": {"origin": origin, "scale": SCALE,
                                     "offset": OFFSET, "decay": DECAY}}}
    elif op == EXPRESSION:
        fs = {"functions": [{"script_score": {"script": {
            "lang": "expression", "inline": SCRIPT}}}]}
    else:
        raise ValueError(f"unknown operation [{op}]")
    return {"query": {"function_score": {"query": {"match_all": {}},
                                         **fs}}, "size": size}


def request(params: dict, queries: list, index: str) -> dict:
    """One REST request for ``queries`` → path, body, items. ``op`` is
    ``search`` (one query) or ``msearch`` (all of them in one request)."""
    size = int(params["size"])
    bodies = [request_body(q, size) for q in queries]
    if params["op"] == "search":
        return {"path": f"/{index}/_search", "items": 1,
                "body": json.dumps(bodies[0])}
    lines = []
    for b in bodies:
        lines += [json.dumps({"index": index}), json.dumps(b)]
    return {"path": "/_msearch", "items": len(bodies),
            "body": "\n".join(lines) + "\n"}


def warm_requests(params: dict, pool: list, index: str,
                  max_batch: int) -> list:
    """Requests that reach every program the stream can reach, ONE
    program a request. Single searches meet in the scheduler's queue of
    their operation (its key fingerprints the function list) and leave as
    one program per operation and power-of-two batch, up to the
    connections the stream keeps in flight (``params["in_flight"]``) or
    the scheduler's ``max_batch``; an ``_msearch`` of b bodies of one
    operation runs the same program of batch b. Then one plain search
    through the scheduler itself."""
    top = min(int(max_batch), int(params.get("in_flight", max_batch)))
    by_op: dict = {}
    for q in pool:
        by_op.setdefault(q[0], []).append(q)
    msearch = {**params, "op": "msearch"}
    out = []
    for op in OPS:
        qs = by_op.get(op)
        b = 1
        while qs and b <= top:
            out.append(request(msearch, [qs[j % len(qs)] for j in range(b)],
                               index))
            b *= 2
    return out + [request({**params, "op": "search"}, pool[:1], index)]


# ---------------------------------------------------------------------------
# the plain reference: float64, Elasticsearch's score functions
# ---------------------------------------------------------------------------

def parse_origin(origin: str) -> tuple:
    lat, lon = (float(x) for x in origin.split(","))
    return lat, lon


def haversine_m(lat, lon, olat: float, olon: float):
    """Great-circle distance (metres) from ``(olat, olon)``, haversine on
    a sphere of :data:`EARTH_RADIUS_M`, float64."""
    p1, p2 = np.radians(lat), np.radians(olat)
    a = np.sin(np.radians(lat - olat) / 2.0) ** 2 + np.cos(p1) * np.cos(
        p2) * np.sin(np.radians(lon - olon) / 2.0) ** 2
    return 2.0 * EARTH_RADIUS_M * np.arcsin(np.sqrt(np.clip(a, 0.0, 1.0)))


class RefScores(np.ndarray):
    """The reference's score of every document, carrying the operation
    it answers (``op``), the run's tally of sampled answers by operation
    (``tally``, shared by every answer of a run) and the reference
    itself (``ref``: the places' columns)."""
    op = None
    tally = None
    ref = None


class Reference:
    """Elasticsearch's function_score over ``match_all`` (query score 1,
    boost_mode multiply, one function):

        field_value_factor  log10(1.2 · population + 2)
        gauss               exp(-max(0, d - offset)² / (2σ²)),
                            σ² = scale² / (2 ln(1 / decay)),
                            d the haversine distance (R = 6,371,008.8 m)
        expression          |ln(|population| + 1) + lon + lat| · 1

    Every document matches. The columns are made again from the seed on
    the host; every float is float64."""

    def __init__(self, corpus: dict, queries: list, log=lambda m: None):
        import time
        t0 = time.perf_counter()
        spec, seed = corpus["spec"], corpus["seed"]
        centres = cluster_centres(spec, seed)
        cols = [place_columns(spec, seed, si, segment_docs(spec, si),
                              centres)
                for si in range(int(spec["segments"]))]
        n = [segment_docs(spec, si) for si in range(len(cols))]
        self.pop = np.concatenate([c["population"][:m] for c, m in
                                   zip(cols, n)]).astype(np.float64)
        self.lat = np.concatenate([c["lat"][:m] for c, m in zip(cols, n)])
        self.lon = np.concatenate([c["lon"][:m] for c, m in zip(cols, n)])
        self._fixed: dict = {}
        self.tally = {"answers": 0, "expected": len(queries),
                      "ops": {op: 0 for op in OPS}}
        log(f"reference: {len(self.pop)} places made again in "
            f"{time.perf_counter() - t0:.2f} s")

    def scores(self, query: list) -> np.ndarray:
        op, origin = query
        if op == GAUSS:
            olat, olon = parse_origin(origin)
            d = np.maximum(haversine_m(self.lat, self.lon, olat, olon)
                           - OFFSET_M, 0.0)
            sigma2 = SCALE_M ** 2 / (2.0 * np.log(1.0 / DECAY))
            s = np.exp(-d * d / (2.0 * sigma2))
        elif op in self._fixed:
            s = self._fixed[op]
        elif op == FVF:
            s = self._fixed[op] = np.log10(FACTOR * self.pop + 2.0)
        elif op == EXPRESSION:
            s = self._fixed[op] = np.abs(np.log(np.abs(self.pop) + 1.0)
                                         + self.lon + self.lat)
        else:
            raise ValueError(f"unknown operation [{op}]")
        out = s.view(RefScores)
        out.op, out.tally, out.ref = op, self.tally, self
        return out

    def source_wrong(self, doc: int, source) -> bool:
        """Whether a hit's ``_source`` is not place ``doc``'s: its
        ``geonameid``, ``population`` and ``location`` ``[lon, lat]``."""
        if not isinstance(source, dict):
            return True
        return (source.get("geonameid") != 1 + doc * 11 // 10
                or source.get("population") != int(self.pop[doc])
                or source.get("location") != [float(self.lon[doc]),
                                              float(self.lat[doc])])


def control_hits(ref: Reference, query: list, k: int):
    """The reference put in the program's place at the nearest precision
    below float32: the same formulas over the same columns with every
    float in bfloat16 → (ids, scores, total) as the program would
    answer."""
    import jax.numpy as jnp
    bf = jnp.bfloat16
    op, origin = query
    pop = jnp.asarray(ref.pop, jnp.float32).astype(bf)
    lat = jnp.asarray(ref.lat, jnp.float32).astype(bf)
    lon = jnp.asarray(ref.lon, jnp.float32).astype(bf)
    if op == FVF:
        s = jnp.log10(pop * bf(FACTOR) + bf(2.0))
    elif op == GAUSS:
        olat, olon = (bf(v) for v in parse_origin(origin))
        rad = bf(np.pi / 180.0)
        a = jnp.sin((lat - olat) * rad / bf(2)) ** 2 + jnp.cos(
            lat * rad) * jnp.cos(olat * rad) * jnp.sin(
            (lon - olon) * rad / bf(2)) ** 2
        d = bf(2.0 * EARTH_RADIUS_M) * jnp.arcsin(
            jnp.sqrt(jnp.clip(a, bf(0), bf(1))))
        sigma2 = bf(SCALE_M ** 2 / (2.0 * np.log(1.0 / DECAY)))
        s = jnp.exp(-(d * d) / (bf(2) * sigma2))
    else:
        s = jnp.abs(jnp.log(jnp.abs(pop) + bf(1)) + lon + lat)
    full = np.asarray(s.astype(jnp.float32)).astype(np.float64)
    kk = min(k, len(full))
    top = np.argpartition(-full, kk - 1)[:kk]
    top = top[np.lexsort((top, -full[top]))]
    return top, full[top], len(full)


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------

def parse_reply(reply: dict):
    """→ (ids, scores, total, sources): each hit's ``_id`` (the loader's
    row), score and ``_source``, and ``hits.total``."""
    hits = reply["hits"]["hits"]
    return (np.array([int(h["_id"]) for h in hits], np.int64),
            np.array([h["_score"] for h in hits], np.float64),
            int(reply["hits"]["total"]), [h.get("_source") for h in hits])


def compare(ref_scores: np.ndarray, params: dict, ids, scores,
            total, sources=None) -> dict:
    """One answer against the reference's scores of every document → the
    numbers compared (PERF.md, "How correct is decided"): ``score_gap``
    (relative), ``rank_gap``, ``order_wrong``, ``total_wrong`` (every
    place matches), ``hits_wrong``, ``source_wrong`` (hits whose
    ``_source`` is not their place's, where the answer has sources),
    ``ties_not_by_id`` (an observation), and ``ops_unsampled``, 1 on a
    run's last answer when an operation had no sampled answer: a sample
    that misses an operation cannot pass."""
    k = int(params["size"])
    n = len(ref_scores)
    want = min(k, n)
    out = {"score_gap": 0.0, "rank_gap": 0.0,
           "total_wrong": int(total != n),
           "hits_wrong": int(len(ids) != want or len(set(ids.tolist()))
                             != len(ids)),
           "order_wrong": 0, "ties_not_by_id": 0}
    op, tally = getattr(ref_scores, "op", None), \
        getattr(ref_scores, "tally", None)
    if tally is not None:
        tally["answers"] += 1
        tally["ops"][op] += 1
        out["ops_unsampled"] = int(tally["answers"] >= tally["expected"]
                                   and min(tally["ops"].values()) == 0)
    if len(ids) == 0 or want == 0:
        return out
    if ids.min() < 0 or ids.max() >= n:
        out["hits_wrong"] = 1
        return out
    ref = getattr(ref_scores, "ref", None)
    if sources is not None and ref is not None:
        out["source_wrong"] = sum(int(ref.source_wrong(int(d), src))
                                  for d, src in zip(ids, sources))
    # best first; among hits of one score Elasticsearch promises no order
    out["order_wrong"] = int((scores[:-1] < scores[1:]).sum())
    out["ties_not_by_id"] = int(((scores[:-1] == scores[1:])
                                 & (ids[:-1] > ids[1:])).sum())
    mine = np.asarray(ref_scores[ids], np.float64)
    out["score_gap"] = float(np.max(
        np.abs(scores - mine) / np.maximum(np.abs(mine), 1e-9)))
    kth = float(np.partition(np.asarray(ref_scores), -want)[-want])
    out["rank_gap"] = float(max(0.0, kth - float(mine.min()))
                            / max(abs(kth), 1e-9))
    return out
