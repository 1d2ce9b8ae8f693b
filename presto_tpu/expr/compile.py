"""Compile Expr trees to JAX functions over Pages.

Reference analog: sql/gen/PageFunctionCompiler.java:164
(compileProjection/compileFilter -> bytecode PageProjection/PageFilter).
The compiled artifact here is a closure ``page -> (data, valid)`` built
from jnp primitives; XLA fuses the whole tree (plus its consumers) into
one kernel, which is the TPU equivalent of the reference's generated
``evaluate`` loops.

SQL NULL semantics: every compiled node returns (data, valid). Scalar
functions are null-propagating; AND/OR implement three-valued logic
(false AND null = false). Filters select rows where data & valid.

String handling: VARCHAR columns are dictionary codes. String literals
resolve to codes at compile time against the column's Dictionary;
LIKE / IN / prefix predicates evaluate host-side once over the
dictionary into a boolean LUT, and the device does one gather —
reference analog of dictionary-aware processing
(operator/project/DictionaryAwarePageProjection.java).
"""

from __future__ import annotations

import fnmatch
import re
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.expr.ir import AggCall, Call, ColumnRef, Expr, Literal
from presto_tpu.page import Dictionary, Page
from presto_tpu.types import BIGINT as BIGINT_T
from presto_tpu.types import BOOLEAN, DOUBLE, MICROS_PER_DAY, Type

CompiledExpr = Callable[[Page], Tuple[jax.Array, jax.Array]]

# derived-dictionary cache: (id(inner), start, length) -> (inner, derived).
# Keeping the inner reference alive pins its id.
_DERIVED_DICTS: dict = {}


# fns whose result is a per-value string transform of a single string
# column: codes pass through, only the dictionary's values change
# (DictionaryAwarePageProjection analog). Transforms may return None
# (SQL NULL) — compile() folds a null-LUT into validity.
STRING_TRANSFORM_FNS = frozenset({
    "substr", "upper", "lower", "trim", "ltrim", "rtrim", "reverse",
    "char2hexint",
    "regexp_extract", "regexp_replace", "replace", "split_part",
    "lpad", "rpad", "concat", "json_extract", "json_extract_scalar",
    "url_extract_host", "url_extract_path", "url_extract_protocol",
    "url_extract_query", "translate", "normalize", "soundex",
    "url_encode", "url_decode", "json_format", "json_parse",
    "md5_hex", "sha1_hex", "sha256_hex",
})


_GEO_FNS = frozenset({
    "st_geometryfromtext", "st_point", "st_distance", "st_contains",
    "st_area", "st_x", "st_y",
})

_CONTAINER_FNS = frozenset({
    "array_construct", "subscript", "element_at", "cardinality",
    "jaccard_index", "intersection_cardinality", "hash_counts",
    "contains", "array_position", "array_min", "array_max", "array_sum",
    "array_average", "array_sort", "array_distinct", "map_keys",
    "map_values", "map", "map_construct",
    "array_transform", "array_filter", "any_match", "all_match",
    "none_match", "sequence", "slice", "repeat", "array_concat",
    "array_intersect", "array_union", "array_except", "arrays_overlap",
    "array_remove", "map_concat",
    "map_filter", "transform_keys", "transform_values", "zip_with",
    "reduce", "split",
})


# single-argument double -> double math (MathFunctions.java sweep)
_UNARY_DOUBLE_FNS = {
    "sqrt": jnp.sqrt, "cbrt": jnp.cbrt, "exp": jnp.exp, "ln": jnp.log,
    "log10": jnp.log10, "log2": jnp.log2,
    "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
    "asin": jnp.arcsin, "acos": jnp.arccos, "atan": jnp.arctan,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
    "degrees": jnp.degrees, "radians": jnp.radians,
    "is_nan": jnp.isnan, "is_finite": jnp.isfinite,
    "is_infinite": jnp.isinf,
}


# -- null-mask policy declarations ------------------------------------------
# Expression-level analogue of analysis/rules.NULL_MASK_POLICY: every
# scalar kernel family declares how its output validity mask relates to
# its inputs'.  analysis/kernel_soundness.py proves this table against an
# independent model (analysis/ranges.null_effect, derived from the
# abstract-transfer catalog); a kernel with no declaration — or one whose
# declaration disagrees with the model — fails EXPLAIN (TYPE VALIDATE)
# and the corpus gate.
#
#   strict      output NULL iff any input NULL (validity = AND of inputs)
#   preserving  validity is DERIVED, not intersected: 3VL short-circuits,
#               conditionals, and null tests can return non-NULL from
#               NULL inputs
#   generating  the kernel itself introduces NULLs beyond its inputs'
#               (overflow / zero-divisor / out-of-range-cast / parse
#               failure lanes go invalid at runtime)
NULL_POLICY = {}
for _f in (
    # comparisons and predicates over valid lanes
    "eq", "ne", "lt", "le", "gt", "ge", "not", "like", "in",
    "regexp_like", "starts_with", "ends_with", "contains",
    "arrays_overlap", "is_json_scalar", "st_contains",
    # arithmetic carried in float lanes (NaN, never wraps)
    "pow", "power", "atan2", "sqrt", "cbrt", "exp", "ln", "log10", "log2",
    "sin", "cos", "tan", "asin", "acos", "atan", "sinh", "cosh", "tanh",
    "degrees", "radians", "is_nan", "is_finite", "is_infinite",
    "sign", "ceil", "ceiling", "floor", "round", "truncate",
    # widening / representation-preserving casts
    "cast_real", "cast_decimal", "cast_char", "cast_varbinary",
    "cast_date", "cast_time", "cast_timestamp",
    # calendar moves and field extraction (every date has every field)
    "year", "month", "day", "quarter", "week", "year_of_week",
    "day_of_week", "day_of_year", "hour", "minute", "second",
    "millisecond", "date_add", "date_add_days", "date_add_months",
    "date_diff", "date_trunc", "date_format", "last_day_of_month",
    "ts_add_micros", "ts_add_months", "to_unixtime",
    # string transforms (total functions over their domain)
    "length", "lower", "upper", "trim", "ltrim", "rtrim", "substr",
    "concat", "replace", "reverse", "lpad", "rpad", "split",
    "regexp_replace", "translate", "normalize", "soundex", "codepoint",
    "levenshtein_distance", "hamming_distance", "jaccard_index",
    "char2hexint", "to_utf8", "url_encode", "json_format", "repeat",
    # digests and hashes
    "md5_hex", "sha1_hex", "sha256_hex", "crc32", "xxhash64",
    "hll_bucket", "hll_rho", "hash_counts", "classify", "regress",
    "intersection_cardinality",
    # containers: construction and total accessors
    "cardinality", "array_construct", "array_concat", "array_distinct",
    "array_union", "array_intersect", "array_except", "array_position",
    "array_remove", "array_sort", "array_filter", "array_transform",
    "any_match", "none_match", "all_match", "zip_with", "slice",
    "sequence", "map", "map_construct", "map_keys", "map_values",
    "map_filter", "transform_keys", "transform_values",
    "row_construct", "row_field", "retype_row", "split_to_map",
    # bitwise (wrap-free lane ops)
    "bitwise_and", "bitwise_or", "bitwise_xor", "bitwise_not",
    "bitwise_shift_left", "bitwise_shift_right", "bit_count",
    # strict-null variadics (any NULL argument nulls the row)
    "greatest", "least",
    # geometry
    "st_point", "st_x", "st_y", "st_area", "st_distance",
    "st_geometryfromtext",
    # TRY marker: runtime identity, mask passes through unchanged (the
    # child's own policy accounts for its trapped lanes)
    "try",
):
    NULL_POLICY[_f] = "strict"
for _f in (
    # 3VL short-circuits and conditionals derive their own validity
    "and", "or", "coalesce", "if", "case",
    # null tests always return a non-NULL boolean
    "is_null", "not_null",
    # and(ge, le) under the hood: FALSE can emerge from a NULL bound
    "between",
):
    NULL_POLICY[_f] = "preserving"
for _f in (
    # wrapped add/sub/mul/neg/abs lanes NULL at runtime (the reference
    # raises ARITHMETIC_OVERFLOW; see _ovf_add and friends)
    "add", "sub", "mul", "neg", "abs",
    # zero divisors NULL the lane (reference raises DIVISION_BY_ZERO)
    "div", "mod",
    # out-of-range narrowing NULLs (reference raises INVALID_CAST_ARGUMENT)
    "cast_smallint", "cast_tinyint",
    # varchar parse failures NULL (reference raises on bad input)
    "cast_bigint", "cast_double",
    "nullif",
    # out-of-bounds / missing-key access
    "subscript", "element_at",
    # partial parses and extractions
    "json_extract", "json_extract_scalar", "json_array_length",
    "json_size", "json_parse",
    "url_extract_host", "url_extract_path", "url_extract_port",
    "url_extract_protocol", "url_extract_query", "url_decode",
    "regexp_extract", "from_base", "date_parse", "from_iso8601_date",
    "split_part", "array_min", "array_max", "array_sum", "array_average",
    "reduce", "map_concat", "strpos", "width_bucket", "from_unixtime",
):
    NULL_POLICY[_f] = "generating"
del _f


# MySQL date_format/date_parse pattern -> python strftime/strptime
# (DateTimeFunctions.java's JodaTime DateTimeFormat table)
_MYSQL_FMT = {
    "Y": "%Y", "y": "%y", "m": "%m", "c": "%-m", "d": "%d", "e": "%-d",
    "j": "%j", "a": "%a", "W": "%A", "b": "%b", "M": "%B", "w": "%w",
    "H": "%H", "k": "%-H", "h": "%I", "I": "%I", "i": "%M", "s": "%S",
    "S": "%S", "f": "%f", "p": "%p", "T": "%H:%M:%S", "r": "%I:%M:%S %p",
    "%": "%%",
    # %-m / %-d / %-H (non-padded c/e/k) are glibc strftime extensions;
    # strptime ignores the flag, so parsing accepts both forms
}

#: format codes that need time-of-day (unsupported for DATE columns'
#: domain-dictionary path only when formatting, fine for parsing)
_MYSQL_TIME_CODES = frozenset("HkhIisSfpTr")


def _mysql_to_strftime(fmt: str, for_parse: bool = False) -> str:
    out, i = [], 0
    while i < len(fmt):
        ch = fmt[i]
        if ch == "%" and i + 1 < len(fmt):
            code = fmt[i + 1]
            got = _MYSQL_FMT.get(code)
            if got is None:
                raise ValueError(f"unsupported date format code %{code}")
            if for_parse:
                # strptime rejects the glibc no-pad flag but already
                # accepts non-padded numbers under the plain codes
                got = got.replace("%-", "%")
            out.append(got)
            i += 2
        else:
            out.append(ch.replace("%", "%%"))
            i += 1
    return "".join(out)


_INT_RX = re.compile(r"^[+-]?\d+$")
_FLOAT_RX = re.compile(r"^[+-]?(\d+\.?\d*|\.\d+)([eE][+-]?\d+)?$")
_I64_LO, _I64_HI = -(1 << 63), (1 << 63) - 1


def parse_number_strict(v, to_double: bool):
    """varchar -> number with the reference's accepted syntax only (no
    python extras like '1_0' or padding) and int64 range enforcement;
    None for anything else (shared by the bind-time literal fold and
    the column dictionary LUT so they cannot diverge)."""
    if not isinstance(v, str):
        return None
    if to_double:
        if _FLOAT_RX.match(v):
            return float(v)
        if v in ("Infinity", "-Infinity", "+Infinity", "NaN"):
            return float(v.replace("Infinity", "inf"))
        return None
    if not _INT_RX.match(v):
        return None
    n = int(v)
    return n if _I64_LO <= n <= _I64_HI else None


def mysql_datetime_micros(v: str, fmt: str):
    """date_parse's conversion, shared by the bind-time literal fold
    and the column LUT so they cannot diverge.  None on parse failure
    (deviation: the reference raises)."""
    import datetime as _dt

    try:
        ts = _dt.datetime.strptime(v, _mysql_to_strftime(fmt, for_parse=True))
    except ValueError:
        return None
    delta = ts - _dt.datetime(1970, 1, 1)
    return ((delta.days * 86400 + delta.seconds) * 1_000_000
            + delta.microseconds)  # exact, no float round-trip


def iso_date_days(v: str):
    """from_iso8601_date's epoch-day conversion (shared fold/LUT)."""
    import datetime as _dt

    try:
        return _dt.date.fromisoformat(v).toordinal() - 719163
    except ValueError:
        return None


def xxh64_signed(data: bytes) -> int:
    """xxhash64 wrapped into BIGINT's signed range (shared fold/LUT)."""
    h = _xxh64(data)
    return h - (1 << 64) if h >= (1 << 63) else h


def _subst_lambda_vars(e, slot_to_index: dict):
    """Replace THIS lambda's slot-numbered variables with ColumnRefs
    into the lambda-evaluation page's appended virtual channels.  Slots
    are binder-unique, so descending through nested LambdaExprs only
    rewrites captures of the outer variables — the inner lambda's own
    parameters (different slots) are left for its compile site."""
    from presto_tpu.expr.ir import (
        ColumnRef as _Ref, LambdaExpr as _LE, LambdaVar as _LV,
    )

    if isinstance(e, _LV):
        if e.slot not in slot_to_index:
            return e  # an inner lambda's own parameter
        return _Ref(type=e.type, index=slot_to_index[e.slot], name=f"λ{e.slot}")
    if isinstance(e, _LE):
        return _LE(type=e.type, params=e.params,
                   body=_subst_lambda_vars(e.body, slot_to_index))
    if isinstance(e, Call):
        return Call(type=e.type, fn=e.fn,
                    args=tuple(_subst_lambda_vars(a, slot_to_index)
                               for a in e.args))
    return e


def _levenshtein(a: str, b: str) -> int:
    """Classic DP edit distance (StringFunctions.java#levenshteinDistance)."""
    if len(a) < len(b):
        a, b = b, a
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i]
        for j, cb in enumerate(b, 1):
            cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                           prev[j - 1] + (ca != cb)))
        prev = cur
    return prev[-1]


def _xxh64(data: bytes, seed: int = 0) -> int:
    """xxHash64 (public spec, xxhash.com) — host-side over dictionary
    values, one device gather for the column form."""
    P1, P2, P3 = 0x9E3779B185EBCA87, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9
    P4, P5 = 0x85EBCA77C2B2AE63, 0x27D4EB2F165667C5
    M = (1 << 64) - 1

    def rotl(x, r):
        return ((x << r) | (x >> (64 - r))) & M

    n = len(data)
    i = 0
    if n >= 32:
        v1 = (seed + P1 + P2) & M
        v2 = (seed + P2) & M
        v3 = seed & M
        v4 = (seed - P1) & M
        while i + 32 <= n:
            for k, v in enumerate((v1, v2, v3, v4)):
                lane = int.from_bytes(data[i + 8 * k:i + 8 * k + 8], "little")
                v = (v + lane * P2) & M
                v = (rotl(v, 31) * P1) & M
                if k == 0:
                    v1 = v
                elif k == 1:
                    v2 = v
                elif k == 2:
                    v3 = v
                else:
                    v4 = v
            i += 32
        h = (rotl(v1, 1) + rotl(v2, 7) + rotl(v3, 12) + rotl(v4, 18)) & M
        for v in (v1, v2, v3, v4):
            v = (rotl((v * P2) & M, 31) * P1) & M
            h = ((h ^ v) * P1 + P4) & M
    else:
        h = (seed + P5) & M
    h = (h + n) & M
    while i + 8 <= n:
        lane = int.from_bytes(data[i:i + 8], "little")
        h ^= (rotl((lane * P2) & M, 31) * P1) & M
        h = (rotl(h, 27) * P1 + P4) & M
        i += 8
    if i + 4 <= n:
        h ^= (int.from_bytes(data[i:i + 4], "little") * P1) & M
        h = (rotl(h, 23) * P2 + P3) & M
        i += 4
    while i < n:
        h ^= (data[i] * P5) & M
        h = (rotl(h, 11) * P1) & M
        i += 1
    h ^= h >> 33
    h = (h * P2) & M
    h ^= h >> 29
    h = (h * P3) & M
    h ^= h >> 32
    return h


def _json_path_lookup(doc: str, path: str):
    """Tiny JSONPath subset: $, .name, [idx] (reference:
    operator/scalar/JsonExtract.java's path engine).
    Returns (found, value) so a JSON null VALUE is distinguishable
    from a missing path."""
    import json as _json

    try:
        cur = _json.loads(doc)
    except Exception:
        return False, None
    if not path.startswith("$"):
        return False, None
    i = 1
    toks = re.findall(r"\.([A-Za-z_][A-Za-z0-9_]*)|\[(\d+)\]", path[i:])
    consumed = sum(len(f".{a}") if a else len(f"[{b}]") for a, b in toks)
    if consumed != len(path) - 1:
        return False, None
    for name, idx in toks:
        if name:
            if not isinstance(cur, dict) or name not in cur:
                return False, None
            cur = cur[name]
        else:
            j = int(idx)
            if not isinstance(cur, list) or j >= len(cur):
                return False, None
            cur = cur[j]
    return True, cur


def _json_path_get(doc: str, path: str):
    found, cur = _json_path_lookup(doc, path)
    if not found:
        return None
    return cur


def _string_transform(e: "Call"):
    """value -> Optional[value] host transform for STRING_TRANSFORM_FNS,
    plus a hashable cache key; None if ``e`` is not such a call."""
    fn = e.fn
    lits = tuple(a.value for a in e.args if isinstance(a, Literal))
    key = (fn,) + lits

    if fn == "substr":
        start = e.args[1].value
        length = e.args[2].value if len(e.args) > 2 else None
        end = None if length is None else start - 1 + length
        return lambda v: v[start - 1 : end], key
    if fn in ("upper", "lower", "trim", "ltrim", "rtrim", "reverse"):
        f = {"upper": str.upper, "lower": str.lower, "trim": str.strip,
             "ltrim": str.lstrip, "rtrim": str.rstrip,
             "reverse": lambda s: s[::-1]}[fn]
        return f, key
    if fn == "char2hexint":
        # teradata: utf-16be code units as uppercase hex
        return lambda v: "".join(f"{ord(ch):04X}" for ch in v), key
    if fn == "regexp_extract":
        rx = re.compile(e.args[1].value)
        group = int(e.args[2].value) if len(e.args) > 2 else 0

        def f(v, rx=rx, g=group):
            m = rx.search(v)
            return m.group(g) if m else None

        return f, key
    if fn == "regexp_replace":
        rx = re.compile(e.args[1].value)
        repl = e.args[2].value if len(e.args) > 2 else ""
        # $N -> \g<N> (plain \N would make $0 a NUL octal escape)
        py_repl = re.sub(r"\$(\d+)", r"\\g<\1>", repl)
        return lambda v: rx.sub(py_repl, v), key
    if fn == "replace":
        frm = e.args[1].value
        to = e.args[2].value if len(e.args) > 2 else ""
        return lambda v: v.replace(frm, to), key
    if fn == "translate":
        # chars of `from` map positionally to `to`; unpaired chars drop
        # (StringFunctions.java#translate)
        frm = e.args[1].value
        to = e.args[2].value
        table: dict = {}
        for i, f in enumerate(frm):
            # first occurrence of a duplicated `from` char wins
            table.setdefault(ord(f), to[i] if i < len(to) else None)
        return lambda v: v.translate(table), key
    if fn == "normalize":
        form = e.args[1].value if len(e.args) > 1 else "NFC"
        import unicodedata

        return lambda v: unicodedata.normalize(form, v), key
    if fn == "url_encode":
        # application/x-www-form-urlencoded (the reference's
        # URLEncoder): space -> '+', '*' '-' '.' '_' stay bare
        from urllib.parse import quote_plus

        # quote_plus hard-codes '~' as safe; URLEncoder encodes it
        return lambda v: quote_plus(v, safe="*-._").replace("~", "%7E"), key
    if fn == "url_decode":
        from urllib.parse import unquote_plus

        return lambda v: unquote_plus(v), key
    if fn in ("json_format", "json_parse"):
        # both normalize JSON text (the engine's JSON values are
        # varchar); invalid input -> NULL (deviation: json_parse raises
        # in the reference)
        import json as _json

        def jf(v):
            try:
                return _json.dumps(_json.loads(v), separators=(",", ":"))
            except Exception:
                return None

        return jf, key
    if fn in ("md5_hex", "sha1_hex", "sha256_hex"):
        import hashlib

        algo = fn[:-4]

        def hx(v, algo=algo):
            # reference to_hex (BaseEncoding.base16) is UPPERCASE
            return hashlib.new(algo, v.encode()).hexdigest().upper()

        return hx, key
    if fn == "soundex":
        # classic American Soundex (StringFunctions.java#soundex)
        codes = {}
        for group, digit in (("BFPV", "1"), ("CGJKQSXZ", "2"),
                             ("DT", "3"), ("L", "4"), ("MN", "5"),
                             ("R", "6")):
            for ch in group:
                codes[ch] = digit

        def sdx(v, codes=codes):
            s = [c for c in v.upper() if c.isalpha()]
            if not s:
                return None
            out = s[0]
            prev = codes.get(s[0], "")
            for c in s[1:]:
                d = codes.get(c, "")
                if d and d != prev:
                    out += d
                if c not in "HW":
                    prev = d
            return (out + "000")[:4]

        return sdx, key
    if fn == "split_part":
        delim, n = e.args[1].value, int(e.args[2].value)

        def f(v, delim=delim, n=n):
            parts = v.split(delim)
            return parts[n - 1] if 0 < n <= len(parts) else None

        return f, key
    if fn in ("lpad", "rpad"):
        n = int(e.args[1].value)
        pad = e.args[2].value if len(e.args) > 2 else " "
        if fn == "lpad":
            def f(v, n=n, pad=pad):
                if len(v) >= n:
                    return v[:n]
                fill = (pad * n)[: n - len(v)]
                return fill + v
        else:
            def f(v, n=n, pad=pad):
                if len(v) >= n:
                    return v[:n]
                return v + (pad * n)[: n - len(v)]
        return f, key
    if fn == "concat":
        # one string column + literals in any positions
        parts = []
        for a in e.args:
            parts.append(a.value if isinstance(a, Literal) else None)
        if parts.count(None) != 1:
            return None

        def f(v, parts=tuple(parts)):
            return "".join(v if p is None else str(p) for p in parts)

        return f, key + ("@" + str(parts.index(None)),)
    if fn in ("json_extract", "json_extract_scalar"):
        path = e.args[1].value
        scalar = fn == "json_extract_scalar"

        def f(v, path=path, scalar=scalar):
            import json as _json

            got = _json_path_get(v, path)
            if got is None:
                return None
            if scalar:
                if isinstance(got, (dict, list)):
                    return None
                if isinstance(got, bool):
                    return "true" if got else "false"
                return str(got)
            return _json.dumps(got, separators=(",", ":"))

        return f, key
    if fn.startswith("url_extract_"):
        from urllib.parse import urlparse

        part = fn[len("url_extract_"):]

        def f(v, part=part):
            try:
                u = urlparse(v)
            except Exception:
                return None
            got = {"host": u.hostname, "path": u.path, "protocol": u.scheme,
                   "query": u.query}[part]
            return got if got else (got if part == "path" else None)

        return f, key
    return None


def literal_array_dictionary(values) -> Dictionary:
    """Shared dictionary for an all-literal string array
    (ARRAY['a','b']): codes are positions in the sorted distinct
    values.  Cached by content so binder, compiler, and channel
    provenance all resolve to the SAME identity-hashed Dictionary."""
    key = ("$litarr", tuple(values))
    if key not in _DERIVED_DICTS:
        _DERIVED_DICTS[key] = (None, Dictionary(sorted(set(values))), [False])
    return _DERIVED_DICTS[key][1]


def expr_dictionary(e: Expr, dictionaries: Sequence[Optional[Dictionary]]) -> Optional[Dictionary]:
    """Dictionary provenance of a string-typed expression: bare columns
    keep theirs; string-transform calls derive a transformed dictionary
    host-side (codes unchanged — only the code->value mapping
    transforms; None results become "" with validity handled by the
    compiler's null LUT)."""
    if isinstance(e, ColumnRef):
        return dictionaries[e.index]
    if isinstance(e, Literal) and e.value is not None:
        # projected string constant ('store' AS channel): a singleton
        # dictionary whose only code is the literal (cached so repeated
        # plans share the identity-hashed Dictionary)
        key = ("$lit", e.value)
        if key not in _DERIVED_DICTS:
            _DERIVED_DICTS[key] = (None, Dictionary([e.value]), [False])
        return _DERIVED_DICTS[key][1]
    if isinstance(e, Call) and e.fn == "cast_char":
        # metadata-only re-type: same codes, same dictionary
        return expr_dictionary(e.args[0], dictionaries)
    if isinstance(e, Call) and e.fn == "split":
        inner = expr_dictionary(e.args[0], dictionaries)
        delim = e.args[1]
        if inner is None or not isinstance(delim, Literal) \
                or delim.value is None:
            return None
        pd, _ = ExprCompiler.split_parts(inner, delim.value,
                                         e.type.max_elems)
        return pd
    if isinstance(e, Call) and e.fn in ("subscript", "element_at") \
            and e.args[0].type.is_array \
            and e.args[0].type.element is not None \
            and e.args[0].type.element.is_string:
        # an element of a dictionary-coded string array keeps the
        # array's element dictionary
        return expr_dictionary(e.args[0], dictionaries)
    if isinstance(e, Call) and e.fn == "array_construct" \
            and e.type.is_array and e.type.element is not None \
            and e.type.element.is_string \
            and all(isinstance(a, Literal) for a in e.args):
        # ARRAY['a','b']: the elements code into one derived dictionary
        return literal_array_dictionary(
            [a.value for a in e.args if a.value is not None])
    if isinstance(e, Call) and e.fn == "date_format":
        fmt = e.args[1]
        if isinstance(fmt, Literal) and fmt.value is not None:
            return ExprCompiler.date_format_dictionary(fmt.value)
        return None
    if isinstance(e, Call) and e.fn in ("case", "if", "coalesce"):
        return merged_string_dictionary(e, dictionaries)
    if isinstance(e, Call) and e.fn in STRING_TRANSFORM_FNS:
        col = _transform_column(e)
        if col is None:
            return None
        inner = expr_dictionary(col, dictionaries)
        if inner is None:
            return None
        tf = _string_transform(e)
        if tf is None:
            return None
        f, tkey = tf
        key = (id(inner),) + tkey
        if key not in _DERIVED_DICTS:
            values = [f(v) for v in inner.values]
            nulls = [v is None for v in values]
            d = Dictionary(["" if v is None else v for v in values])
            _DERIVED_DICTS[key] = (inner, d, nulls)
        return _DERIVED_DICTS[key][1]
    return None


def _string_case_branches(e: "Call") -> Sequence[Expr]:
    """Value-producing operands of a case/if/coalesce expression."""
    if e.fn == "case":
        return list(e.args[1::2]) + [e.args[-1]]
    if e.fn == "if":
        return [e.args[1], e.args[2]]
    return list(e.args)  # coalesce


def merged_string_dictionary(e: "Call", dictionaries) -> Optional[Dictionary]:
    """Union dictionary for a string-valued case/if/coalesce: every
    branch is either a literal or an expression with a known dictionary;
    branch codes remap into the union at compile time (the compiler's
    _compile_string_case must build the SAME dictionary — cached by
    branch identity so both see one object)."""
    parts = []
    key_parts = []
    for b in _string_case_branches(e):
        if isinstance(b, Literal):
            parts.append(("lit", b.value))
            key_parts.append(("L", b.value))
        else:
            d = expr_dictionary(b, dictionaries)
            if d is None:
                return None
            parts.append(("dict", d))
            key_parts.append(("D", id(d)))
    key = ("$case",) + tuple(key_parts)
    if key not in _DERIVED_DICTS:
        values: list = []
        seen: dict = {}
        for kind, v in parts:
            vals = [v] if kind == "lit" else v.values
            for val in vals:
                if val is not None and val not in seen:
                    seen[val] = len(values)
                    values.append(val)
        d = Dictionary(values if values else [""])
        # pin the branch dictionaries in the value tuple: the key uses
        # their id()s, and a GC'd-then-reallocated Dictionary must not
        # hit a stale entry (same contract as the transform-dict cache)
        pins = tuple(v for kind, v in parts if kind == "dict")
        _DERIVED_DICTS[key] = (pins, d, [False] * len(d.values))
    return _DERIVED_DICTS[key][1]


def _transform_column(e: "Call") -> Optional[Expr]:
    """The single string-typed non-literal argument of a transform."""
    cols = [a for a in e.args if not isinstance(a, Literal)]
    if len(cols) != 1:
        return None
    return cols[0]


def _transform_null_lut(e: "Call", dictionaries) -> Optional["jnp.ndarray"]:
    """Per-code validity for a derived dictionary (False where the
    transform yielded NULL); None when no entry is null."""
    col = _transform_column(e)
    inner = expr_dictionary(col, dictionaries)
    tf = _string_transform(e)
    if inner is None or tf is None:
        return None
    _, tkey = tf
    key = (id(inner),) + tkey
    entry = _DERIVED_DICTS.get(key)
    if entry is None or not any(entry[2]):
        return None
    return jnp.asarray([not n for n in entry[2]])


def _hll_from_hash(h: jax.Array, fn: str, P: int = None) -> jax.Array:
    """Shared HLL tail over a mixed uint64 hash lane: bucket = top P
    bits; rho = leading-zero count of the remainder + 1 (sentinel bit
    caps it)."""
    if P is None:
        P = ExprCompiler.HLL_P
    if fn == "hll_bucket":
        return (h >> jnp.uint64(64 - P)).astype(jnp.int64)
    rest = (h << jnp.uint64(P)) | jnp.uint64(1 << (P - 1))
    clz = jnp.zeros(h.shape, dtype=jnp.uint64)
    x = rest
    for shift in (32, 16, 8, 4, 2, 1):
        empty = x < (jnp.uint64(1) << jnp.uint64(64 - shift))
        clz = clz + jnp.where(empty, jnp.uint64(shift), jnp.uint64(0))
        x = jnp.where(empty, x << jnp.uint64(shift), x)
    return (clz + jnp.uint64(1)).astype(jnp.int64)


def _mix_u64(x: jax.Array) -> jax.Array:
    """splitmix64 finalizer over uint64 lanes (device hash)."""
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def _rescale(data: jax.Array, from_scale: int, to_scale: int) -> jax.Array:
    if to_scale > from_scale:
        return data * (10 ** (to_scale - from_scale))
    if to_scale < from_scale:
        return data // (10 ** (from_scale - to_scale))
    return data


def _to_double(data: jax.Array, t: Type) -> jax.Array:
    if t.is_long_decimal:
        from presto_tpu.ops import decimal128 as d128

        return d128.to_double(data, t.scale)
    if t.is_decimal:
        return data.astype(jnp.float64) / (10.0 ** t.scale)
    return data.astype(jnp.float64)


def _to_long_limbs(data: jax.Array, t: Type, from_scale: int, to_scale: int,
                   limbs: int = 2) -> jax.Array:
    """Coerce a short/long decimal (or integer) column to long-decimal
    limbs at the target scale (``limbs`` = 5 for decimal(37..38))."""
    from presto_tpu.ops import decimal128 as d128

    if t.is_long_decimal:
        cur = data
        if limbs == 5 and data.shape[-1] == 2:
            cur = d128.widen(cur)
        return d128.rescale(cur, from_scale, to_scale)
    return d128.rescale(d128.from_int64(data.astype(jnp.int64), limbs=limbs),
                        from_scale, to_scale)


def _decimal_limbs(*types) -> int:
    """Limb width covering every decimal operand (5 once any p > 36)."""
    return 5 if any(t.is_decimal and (t.precision or 0) > 36
                    for t in types) else 2


def _where_rows(cond: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """Row-mask select that broadcasts over per-value trailing dims
    (long-decimal limbs)."""
    if a.ndim > cond.ndim:
        cond = cond.reshape(cond.shape + (1,) * (a.ndim - cond.ndim))
    return jnp.where(cond, a, b)


def _trunc_div(a: jax.Array, b: jax.Array) -> jax.Array:
    """SQL integer division truncates toward zero (Presto semantics),
    unlike Python/jnp floor division."""
    bs = jnp.where(b == 0, 1, b)
    q = jnp.abs(a) // jnp.abs(bs)
    return jnp.where((a < 0) ^ (bs < 0), -q, q)


# -- two's-complement overflow detection ------------------------------------
# jnp integer ops wrap like C; the reference's checked bytecode raises
# ARITHMETIC_OVERFLOW instead (operator/scalar/MathFunctions.java uses
# Math.addExact and friends).  Jitted kernels can't raise, so wrapped
# lanes are detected post-hoc and NULLed — the same documented-deviation
# family as division-by-zero -> NULL.  The static analyzer
# (analysis/kernel_soundness.py) reports where these guards can fire.

def _ovf_add(a: jax.Array, b: jax.Array, r: jax.Array) -> jax.Array:
    """r = a + b wrapped iff operands share a sign the result lost."""
    return ((a ^ r) & (b ^ r)) < 0


def _ovf_sub(a: jax.Array, b: jax.Array, r: jax.Array) -> jax.Array:
    """r = a - b wrapped iff operands differ in sign and r flipped."""
    return ((a ^ b) & (a ^ r)) < 0


def _ovf_mul(a: jax.Array, b: jax.Array, r: jax.Array) -> jax.Array:
    """r = a * b wrapped iff floor-dividing the result back misses b or
    leaves a remainder (any nonzero deviation is a multiple of 2^width,
    far above |a|).  The -1 * INT_MIN corner is pinned separately: there
    the check division itself wraps and reports exact."""
    imin = jnp.iinfo(r.dtype).min
    den = jnp.where(a == 0, 1, a)
    q = r // den
    exact = (r - q * den == 0) & (q == b)
    return ((a != 0) & jnp.logical_not(exact)) | ((a == -1) & (b == imin))


def _ovf_neg(d: jax.Array) -> jax.Array:
    """-INT_MIN / |INT_MIN| have no representation and wrap in place."""
    return d == jnp.iinfo(d.dtype).min


def _rescale_guard(data: jax.Array, from_scale: int,
                   to_scale: int) -> Tuple[jax.Array, jax.Array]:
    """`_rescale` plus a wrap mask: up-scaling multiplies by 10^k, so
    any |value| beyond int64_max // 10^k wraps before the arithmetic it
    feeds even runs (down-scaling only shrinks — never wraps)."""
    if to_scale > from_scale:
        f = 10 ** (to_scale - from_scale)
        lim = jnp.iinfo(jnp.int64).max // f
        return data * f, (data > lim) | (data < -lim)
    return _rescale(data, from_scale, to_scale), jnp.zeros(data.shape, jnp.bool_)


def _trunc_mod(a: jax.Array, b: jax.Array) -> jax.Array:
    """SQL mod takes the sign of the dividend."""
    bs = jnp.where(b == 0, 1, b)
    r = jnp.abs(a) % jnp.abs(bs)
    return jnp.where(a < 0, -r, r)


def _like_to_regex(pattern: str) -> "re.Pattern":
    # SQL LIKE: % = any run, _ = any single char
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return re.compile("^" + "".join(out) + "$", re.DOTALL)


class ExprCompiler:
    """Compiles expressions against a fixed input schema (types +
    dictionaries), mirroring how the reference compiles per plan node."""

    def __init__(self, input_types: Sequence[Type],
                 dictionaries: Sequence[Optional[Dictionary]],
                 proven: Optional[Dict[int, bool]] = None):
        """``proven``: ``id(site) -> bool`` for the guarded arithmetic
        calls of the expressions to compile (``analysis.ranges.
        proven_table``: what the plan's intervals proved, as a chain's
        stage signed it at lowering, so that the program is a function
        of its signature and not of an interval's numbers).  A site
        proved compiles without its runtime guard; absent, every guard
        stays."""
        self.input_types = list(input_types)
        self.dictionaries = list(dictionaries)
        self._proven = proven or {}

    @classmethod
    def for_page(cls, page: Page, proven=None) -> "ExprCompiler":
        return cls([b.type for b in page.blocks],
                   [b.dictionary for b in page.blocks], proven=proven)

    def _guard_free(self, expr: Call) -> bool:
        """True where the interval analysis proved that no runtime
        guard of the arithmetic call ``expr`` can fire."""
        return self._proven.get(id(expr), False)

    # ------------------------------------------------------------------
    def compile(self, expr: Expr) -> CompiledExpr:
        if isinstance(expr, ColumnRef):
            i = expr.index
            return lambda page: (page.blocks[i].data, page.blocks[i].valid)

        if isinstance(expr, Literal):
            return self._compile_literal(expr)

        assert isinstance(expr, Call), expr
        fn = expr.fn
        if fn == "try":
            # runtime identity: trappable errors already NULL their
            # lanes engine-wide; the node only marks the subtree as
            # TRY-sanctioned for the kernel-soundness tier
            return self.compile(expr.args[0])
        if fn == "row_construct":
            fns = [self.compile(a) for a in expr.args]
            rt = expr.type

            def run_row_construct(page, fns=fns, rt=rt):
                from presto_tpu.ops import container as ct

                pairs = [f(page) for f in fns]
                out = ct.construct_row([d for d, _ in pairs],
                                       [v for _, v in pairs], rt)
                return out, page.row_mask

            return run_row_construct
        if fn == "row_field":
            base_f = self.compile(expr.args[0])
            rt = expr.args[0].type
            i = int(expr.args[1].value)

            def run_row_field(page, base_f=base_f, rt=rt, i=i):
                from presto_tpu.ops import container as ct

                d, v = base_f(page)
                out, nn = ct.row_field(d, rt, i)
                return out, v & nn

            return run_row_field
        if fn == "retype_row":
            # CAST(row AS ROW(name type, ...)): names are metadata on
            # the type; the storage matrix passes through unchanged
            base_f = self.compile(expr.args[0])

            def run_retype_row(page, base_f=base_f):
                return base_f(page)

            return run_retype_row
        if fn in _CONTAINER_FNS:
            return self._compile_container(expr)
        if fn in _GEO_FNS:
            return self._compile_geo(expr)
        if fn in ("regress", "classify"):
            return self._compile_ml(expr)
        if fn in ("and", "or"):
            return self._compile_logic(expr)
        if fn == "not":
            (a,) = [self.compile(x) for x in expr.args]

            def run_not(page):
                d, v = a(page)
                return jnp.logical_not(d), v

            return run_not
        if fn in ("is_null", "not_null"):
            (a,) = [self.compile(x) for x in expr.args]
            want_null = fn == "is_null"

            def run_isnull(page):
                _, v = a(page)
                d = jnp.logical_not(v) if want_null else v
                return d, jnp.ones_like(v)

            return run_isnull
        if fn == "like":
            return self._compile_like(expr)
        if fn == "in":
            return self._compile_in(expr)
        if fn == "between":
            lo = Call(type=BOOLEAN, fn="ge", args=(expr.args[0], expr.args[1]))
            hi = Call(type=BOOLEAN, fn="le", args=(expr.args[0], expr.args[2]))
            return self.compile(Call(type=BOOLEAN, fn="and", args=(lo, hi)))
        if fn in ("eq", "ne", "lt", "le", "gt", "ge"):
            return self._compile_cmp(expr)
        if fn in ("add", "sub", "mul", "div", "mod"):
            return self._compile_arith(expr)
        if fn == "neg":
            (a,) = [self.compile(x) for x in expr.args]
            if expr.type.is_long_decimal:
                from presto_tpu.ops import decimal128 as d128

                return lambda page: ((lambda dv: (d128.neg(dv[0]), dv[1]))(a(page)))

            free = self._guard_free(expr)
            lane = expr.type.np_dtype

            def run_neg(page):
                d, v = a(page)
                if jnp.issubdtype(d.dtype, jnp.integer) \
                        and not (free and d.dtype == lane):
                    # -INT_MIN wraps in place; NULL that lane (deviation:
                    # the reference raises ARITHMETIC_OVERFLOW)
                    v = v & jnp.logical_not(_ovf_neg(d))
                return -d, v

            return run_neg
        if fn in ("year", "month", "day"):
            return self._compile_datepart(expr)
        if fn == "date_add_days":
            a, b = [self.compile(x) for x in expr.args]

            def run_dadd(page):
                (da, va), (db, vb) = a(page), b(page)
                return (da + db).astype(jnp.int32), va & vb

            return run_dadd
        if fn == "if":
            if self._is_dict_string_case(expr):
                return self._compile_string_case(expr)
            out_t = expr.type
            c = self.compile(expr.args[0])
            t = self._compile_operand(expr.args[1], out_t)
            f = self._compile_operand(expr.args[2], out_t)
            tt, ft = expr.args[1].type, expr.args[2].type

            def run_if(page):
                (dc, vc), (dt, vt), (df, vf) = c(page), t(page), f(page)
                dt2 = self._coerce(dt, tt, out_t)
                df2 = self._coerce(df, ft, out_t)
                cond = dc & vc
                return _where_rows(cond, dt2, df2), jnp.where(cond, vt, vf)

            return run_if
        if fn == "case":
            if self._is_dict_string_case(expr):
                return self._compile_string_case(expr)
            return self._compile_case(expr)
        if fn == "coalesce":
            if self._is_dict_string_case(expr):
                return self._compile_string_case(expr)
            out_t = expr.type
            parts = [(self._compile_operand(x, out_t), x.type) for x in expr.args]

            def run_coalesce(page):
                data = None
                valid = None
                for cf, t in parts:
                    d, v = cf(page)
                    d = self._coerce(d, t, out_t)
                    if data is None:
                        data, valid = d, v
                    else:
                        take = jnp.logical_not(valid) & v
                        data = _where_rows(take, d, data)
                        valid = valid | v
                return data, valid

            return run_coalesce
        if fn in ("cast_double", "cast_bigint") \
                and expr.args[0].type.is_raw_string:
            raise ValueError(
                f"{fn} is unsupported over raw varchar columns "
                "(dictionary varchar parses via a value LUT)")
        if fn in ("cast_double", "cast_bigint") \
                and expr.args[0].type.is_string:
            # varchar -> number: parse the dictionary values host-side,
            # one device gather; unparseable -> NULL (deviation: the
            # reference raises)
            return self._compile_string_number_cast(expr)
        if fn == "cast_double":
            (a,) = [self.compile(x) for x in expr.args]
            t = expr.args[0].type
            return lambda page: ((lambda dv: (_to_double(dv[0], t), dv[1]))(a(page)))
        if fn == "cast_bigint":
            (a,) = [self.compile(x) for x in expr.args]
            t = expr.args[0].type

            def run_cast_bigint(page):
                d, v = a(page)
                if t.is_long_decimal:
                    return self._coerce(d, t, BIGINT_T), v
                if t.is_decimal and t.scale:
                    # HALF_UP, matching the reference's
                    # DecimalCasts.shortDecimalToBigint (2.5 -> 3,
                    # -2.5 -> -3); floor q plus remainder vote, with the
                    # negative side tipping strictly past the midpoint
                    s = 10 ** t.scale
                    q = d // s
                    r = d - q * s
                    up = jnp.where(d >= 0, r * 2 >= s, r * 2 > s)
                    d = q + up.astype(d.dtype)
                return d.astype(jnp.int64), v

            return run_cast_bigint
        if fn in ("cast_real", "cast_smallint", "cast_tinyint"):
            (a,) = [self.compile(x) for x in expr.args]
            t = expr.args[0].type
            target = {"cast_real": jnp.float32, "cast_smallint": jnp.int16,
                      "cast_tinyint": jnp.int8}[fn]

            def run_cast_narrow(page):
                d, v = a(page)
                if t.is_long_decimal:
                    # collapse the two-limb matrix through the shared
                    # coercion first (as cast_bigint does)
                    d = (self._coerce(d, t, DOUBLE) if fn == "cast_real"
                         else self._coerce(d, t, BIGINT_T))
                elif t.is_decimal:
                    d = d / (10.0 ** t.scale) if fn == "cast_real" \
                        else d // (10 ** t.scale)
                if fn == "cast_real":
                    return d.astype(target), v
                # out-of-range values NULL instead of wrapping
                # (documented deviation: the reference raises
                # INVALID_CAST_ARGUMENT); the range test runs at the
                # wide dtype, before the narrowing astype can lie
                info = jnp.iinfo(target)
                wide = d.astype(jnp.int64)
                fits = (wide >= info.min) & (wide <= info.max)
                return wide.astype(target), v & fits

            return run_cast_narrow
        if fn in ("cast_char", "cast_varbinary"):
            # metadata-only re-typing: dictionary codes / byte matrices
            # pass through unchanged
            a = self.compile(expr.args[0])
            return lambda page: a(page)
        if fn == "cast_time":
            (a,) = [self.compile(x) for x in expr.args]
            t = expr.args[0].type
            if not (t.name in ("timestamp", "time")):
                raise ValueError(f"cannot cast {t} to time")

            def run_cast_time(page):
                d, v = a(page)
                if t.name == "timestamp":
                    d = jnp.mod(d, MICROS_PER_DAY)  # time-of-day part
                return d.astype(jnp.int64), v

            return run_cast_time
        if fn in STRING_TRANSFORM_FNS:
            if fn == "concat" and any(
                a.type.is_raw_string for a in expr.args if not isinstance(a, Literal)
            ):
                return self._compile_raw_concat(expr)
            _rc = _transform_column(expr)
            if _rc is not None and _rc.type.is_raw_string:
                return self._compile_raw_transform(expr)
            # dictionary codes pass through unchanged; the *values* are
            # transformed host-side once (see _dict_of) — the device
            # never touches bytes (DictionaryAwarePageProjection analog).
            # Transforms that can yield NULL fold a per-code LUT into
            # validity.
            col = _transform_column(expr)
            if col is None or _string_transform(expr) is None:
                # never silently pass raw codes through an underivable
                # transform — that would surface codes as values
                raise KeyError(f"cannot compile string transform {expr}")
            # force derived-dict materialization so the null LUT exists
            if expr_dictionary(expr, self.dictionaries) is None:
                raise ValueError(f"no dictionary for string transform {expr}")
            null_lut = _transform_null_lut(expr, self.dictionaries)
            inner_f = self.compile(col)
            if null_lut is None:
                return inner_f

            def run_derived(page):
                d, v = inner_f(page)
                return d, v & null_lut[jnp.clip(d, 0, null_lut.shape[0] - 1)]

            return run_derived
        if fn in ("length", "strpos", "codepoint", "json_array_length",
                  "url_extract_port", "from_base", "date_parse",
                  "from_iso8601_date", "levenshtein_distance",
                  "hamming_distance", "json_size"):
            if expr.args[0].type.is_raw_string:
                if fn not in ("length", "strpos", "codepoint"):
                    raise ValueError(
                        f"{fn} is unsupported over raw varchar columns "
                        "(dictionary varchar runs it as a value LUT)")
                return self._compile_raw_int_fn(expr)
            return self._compile_string_lut_fn(expr)
        if fn in ("crc32", "xxhash64"):
            return self._compile_binary_hash(expr)
        if fn == "date_format":
            return self._compile_date_format(expr)
        if fn in ("last_day_of_month", "year_of_week"):
            return self._compile_datepart(expr)
        if fn in ("regexp_like", "starts_with", "ends_with", "is_json_scalar"):
            if expr.args[0].type.is_raw_string:
                return self._compile_raw_bool(expr)
            return self._compile_string_bool_lut(expr)
        if fn in ("hll_bucket", "hll_rho"):
            return self._compile_hll(expr)
        if fn == "cast_decimal":
            (a,) = [self.compile(x) for x in expr.args[:1]]
            t0 = expr.args[0].type
            out_t = expr.type

            def run_cast_decimal(page):
                d, v = a(page)
                if t0.name == "double":
                    if out_t.is_long_decimal:
                        # scale in limb space: hi/lo split of the scaled
                        # float stays within int64 for any p<=36 value
                        from presto_tpu.ops import decimal128 as d128

                        scaled = jnp.round(d * (10.0 ** out_t.scale))
                        hi = jnp.floor(scaled / float(d128.BASE))
                        lo = scaled - hi * float(d128.BASE)
                        two = d128.normalize(hi.astype(jnp.int64),
                                             lo.astype(jnp.int64))
                        if (out_t.precision or 0) > 36:
                            # float64 carries < 54 bits anyway; the
                            # 2-limb path is exact for every float
                            return d128.widen(two), v
                        return two, v
                    return jnp.round(d * (10.0 ** out_t.scale)).astype(jnp.int64), v
                return self._coerce(d, t0, out_t), v

            return run_cast_decimal
        if fn in ("abs", "sign", "sqrt", "cbrt", "exp", "ln", "log10", "log2",
                  "power", "pow", "ceil", "ceiling", "floor", "round",
                  "sin", "cos", "tan", "asin", "acos", "atan", "atan2",
                  "sinh", "cosh", "tanh", "degrees", "radians", "truncate",
                  "width_bucket", "is_nan", "is_finite", "is_infinite"):
            return self._compile_math(expr)
        if fn in ("bitwise_and", "bitwise_or", "bitwise_xor", "bitwise_not",
                  "bitwise_shift_left", "bitwise_shift_right", "bit_count"):
            return self._compile_bitwise(expr)
        if fn in ("greatest", "least"):
            return self._compile_greatest_least(expr)
        if fn == "nullif":
            ta, tb = expr.args[0].type, expr.args[1].type
            a = self.compile(expr.args[0])
            b = self._compile_operand(expr.args[1], ta)
            if ta.is_raw_string:
                from presto_tpu.ops import rawstring as rs

                def run_nullif_raw(page):
                    (da, va), (db, vb) = a(page), b(page)
                    _, eq_ = rs.compare(da, db)
                    return da, va & jnp.logical_not(va & vb & eq_)

                return run_nullif_raw

            def run_nullif(page):
                (da, va), (db, vb) = a(page), b(page)
                da2, db2 = self._align_pair(da, ta, db, tb)
                eq_ = va & vb & (da2 == db2)
                return da, va & jnp.logical_not(eq_)

            return run_nullif
        if fn in ("day_of_week", "day_of_year", "quarter", "week",
                  "hour", "minute", "second", "millisecond"):
            return self._compile_datepart(expr)
        if fn in ("ts_add_micros", "ts_add_months", "date_add_months",
                  "cast_timestamp", "cast_date", "to_unixtime", "from_unixtime",
                  "date_trunc", "date_add", "date_diff"):
            return self._compile_datetime(expr)
        raise KeyError(f"cannot compile {expr}")

    def _compile_string_lut_fn(self, expr: Call) -> CompiledExpr:
        """String scalar -> int via a host-computed LUT over the
        dictionary, one device gather (length, strpos, codepoint,
        json_array_length, url_extract_port). None values null out."""
        colref = expr.args[0]
        if expr.fn in ("levenshtein_distance", "hamming_distance") \
                and isinstance(colref, Literal):
            colref = expr.args[1]  # literal may sit on either side
        cf = self.compile(colref)
        d = self._dict_of(colref)
        if d is None:
            raise ValueError(f"no dictionary for string column {colref}")
        fn = expr.fn
        if any(isinstance(a, Literal) and a.value is None
               for a in expr.args):
            # a NULL parameter argument (either side for the symmetric
            # distance fns) nulls the whole column out
            def run_null(page):
                dd, v = cf(page)
                return jnp.zeros_like(dd, dtype=jnp.int64), v & False

            return run_null
        if fn == "length":
            lut_vals = [len(v) for v in d.values]
        elif fn == "strpos":  # strpos(col, needle_literal): 1-based, 0 = miss
            sub = expr.args[1]
            assert isinstance(sub, Literal), "strpos needle must be a literal"
            lut_vals = [v.find(sub.value) + 1 for v in d.values]
        elif fn == "codepoint":
            lut_vals = [ord(v[0]) if v else None for v in d.values]
        elif fn == "json_array_length":
            import json as _json

            def jal(v):
                try:
                    got = _json.loads(v)
                except Exception:
                    return None
                return len(got) if isinstance(got, list) else None

            lut_vals = [jal(v) for v in d.values]
        elif fn == "json_size":
            path = expr.args[1].value

            def jsize(v, path=path):
                found, got = _json_path_lookup(v, path)
                if not found:
                    return None
                return len(got) if isinstance(got, (dict, list)) else 0

            lut_vals = [jsize(v) for v in d.values]
        elif fn == "from_base":
            radix = int(expr.args[1].value)

            def fb(v, radix=radix):
                try:
                    return int(v, radix)
                except Exception:
                    return None

            lut_vals = [fb(v) for v in d.values]
        elif fn == "date_parse":
            fmt = expr.args[1].value
            lut_vals = [mysql_datetime_micros(v, fmt) for v in d.values]
        elif fn == "from_iso8601_date":
            lut_vals = [iso_date_days(v) for v in d.values]
        elif fn in ("levenshtein_distance", "hamming_distance"):
            other = expr.args[1] if isinstance(expr.args[1], Literal) \
                else expr.args[0]
            if not isinstance(other, Literal) or other.value is None:
                raise ValueError(f"{fn} needs one literal argument "
                                 "(column x column would need a cross "
                                 "product of dictionaries)")
            lit = other.value
            if fn == "hamming_distance":
                lut_vals = [
                    sum(a != b for a, b in zip(v, lit))
                    if len(v) == len(lit) else None  # deviation: ref raises
                    for v in d.values]
            else:
                lut_vals = [_levenshtein(v, lit) for v in d.values]
        else:  # url_extract_port
            from urllib.parse import urlparse

            def port(v):
                try:
                    return urlparse(v).port
                except Exception:
                    return None

            lut_vals = [port(v) for v in d.values]
        nulls = [v is None for v in lut_vals]
        lut = jnp.asarray([0 if v is None else v for v in lut_vals], dtype=jnp.int64)
        vlut = None if not any(nulls) else jnp.asarray([not n for n in nulls])

        def run_lut(page):
            dd, v = cf(page)
            c = jnp.clip(dd, 0, lut.shape[0] - 1)
            if vlut is not None:
                v = v & vlut[c]
            return lut[c], v

        return run_lut

    def _compile_string_bool_lut(self, expr: Call) -> CompiledExpr:
        """String predicate via a host-computed boolean LUT over the
        dictionary (regexp_like, starts_with, ends_with, is_json_scalar)."""
        colref = expr.args[0]
        cf = self.compile(colref)
        d = self._dict_of(colref)
        if d is None:
            raise ValueError(f"no dictionary for string column {colref}")
        fn = expr.fn
        if fn == "regexp_like":
            rx = re.compile(expr.args[1].value)
            pred = lambda v: rx.search(v) is not None
        elif fn == "starts_with":
            prefix = expr.args[1].value
            pred = lambda v: v.startswith(prefix)
        elif fn == "ends_with":
            suffix = expr.args[1].value
            pred = lambda v: v.endswith(suffix)
        else:  # is_json_scalar
            import json as _json

            def pred(v):
                try:
                    return not isinstance(_json.loads(v), (dict, list))
                except Exception:
                    return False

        lut = jnp.asarray(d.lut(pred))

        def run_blut(page):
            dd, v = cf(page)
            return lut[jnp.clip(dd, 0, lut.shape[0] - 1)], v

        return run_blut

    def _compile_string_number_cast(self, expr: Call) -> CompiledExpr:
        colref = expr.args[0]
        cf = self.compile(colref)
        d = self._dict_of(colref)
        if d is None:
            raise ValueError(f"no dictionary for string column {colref}")
        to_double = expr.fn == "cast_double"
        vals = [parse_number_strict(v, to_double) for v in d.values]
        dtype = jnp.float64 if to_double else jnp.int64
        lut = jnp.asarray([0 if x is None else x for x in vals], dtype=dtype)
        vlut = jnp.asarray([x is not None for x in vals])

        def run_str_cast(page):
            dd, v = cf(page)
            c = jnp.clip(dd, 0, lut.shape[0] - 1)
            return lut[c], v & vlut[c]

        return run_str_cast

    # (id(inner dict), delim, cap) -> (inner ref, parts Dictionary,
    # np code matrix, np lengths)
    _SPLIT_CACHE: dict = {}

    @classmethod
    def split_parts(cls, d, delim: str, cap: int):
        """Derived artifacts of split(col, delim): the union dictionary
        of every value's parts plus a (n_codes, 1+cap) array-matrix LUT
        of part codes — one device gather per page
        (StringFunctions.java#split realized dictionary-side)."""
        key = (id(d), delim, cap)
        got = cls._SPLIT_CACHE.get(key)
        if got is not None:
            return got[1], got[2]
        parts_index: dict = {}
        values: list = []

        def code_of(p):
            c = parts_index.get(p)
            if c is None:
                c = parts_index[p] = len(values)
                values.append(p)
            return c

        import numpy as np

        lut = np.zeros((len(d.values), 1 + cap), dtype=np.int32)
        for i, v in enumerate(d.values):
            # limit semantics: the last element keeps the unsplit
            # remainder (StringFunctions.java#split's limit contract —
            # the slot capacity acts as the limit, losslessly)
            ps = v.split(delim, cap - 1)
            lut[i, 0] = len(ps)
            for j, p in enumerate(ps):
                lut[i, 1 + j] = code_of(p)
        pd = Dictionary(values or [""])
        cls._SPLIT_CACHE[key] = (d, pd, lut)
        return pd, lut

    def _compile_split(self, expr: Call) -> CompiledExpr:
        colref = expr.args[0]
        cf = self.compile(colref)
        d = self._dict_of(colref)
        if d is None:
            raise ValueError(f"no dictionary for string column {colref}")
        delim = expr.args[1]
        if not isinstance(delim, Literal) or delim.value is None:
            raise ValueError("split delimiter must be a literal")
        cap = expr.type.max_elems
        _, lut_np = self.split_parts(d, delim.value, cap)
        lut = jnp.asarray(lut_np)

        def run_split(page):
            dd, v = cf(page)
            c = jnp.clip(dd, 0, lut.shape[0] - 1)
            return lut[c].astype(expr.type.np_dtype), v

        return run_split

    def _compile_binary_hash(self, expr: Call) -> CompiledExpr:
        """crc32 / xxhash64 of to_utf8(varchar): hashed host-side over
        the dictionary values, one device gather
        (VarbinaryFunctions.java#crc32/#xxhash64).  Only the
        to_utf8(string) composition is supported — general varbinary
        lanes would hash bytes on device."""
        inner = expr.args[0]
        if not (isinstance(inner, Call) and inner.fn == "to_utf8"):
            raise ValueError(f"{expr.fn} supports to_utf8(varchar) "
                             "arguments only")
        colref = inner.args[0]
        cf = self.compile(colref)
        d = self._dict_of(colref)
        if d is None:
            raise ValueError(f"no dictionary for string column {colref}")
        if expr.fn == "crc32":
            import zlib

            vals = [zlib.crc32(v.encode()) for v in d.values]
        else:
            vals = [xxh64_signed(v.encode()) for v in d.values]
        lut = jnp.asarray(vals, dtype=jnp.int64)

        def run_hash(page):
            dd, v = cf(page)
            return lut[jnp.clip(dd, 0, lut.shape[0] - 1)], v

        return run_hash

    # date_format dictionaries are pure functions of (fmt, day range) —
    # cache them across queries
    _DATE_FMT_CACHE: dict = {}
    #: formatted-day dictionary range: 1900-01-01 .. 2100-01-01
    DATE_FMT_BASE = -25567
    DATE_FMT_SPAN = 73049

    @classmethod
    def date_format_dictionary(cls, fmt: str) -> "Dictionary":
        """The domain dictionary for date_format(date_col, fmt): one
        formatted string per epoch day over a 1900..2100 range, codes =
        day - base.  TPU-first: the format never touches the device —
        dates become dictionary codes with one subtract."""
        got = cls._DATE_FMT_CACHE.get(fmt)
        if got is not None:
            return got
        import datetime as _dt

        py_fmt = _mysql_to_strftime(fmt)
        if any(c in _MYSQL_TIME_CODES
               for c in re.findall(r"%(.)", fmt)):
            raise ValueError(
                "date_format supports date-valued columns (time-of-day "
                "format codes need the timestamp's full domain)")
        base = _dt.date(1900, 1, 1)
        values = [(base + _dt.timedelta(days=i)).strftime(py_fmt)
                  for i in range(cls.DATE_FMT_SPAN)]
        d = Dictionary(values)
        cls._DATE_FMT_CACHE[fmt] = d
        return d

    def _compile_date_format(self, expr: Call) -> CompiledExpr:
        if expr.args[0].type.name not in ("date", "timestamp"):
            raise ValueError("date_format requires a date argument")
        fmt = expr.args[1]
        if not isinstance(fmt, Literal) or fmt.value is None:
            raise ValueError("date_format format must be a literal")
        self.date_format_dictionary(fmt.value)  # validate fmt eagerly
        a = self.compile(expr.args[0])
        is_ts = expr.args[0].type.name == "timestamp"

        def run_date_format(page):
            d, v = a(page)
            days = (d.astype(jnp.int64) // MICROS_PER_DAY) if is_ts \
                else d.astype(jnp.int64)
            code = days - self.DATE_FMT_BASE
            inrange = (code >= 0) & (code < self.DATE_FMT_SPAN)
            return jnp.clip(code, 0, self.DATE_FMT_SPAN - 1).astype(
                jnp.int32), v & inrange

        return run_date_format

    # HLL sketch primitives (reference:
    # operator/aggregation/ApproximateCountDistinctAggregations.java +
    # airlift HyperLogLog; here integer device math, m = 4096 buckets)
    HLL_P = 12
    HLL_M = 1 << 12

    def _compile_hll(self, expr: Call) -> CompiledExpr:
        colref = expr.args[0]
        # optional second literal argument: register-index width P
        # (approx_set's value sketches use a smaller m than
        # approx_distinct's internal rewrite)
        P = (int(expr.args[1].value) if len(expr.args) > 1
             else ExprCompiler.HLL_P)
        cf = self.compile(colref)
        t = colref.type
        fn = expr.fn
        canon_lut = None
        if t.is_raw_string:
            from presto_tpu.ops.rawstring import hash_bytes

            def run_raw_hll(page):
                d, v = cf(page)
                h = _mix_u64(hash_bytes(d).astype(jnp.uint64))
                return _hll_from_hash(h, fn, P), v

            return run_raw_hll
        if t.is_string:
            # canonicalize codes to value ids so transforms that map
            # many codes to one value (substr/upper/...) count distinct
            # VALUES, not distinct source codes
            d = expr_dictionary(colref, self.dictionaries)
            if d is None:
                raise ValueError(f"no dictionary for string column {colref}")
            canon: dict = {}
            canon_lut = jnp.asarray(
                [canon.setdefault(v, len(canon)) for v in d.values],
                dtype=jnp.int64)

        def run_hll(page):
            d, v = cf(page)
            if t.name == "double":
                lane = jax.lax.bitcast_convert_type(d, jnp.int64)
            elif canon_lut is not None:
                lane = canon_lut[jnp.clip(d, 0, canon_lut.shape[0] - 1)]
            else:
                lane = d.astype(jnp.int64)
            h = _mix_u64(lane.astype(jnp.uint64))
            return _hll_from_hash(h, fn, P), v

        return run_hll

    def _compile_ml(self, expr: Call) -> CompiledExpr:
        """regress(model, features) / classify(model, features) —
        models are ARRAY(double) values from learn_regressor /
        learn_classifier (presto-ml's model type realized as plain
        arrays, so inference is pure device math)."""
        from presto_tpu.ops import container as ct

        model_e, feats_e = expr.args
        mf = self.compile(model_e)
        ff = self.compile(feats_e)
        mt, ft = model_e.type, feats_e.type
        if not (mt.is_array and ft.is_array):
            raise ValueError(f"{expr.fn} expects (model array, features array)")
        k = ft.max_elems

        def feats_matrix(fd):
            slots = ct.elem_slots(fd, ft)
            return jnp.where(ct.elem_null_mask(slots), 0.0,
                             slots.astype(jnp.float64))

        if expr.fn == "regress":

            def run_regress(page):
                (md, mv), (fd, fv) = mf(page), ff(page)
                w = ct.elem_slots(md, mt).astype(jnp.float64)
                x = feats_matrix(fd)
                pred = jnp.sum(w[:, :k] * x, axis=1) + w[:, k]
                return pred, mv & fv

            return run_regress

        from presto_tpu.ops.aggregate import ML_MAX_CLASSES

        C = ML_MAX_CLASSES

        def run_classify(page):
            (md, mv), (fd, fv) = mf(page), ff(page)
            m = ct.elem_slots(md, mt).astype(jnp.float64)
            x = feats_matrix(fd)
            n = x.shape[0]
            prior = m[:, 1 : 1 + C]
            mean = m[:, 1 + C : 1 + C + C * k].reshape(n, C, k)
            var = jnp.maximum(m[:, 1 + C + C * k : 1 + C + 2 * C * k]
                              .reshape(n, C, k), 1e-12)
            ll = jnp.log(jnp.maximum(prior, 1e-12)) + jnp.sum(
                -0.5 * jnp.log(2 * jnp.pi * var)
                - (x[:, None, :] - mean) ** 2 / (2 * var), axis=2)
            return jnp.argmax(ll, axis=1).astype(jnp.int64), mv & fv

        return run_classify

    def _compile_geo(self, expr: Call) -> CompiledExpr:
        """ST_* functions (presto-geospatial GeoFunctions.java).  WKT
        geometries ride dictionary varchar: host parse per distinct
        value, device kernels per row (geo.py)."""
        from presto_tpu import geo

        fn = expr.fn
        if fn == "st_geometryfromtext":
            arg = expr.args[0]
            if isinstance(arg, Literal) and arg.value is not None:
                geo.parse_wkt(str(arg.value))  # fail at compile, not per row
            return self.compile(arg)
        if fn == "st_point":
            raise ValueError(
                "ST_Point is only usable inside ST_Distance / ST_Contains")
        if fn in ("st_area", "st_x", "st_y"):
            host = {"st_area": geo.st_area, "st_x": geo.st_x, "st_y": geo.st_y}[fn]
            return self._geo_float_lut(expr.args[0], host)
        if fn == "st_distance":
            ax, ay = self._point_accessor(expr.args[0])
            bx, by = self._point_accessor(expr.args[1])

            def run_dist(page):
                (x1, v1), (y1, vy1) = ax(page), ay(page)
                (x2, v2), (y2, vy2) = bx(page), by(page)
                return (geo.point_distance(x1, y1, x2, y2),
                        v1 & vy1 & v2 & vy2)

            return run_dist
        assert fn == "st_contains"
        garg = _unwrap_geomtext(expr.args[0])
        px, py = self._point_accessor(expr.args[1])
        if isinstance(garg, Literal):
            g = geo.parse_wkt(str(garg.value))

            def run_contains_lit(page):
                (x, vx), (y, vy) = px(page), py(page)
                hit = geo.bbox_mask(g.bbox, x, y) & geo.points_in_geometry(g, x, y)
                return hit, vx & vy

            return run_contains_lit
        # dictionary-coded geometry column: one fused PIP per distinct
        # geometry, selected by code (the spatial-join inner kernel)
        d = self._dict_of(garg)
        if d is None:
            raise ValueError("ST_Contains geometry must be a WKT literal or "
                             "dictionary varchar column")
        cf = self.compile(garg)
        geoms = []
        for v in d.values:
            try:
                geoms.append(geo.parse_wkt(v))
            except Exception:
                geoms.append(None)

        def run_contains_col(page):
            (code, vg) = cf(page)
            (x, vx), (y, vy) = px(page), py(page)
            hit = jnp.zeros(x.shape[0], dtype=jnp.bool_)
            ok = jnp.zeros(x.shape[0], dtype=jnp.bool_)
            for gi, g in enumerate(geoms):
                sel = code == gi
                if g is None:
                    continue
                ok = ok | sel
                ghit = geo.bbox_mask(g.bbox, x, y) & geo.points_in_geometry(g, x, y)
                hit = jnp.where(sel, ghit, hit)
            return hit, vg & vx & vy & ok

        return run_contains_col

    def _geo_float_lut(self, arg: Expr, host) -> CompiledExpr:
        """varchar WKT -> float via host LUT over the dictionary."""
        arg = _unwrap_geomtext(arg)
        if isinstance(arg, Literal):
            val = host(str(arg.value)) if arg.value is not None else None

            def run_const(page):
                n = page.capacity
                return (jnp.full(n, 0.0 if val is None else float(val)),
                        jnp.full(n, val is not None))

            return run_const
        d = self._dict_of(arg)
        if d is None:
            raise ValueError("geometry argument needs a WKT literal or "
                             "dictionary varchar column")
        cf = self.compile(arg)
        vals = []
        for v in d.values:
            try:
                vals.append(host(v))
            except Exception:
                vals.append(None)
        lut = jnp.asarray([0.0 if v is None else float(v) for v in vals])
        vlut = jnp.asarray([v is not None for v in vals])

        def run_lut(page):
            code, v = cf(page)
            c = jnp.clip(code, 0, lut.shape[0] - 1)
            return lut[c], v & vlut[c]

        return run_lut

    def _point_accessor(self, e: Expr):
        """-> (x_fn, y_fn) compiled accessors for a point operand:
        ST_Point(x, y) call, WKT literal, or dictionary point column."""
        e = _unwrap_geomtext(e)
        if isinstance(e, Call) and e.fn == "st_point":
            xa = self.compile(e.args[0])
            ya = self.compile(e.args[1])
            tx, ty = e.args[0].type, e.args[1].type

            def run_x(page):
                data, v = xa(page)
                return _to_double(data, tx), v

            def run_y(page):
                data, v = ya(page)
                return _to_double(data, ty), v

            return run_x, run_y
        from presto_tpu import geo

        return (self._geo_float_lut(e, geo.st_x),
                self._geo_float_lut(e, geo.st_y))

    def _compile_container(self, expr: Call) -> CompiledExpr:
        """ARRAY/MAP functions -> masked trailing-axis vector kernels
        (ops/container.py; reference operator/scalar/ArrayFunctions,
        MapKeys, MapValues, ElementAt, CardinalityFunction)."""
        from presto_tpu.ops import container as ct

        fn = expr.fn
        out_t = expr.type
        if fn == "array_construct":
            elem_t = out_t.element
            if elem_t is not None and elem_t.is_string \
                    and all(isinstance(a, Literal) for a in expr.args):
                # all-literal string array (the binder rejects any
                # other string-array construction): elements become
                # codes in the shared derived dictionary; the channel/
                # unnest layer re-attaches it via expr_dictionary
                dic = literal_array_dictionary(
                    [a.value for a in expr.args if a.value is not None])
                codes = [(dic.code_of(a.value) if a.value is not None
                          else 0, a.value is not None) for a in expr.args]

                def run_construct_lit(page):
                    n = page.capacity
                    datas = [jnp.full((n,), c, jnp.int64) for c, _ in codes]
                    valids = [jnp.full((n,), ok, jnp.bool_)
                              for _, ok in codes]
                    return (ct.construct_array(datas, valids, out_t),
                            jnp.ones(n, jnp.bool_))

                return run_construct_lit
            parts = [(self._compile_operand(a, elem_t), a.type) for a in expr.args]

            def run_construct(page):
                datas, valids = [], []
                for cf, t in parts:
                    d, v = cf(page)
                    datas.append(self._coerce(d, t, elem_t))
                    valids.append(v)
                n = page.capacity
                return ct.construct_array(datas, valids, out_t), jnp.ones(n, jnp.bool_)

            return run_construct
        if fn in ("map", "map_construct"):
            k = self.compile(expr.args[0])
            v = self.compile(expr.args[1])
            kt, vt = expr.args[0].type, expr.args[1].type

            def run_map(page):
                (kd, kv), (vd, vv) = k(page), v(page)
                return ct.construct_map(kd, kt, vd, vt, out_t), kv & vv

            return run_map
        if fn == "sequence":
            lo = int(expr.args[0].value)
            step = int(expr.args[2].value) if len(expr.args) > 2 else 1
            n = out_t.max_elems
            row = jnp.concatenate([
                jnp.asarray([n], dtype=jnp.int64),
                lo + step * jnp.arange(n, dtype=jnp.int64),
            ])

            def run_seq(page):
                cap = page.capacity
                return (jnp.broadcast_to(row[None, :], (cap, n + 1)),
                        jnp.ones(cap, jnp.bool_))

            return run_seq
        if fn == "repeat":
            val = self.compile(expr.args[0])
            n = out_t.max_elems
            count = int(expr.args[1].value)
            storage = out_t.np_dtype

            def run_repeat(page):
                d, v = val(page)
                sent = ct._null_const(storage)
                elems = jnp.where(v[:, None], d.astype(storage)[:, None],
                                  sent)
                body = jnp.broadcast_to(elems, (page.capacity, n))
                length = jnp.full((page.capacity, 1), float(count)
                                  if storage.kind == "f" else count,
                                  dtype=storage)
                return (jnp.concatenate([length, body], axis=1),
                        jnp.ones(page.capacity, jnp.bool_))

            return run_repeat
        if fn == "array_concat":
            a = self.compile(expr.args[0])
            b = self.compile(expr.args[1])
            ta, tb = expr.args[0].type, expr.args[1].type

            def run_cat(page):
                (da, va), (db, vb) = a(page), b(page)
                return ct.concat_arrays(da, ta, db, tb, out_t), va & vb

            return run_cat

        arg0 = self.compile(expr.args[0])
        t0 = expr.args[0].type
        if fn in ("subscript", "element_at"):
            idx = self.compile(expr.args[1])

            def run_sub(page):
                (d, v), (di, vi) = arg0(page), idx(page)
                out, ov = ct.subscript(d, t0, di, vi)
                return out.astype(out_t.np_dtype), v & ov

            return run_sub
        if fn == "cardinality":
            t0 = expr.args[0].type
            if t0.is_hll:
                # HLL estimate with linear-counting small-range
                # correction (same estimator family as hll_merge);
                # slots 0..count-1 of the value half hold the rho of
                # each populated register
                m = t0.max_elems
                alpha = 0.7213 / (1.0 + 1.079 / m)

                def run_hll_card(page):
                    d, v = arg0(page)
                    cnt = jnp.clip(d[:, 0].astype(jnp.int64), 0, m)
                    rho = d[:, 1 + m: 1 + 2 * m].astype(jnp.float64)
                    j = jnp.arange(m, dtype=jnp.int64)[None, :]
                    present = j < cnt[:, None]
                    inv = jnp.where(present, jnp.exp2(-rho), 0.0).sum(axis=1)
                    zeros = (m - cnt).astype(jnp.float64)
                    raw = alpha * m * m / jnp.maximum(inv + zeros, 1e-12)
                    lc = m * jnp.log(m / jnp.maximum(zeros, 1.0))
                    est = jnp.where((raw <= 2.5 * m) & (zeros > 0), lc, raw)
                    return jnp.round(est).astype(jnp.int64), v

                return run_hll_card
            if t0.name == "setdigest":
                # KMV estimator: exact below K slots; else
                # (K-1) / (fraction of hash space below the K-th
                # smallest hash)
                K = t0.max_elems

                def run_kmv_card(page):
                    d, v = arg0(page)
                    ln = jnp.maximum(d[:, 0].astype(jnp.int64), 0)
                    kth = d[:, K].astype(jnp.float64)  # largest stored
                    span = kth - float(jnp.iinfo(jnp.int64).min)
                    frac = jnp.maximum(span, 1.0) / 2.0 ** 64
                    est = jnp.round((K - 1) / frac).astype(jnp.int64)
                    return jnp.where(ln < K, ln, jnp.maximum(est, ln)), v

                return run_kmv_card

            def run_card(page):
                d, v = arg0(page)
                return ct.cardinality(d), v

            return run_card
        if fn in ("jaccard_index", "intersection_cardinality") \
                and t0.name == "setdigest":
            # KMV minhash comparison (SetDigestFunctions.java): over the
            # K smallest distinct hashes of the UNION, jaccard = the
            # fraction present in both digests; intersection = jaccard
            # x the union's KMV cardinality estimate.  A hash appearing
            # in both digests shows up as an adjacent duplicate in the
            # per-row sorted concat (hashes are distinct WITHIN one
            # digest).
            K = t0.max_elems
            argb = self.compile(expr.args[1])
            imin = float(jnp.iinfo(jnp.int64).min)

            def run_setdigest_pair(page):
                (da, va), (db, vb) = arg0(page), argb(page)
                la = jnp.clip(da[:, 0].astype(jnp.int64), 0, K)
                lb = jnp.clip(db[:, 0].astype(jnp.int64), 0, K)
                j = jnp.arange(K, dtype=jnp.int64)[None, :]
                big = jnp.iinfo(jnp.int64).max
                ha = jnp.where(j < la[:, None],
                               da[:, 1:1 + K].astype(jnp.int64), big)
                hb = jnp.where(j < lb[:, None],
                               db[:, 1:1 + K].astype(jnp.int64), big)
                m = jnp.sort(jnp.concatenate([ha, hb], axis=1), axis=1)
                live = m < big
                firsts = jnp.concatenate(
                    [jnp.ones_like(m[:, :1], jnp.bool_),
                     m[:, 1:] != m[:, :-1]], axis=1) & live
                nxt_dup = jnp.concatenate(
                    [m[:, 1:] == m[:, :-1],
                     jnp.zeros_like(m[:, :1], jnp.bool_)], axis=1)
                rank = jnp.cumsum(firsts.astype(jnp.int64), axis=1) - 1
                in_s = firsts & (rank < K)
                inter = jnp.sum((in_s & nxt_dup).astype(jnp.int64), axis=1)
                s_size = jnp.sum(in_s.astype(jnp.int64), axis=1)
                jac = inter.astype(jnp.float64) / jnp.maximum(s_size, 1)
                ok = va & vb
                if fn == "jaccard_index":
                    return jac, ok
                # union KMV estimate from the merged distinct hashes
                distinct_total = jnp.sum(firsts.astype(jnp.int64), axis=1)
                kth = jnp.max(jnp.where(in_s, m, jnp.iinfo(jnp.int64).min),
                              axis=1).astype(jnp.float64)
                frac = jnp.maximum(kth - imin, 1.0) / 2.0 ** 64
                union_est = jnp.where(
                    distinct_total < K, distinct_total,
                    jnp.round((K - 1) / frac).astype(jnp.int64))
                return (jnp.round(jac * union_est).astype(jnp.int64), ok)

            return run_setdigest_pair
        if fn == "hash_counts" and t0.name == "setdigest":
            # the digest IS [len, hashes.., counts..] — identical to the
            # map(bigint,bigint) layout; retype in place
            def run_hash_counts(page):
                d, v = arg0(page)
                return d.astype(out_t.np_dtype), v

            return run_hash_counts
        if fn in ("contains", "array_position"):
            x = self.compile(expr.args[1])
            kern = ct.contains if fn == "contains" else ct.array_position

            def run_ct(page):
                (d, v), (xd, xv) = arg0(page), x(page)
                out, ov = kern(d, t0, xd, xv)
                return out, v & ov

            return run_ct
        if fn in ("array_min", "array_max", "array_sum", "array_average"):

            def run_red(page):
                d, v = arg0(page)
                out, nonempty = ct.array_reduce(d, t0, fn)
                return out.astype(out_t.np_dtype), v & nonempty

            return run_red
        if fn in ("array_sort", "array_distinct"):
            kern = ct.array_sort if fn == "array_sort" else ct.array_distinct

            def run_tf(page):
                d, v = arg0(page)
                return kern(d, t0), v

            return run_tf
        if fn in ("map_keys", "map_values"):
            kern = ct.map_keys_array if fn == "map_keys" else ct.map_values_array

            def run_mk(page):
                d, v = arg0(page)
                return kern(d, t0, out_t), v

            return run_mk
        if fn in ("array_transform", "array_filter", "any_match",
                  "all_match", "none_match"):
            return self._compile_array_lambda(expr, arg0, t0)
        if fn in ("array_intersect", "array_union", "array_except"):
            b_f = self.compile(expr.args[1])
            tb = expr.args[1].type
            kern = {"array_intersect": ct.array_intersect,
                    "array_union": ct.array_union,
                    "array_except": ct.array_except}[fn]

            def run_setop(page):
                (d, v), (bd, bv) = arg0(page), b_f(page)
                return kern(d, t0, bd, tb, out_t), v & bv

            return run_setop
        if fn == "arrays_overlap":
            b_f = self.compile(expr.args[1])
            tb = expr.args[1].type

            def run_overlap(page):
                (d, v), (bd, bv) = arg0(page), b_f(page)
                out, ov = ct.arrays_overlap(d, t0, bd, tb)
                return out, v & bv & ov

            return run_overlap
        if fn == "array_remove":
            x_f = self.compile(expr.args[1])

            def run_remove(page):
                (d, v), (xd, xv) = arg0(page), x_f(page)
                return ct.array_remove(d, t0, xd), v & xv

            return run_remove
        if fn == "map_concat":
            b_f = self.compile(expr.args[1])
            tb = expr.args[1].type

            def run_mconcat(page):
                (d, v), (bd, bv) = arg0(page), b_f(page)
                return ct.map_concat(d, t0, bd, tb, out_t), v & bv

            return run_mconcat
        if fn in ("map_filter", "transform_keys", "transform_values"):
            return self._compile_map_lambda(expr, arg0, t0)
        if fn == "zip_with":
            return self._compile_zip_with(expr)
        if fn == "split":
            return self._compile_split(expr)
        if fn == "reduce":
            return self._compile_reduce(expr)
        if fn == "slice":
            start_e, len_e = expr.args[1], expr.args[2]
            if not (isinstance(start_e, Literal) and isinstance(len_e, Literal)):
                raise ValueError("slice() start/length must be literals")
            start = int(start_e.value)
            ln = int(len_e.value)

            def run_slice(page):
                d, v = arg0(page)
                return ct.slice_array(d, t0, start, ln), v

            return run_slice
        raise KeyError(fn)

    def _compile_map_lambda(self, expr: Call, m_f, t0: Type) -> CompiledExpr:
        """Two-parameter lambdas over map entries (MapFilterFunction /
        MapTransformKey/ValueFunction): both entry halves flatten into
        TWO appended virtual channels and the body evaluates once over
        the entry lanes — the array-lambda design with a (k, v) pair."""
        from presto_tpu.ops import container as ct
        from presto_tpu.page import Block as _Block, Page as _Page

        fn = expr.fn
        lam = expr.args[1]
        body = lam.body
        k_slot, v_slot = lam.params[0].slot, lam.params[1].slot
        out_t = expr.type
        M = t0.max_elems
        kt, vt = t0.key_element, t0.element

        def run(page):
            d, v = m_f(page)
            ks = ct.map_key_slots(d, t0)
            vs = ct.map_value_slots(d, t0)
            live = ct.slot_mask(d, M)
            k_ok = live & ~ct.elem_null_mask(ks)
            v_ok = live & ~ct.elem_null_mask(vs)
            cap = page.capacity
            rep_blocks = tuple(
                _Block(jnp.repeat(b.data, M, axis=0), jnp.repeat(b.valid, M),
                       b.type, b.dictionary)
                for b in page.blocks)
            lam_k = _Block(ks.reshape(cap * M).astype(kt.np_dtype),
                           k_ok.reshape(cap * M), kt)
            lam_v = _Block(vs.reshape(cap * M).astype(vt.np_dtype),
                           v_ok.reshape(cap * M), vt)
            epage = _Page(rep_blocks + (lam_k, lam_v),
                          jnp.repeat(page.row_mask, M))
            nb = len(page.blocks)
            body2 = _subst_lambda_vars(body, {k_slot: nb, v_slot: nb + 1})
            bd, bv = ExprCompiler.for_page(epage).compile(body2)(epage)
            bd2 = bd.reshape(cap, M)
            bv2 = bv.reshape(cap, M)
            storage = out_t.np_dtype
            sent = ct._null_const(storage)
            n_live = ct.lengths(d)
            if fn == "map_filter":
                keep = live & bv2 & bd2.astype(jnp.bool_)
                return ct.compact_entry_pairs(ks, vs, keep, M, storage), v
            if fn == "transform_values":
                newv = jnp.where(live & bv2, bd2.astype(storage), sent)
                out = jnp.concatenate(
                    [n_live[:, None].astype(storage),
                     ks.astype(storage), newv], axis=1)
                return out, v
            # transform_keys: entries whose new key is NULL drop, and
            # duplicate new keys keep the FIRST entry (deviations: the
            # reference raises on both — deduping keeps device lookups
            # and host decodes agreeing)
            newk = bd2.astype(storage)
            keep0 = live & bv2
            eq = newk[:, :, None] == newk[:, None, :]
            earlier = jnp.triu(jnp.ones((M, M), jnp.bool_), 1)  # [i, j] = i<j
            dup = jnp.any(eq & keep0[:, :, None] & earlier[None], axis=1)
            keep = keep0 & ~dup
            return ct.compact_entry_pairs(newk, vs, keep, M, storage), v

        return run

    def _compile_zip_with(self, expr: Call) -> CompiledExpr:
        """zip_with(a1, a2, (x, y) -> body): lanes align by index, the
        shorter array's missing lanes bind NULL (ZipWithFunction), and
        the body evaluates once over max-capacity flattened lanes."""
        from presto_tpu.ops import container as ct
        from presto_tpu.page import Block as _Block, Page as _Page

        a1_f = self.compile(expr.args[0])
        a2_f = self.compile(expr.args[1])
        t1, t2 = expr.args[0].type, expr.args[1].type
        lam = expr.args[2]
        body = lam.body
        x_slot, y_slot = lam.params[0].slot, lam.params[1].slot
        out_t = expr.type
        M = out_t.max_elems

        def pad_slots(slots, m):
            if m >= M:
                return slots[:, :M]
            pad = jnp.full((slots.shape[0], M - m),
                           ct._null_const(slots.dtype), slots.dtype)
            return jnp.concatenate([slots, pad], axis=1)

        def run(page):
            (d1, v1), (d2, v2) = a1_f(page), a2_f(page)
            s1 = pad_slots(ct.elem_slots(d1, t1), t1.max_elems)
            s2 = pad_slots(ct.elem_slots(d2, t2), t2.max_elems)
            l1, l2 = ct.lengths(d1), ct.lengths(d2)
            j = jnp.arange(M)[None, :]
            x_ok = (j < l1[:, None]) & ~ct.elem_null_mask(s1)
            y_ok = (j < l2[:, None]) & ~ct.elem_null_mask(s2)
            lout = jnp.maximum(l1, l2)
            live = j < lout[:, None]
            cap = page.capacity
            rep_blocks = tuple(
                _Block(jnp.repeat(b.data, M, axis=0), jnp.repeat(b.valid, M),
                       b.type, b.dictionary)
                for b in page.blocks)
            lam_x = _Block(s1.reshape(cap * M).astype(t1.element.np_dtype),
                           x_ok.reshape(cap * M), t1.element)
            lam_y = _Block(s2.reshape(cap * M).astype(t2.element.np_dtype),
                           y_ok.reshape(cap * M), t2.element)
            epage = _Page(rep_blocks + (lam_x, lam_y),
                          jnp.repeat(page.row_mask, M))
            nb = len(page.blocks)
            body2 = _subst_lambda_vars(body, {x_slot: nb, y_slot: nb + 1})
            bd, bv = ExprCompiler.for_page(epage).compile(body2)(epage)
            storage = out_t.np_dtype
            sent = ct._null_const(storage)
            vals = jnp.where(live & bv.reshape(cap, M),
                             bd.reshape(cap, M).astype(storage), sent)
            out = jnp.concatenate(
                [lout[:, None].astype(storage), vals], axis=1)
            return out, v1 & v2

        return run

    def _compile_reduce(self, expr: Call) -> CompiledExpr:
        """reduce(arr, init, (s, x) -> comb, s -> out): the combiner
        unrolls over the static slot capacity — M body evaluations over
        full columns, XLA-fused; NULL elements bind as NULL
        (ReduceFunction)."""
        from presto_tpu.ops import container as ct
        from presto_tpu.page import Block as _Block, Page as _Page

        arr_f = self.compile(expr.args[0])
        init_f = self.compile(expr.args[1])
        t0 = expr.args[0].type
        st = expr.args[1].type
        comb_lam, out_lam = expr.args[2], expr.args[3]
        comb, out_body = comb_lam.body, out_lam.body
        s_slot, x_slot = comb_lam.params[0].slot, comb_lam.params[1].slot
        o_slot = out_lam.params[0].slot
        out_t = expr.type
        M = t0.max_elems

        def run(page):
            d, v = arr_f(page)
            sd, sv = init_f(page)
            sd = jnp.broadcast_to(sd, (page.capacity,)).astype(st.np_dtype)
            sv = jnp.broadcast_to(sv, (page.capacity,))
            slots = ct.elem_slots(d, t0)
            live = ct.slot_mask(d, M)
            nulls = ct.elem_null_mask(slots)
            nb = len(page.blocks)
            for i in range(M):
                elem = _Block(slots[:, i].astype(t0.element.np_dtype),
                              live[:, i] & ~nulls[:, i], t0.element)
                state = _Block(sd, sv, st)
                epage = _Page(page.blocks + (state, elem), page.row_mask)
                body2 = _subst_lambda_vars(comb, {s_slot: nb, x_slot: nb + 1})
                bd, bv = ExprCompiler.for_page(epage).compile(body2)(epage)
                has = live[:, i]
                sd = jnp.where(has, bd.astype(st.np_dtype), sd)
                sv = jnp.where(has, bv, sv)
            state = _Block(sd, sv, st)
            epage = _Page(page.blocks + (state,), page.row_mask)
            body3 = _subst_lambda_vars(out_body, {o_slot: nb})
            od, ov = ExprCompiler.for_page(epage).compile(body3)(epage)
            return od.astype(out_t.np_dtype), v & ov

        return run

    def _compile_array_lambda(self, expr: Call, arr_f, t0: Type) -> CompiledExpr:
        """Lambda functions over arrays (LambdaBytecodeGenerator +
        ArrayTransformFunction/ArrayFilterFunction analogs): the body
        evaluates ONCE over the flattened element lanes — rows repeat M
        times so outer-column references broadcast, and the lambda
        variable becomes an appended virtual channel.  Shapes stay
        static; XLA fuses the whole thing."""
        from presto_tpu.expr.ir import LambdaVar
        from presto_tpu.ops import container as ct
        from presto_tpu.page import Block as _Block, Page as _Page

        fn = expr.fn
        lam = expr.args[1]
        body, lam_slot = lam.body, lam.params[0].slot
        out_t = expr.type
        M = t0.max_elems
        elem_t = t0.element

        def substitute(e, var_index):
            return _subst_lambda_vars(e, {lam_slot: var_index})

        def run(page):
            d, v = arr_f(page)
            slots = ct.elem_slots(d, t0)
            live = ct.slot_mask(d, M)
            elem_ok = live & ~ct.elem_null_mask(slots)
            cap = page.capacity
            flat = slots.reshape(cap * M).astype(elem_t.np_dtype)
            rep_blocks = tuple(
                _Block(jnp.repeat(b.data, M, axis=0), jnp.repeat(b.valid, M),
                       b.type, b.dictionary)
                for b in page.blocks
            )
            lam = _Block(flat, elem_ok.reshape(cap * M), elem_t)
            epage = _Page(rep_blocks + (lam,), jnp.repeat(page.row_mask, M))
            body2 = substitute(body, len(page.blocks))
            bd, bv = ExprCompiler.for_page(epage).compile(body2)(epage)
            bd2 = bd.reshape(cap, M)
            bv2 = bv.reshape(cap, M)
            n_live = ct.lengths(d)

            if fn == "array_transform":
                storage = out_t.np_dtype
                sent = ct._null_const(storage)
                vals = jnp.where(live & bv2, bd2.astype(storage), sent)
                out = jnp.concatenate(
                    [n_live[:, None].astype(storage), vals], axis=1)
                return out, v
            if fn == "array_filter":
                keep = live & bv2 & bd2.astype(jnp.bool_)
                order = jnp.argsort(~keep, axis=1, stable=True)
                comp = jnp.take_along_axis(slots, order, axis=1)
                nkeep = jnp.sum(keep.astype(jnp.int64), axis=1)
                j = jnp.arange(M)[None, :]
                storage = t0.np_dtype
                sent = ct._null_const(storage)
                out_vals = jnp.where(j < nkeep[:, None], comp, sent)
                out = jnp.concatenate(
                    [nkeep[:, None].astype(storage), out_vals], axis=1)
                return out, v
            hit = live & bv2 & bd2.astype(jnp.bool_)
            if fn == "any_match":
                return jnp.any(hit, axis=1), v
            if fn == "none_match":
                return ~jnp.any(hit, axis=1), v
            # all_match: vacuously true on empty arrays; a null lambda
            # result counts false (deviation from 3-valued logic)
            ok = jnp.where(live, hit, True)
            return jnp.all(ok, axis=1), v

        return run

    def _compile_math(self, expr: Call) -> CompiledExpr:
        fn = expr.fn
        a = self.compile(expr.args[0])
        ta = expr.args[0].type

        if ta.is_long_decimal:
            from presto_tpu.ops import decimal128 as d128

            if fn == "abs":
                def run_labs(page):
                    d, v = a(page)
                    neg = d[..., 0] < 0
                    return _where_rows(neg, d128.neg(d), d), v

                return run_labs
            if fn == "sign":
                def run_lsign(page):
                    d, v = a(page)
                    hi = d[..., 0]
                    nonzero = jnp.any(d != 0, axis=-1)
                    s = jnp.where(hi < 0, -1,
                                  jnp.where(nonzero, 1, 0))
                    return s.astype(jnp.int64), v

                return run_lsign
            # silently-wrong elementwise limb math is worse than an error
            raise ValueError(f"{fn} on long decimals unsupported (cast first)")

        if fn in ("power", "pow", "atan2"):
            b = self.compile(expr.args[1])
            tb = expr.args[1].type
            op = jnp.power if fn in ("power", "pow") else jnp.arctan2

            def run_pow(page):
                (da, va), (db, vb) = a(page), b(page)
                return op(_to_double(da, ta), _to_double(db, tb)), va & vb

            return run_pow

        if fn == "width_bucket":
            args = [self.compile(x) for x in expr.args]
            ts = [x.type for x in expr.args]

            def run_wb(page):
                (x, vx), (lo, vlo), (hi, vhi), (n, vn) = [f(page) for f in args]
                xd = _to_double(x, ts[0])
                lod = _to_double(lo, ts[1])
                hid = _to_double(hi, ts[2])
                nb = n.astype(jnp.int64)
                frac = (xd - lod) / jnp.where(hid == lod, 1.0, hid - lod)
                b = jnp.floor(frac * nb.astype(jnp.float64)).astype(jnp.int64) + 1
                b = jnp.clip(b, 0, nb + 1)
                return b, vx & vlo & vhi & vn

            return run_wb

        if fn == "round" and len(expr.args) > 1:
            digits = expr.args[1].value
        else:
            digits = 0

        def run_math(page):
            da, va = a(page)
            if fn == "abs":
                if jnp.issubdtype(da.dtype, jnp.integer):
                    # |INT_MIN| wraps in place; NULL that lane
                    # (deviation: the reference raises)
                    va = va & jnp.logical_not(_ovf_neg(da))
                return jnp.abs(da), va
            if fn == "sign":
                return jnp.sign(_to_double(da, ta)).astype(jnp.int64), va
            if fn in _UNARY_DOUBLE_FNS:
                return _UNARY_DOUBLE_FNS[fn](_to_double(da, ta)), va
            if fn == "truncate":
                x = _to_double(da, ta)
                return jnp.trunc(x), va
            if fn in ("ceil", "ceiling", "floor"):
                up = fn in ("ceil", "ceiling")
                if ta.is_decimal:
                    # scaled-int ceil/floor: // floors for any sign
                    s = 10 ** ta.scale
                    q = (da + (s - 1)) // s if up else da // s
                    return q.astype(jnp.int64), va
                if ta.name == "double":
                    return (jnp.ceil(da) if up else jnp.floor(da)), va
                return da, va
            if fn == "round":
                if ta.is_decimal:
                    drop = ta.scale - min(digits, ta.scale)
                    if drop <= 0:
                        return da, va
                    p = 10 ** drop
                    half = p // 2
                    q = jnp.where(da >= 0, (da + half) // p, -((-da + half) // p))
                    return q, va
                if ta.name == "double":
                    m = 10.0 ** digits
                    x = da * m
                    r = jnp.where(x >= 0, jnp.floor(x + 0.5), jnp.ceil(x - 0.5))
                    return r / m, va
                return da, va
            raise KeyError(fn)

        return run_math

    def _compile_operand(self, e: Expr, out_t: Type) -> CompiledExpr:
        """Compile an argument in the context of a raw-string result:
        dictionary-typed string literals encode to byte rows."""
        if out_t.is_raw_string and isinstance(e, Literal) and e.type.is_string \
                and not e.type.is_raw_string:
            from presto_tpu.ops import rawstring as rs

            width = out_t.value_shape[0]
            lit = rs.encode_literal(str(e.value), width)
            null = e.value is None

            def run_rawlit(page):
                n = page.capacity
                return (jnp.broadcast_to(lit[None, :], (n, width)),
                        jnp.zeros(n, jnp.bool_) if null else jnp.ones(n, jnp.bool_))

            return run_rawlit
        return self.compile(e)

    def _compile_bitwise(self, expr: Call) -> CompiledExpr:
        """Two's-complement bitwise scalars over int64 lanes
        (operator/scalar/BitwiseFunctions.java).  Shifts and bit_count
        take a literal `bits` width and operate on the value's low
        `bits` as an unsigned field (the reference's contract)."""
        fn = expr.fn
        fns = [self.compile(a) for a in expr.args
               if not (fn in ("bitwise_shift_left", "bitwise_shift_right",
                              "bit_count") and a is expr.args[-1])]
        bits = None
        if fn in ("bitwise_shift_left", "bitwise_shift_right", "bit_count"):
            blit = expr.args[-1]
            if not isinstance(blit, Literal) or blit.value is None:
                raise ValueError(f"{fn} bits must be a literal")
            bits = int(blit.value)
            if not 2 <= bits <= 64:
                raise ValueError(f"{fn} bits must be in [2, 64]")

        def run_bitwise(page):
            vals = [f(page) for f in fns]
            v = vals[0][1]
            for _, vv in vals[1:]:
                v = v & vv
            a = vals[0][0].astype(jnp.int64)
            if fn == "bitwise_not":
                return ~a, v
            if fn in ("bitwise_and", "bitwise_or", "bitwise_xor"):
                b = vals[1][0].astype(jnp.int64)
                out = {"bitwise_and": a & b, "bitwise_or": a | b,
                       "bitwise_xor": a ^ b}[fn]
                return out, v
            ua = a.astype(jnp.uint64)
            if bits < 64:
                ua = ua & jnp.uint64((1 << bits) - 1)
            if fn == "bit_count":
                return jax.lax.population_count(ua).astype(jnp.int64), v
            # Java shift semantics (the reference's engine): the shift
            # amount wraps mod 64, so shift 64 is a no-op and -1 acts
            # as 63 — mask, don't clamp
            s = (vals[1][0].astype(jnp.int64) & 63).astype(jnp.uint64)
            out = jnp.left_shift(ua, s) if fn == "bitwise_shift_left" \
                else jnp.right_shift(ua, s)
            if bits < 64:
                out = out & jnp.uint64((1 << bits) - 1)
            return out.astype(jnp.int64), v

        return run_bitwise

    def _compile_greatest_least(self, expr: Call) -> CompiledExpr:
        out_t = expr.type
        parts = [(self._compile_operand(x, out_t), x.type) for x in expr.args]
        take_max = expr.fn == "greatest"

        def run_gl(page):
            data = None
            valid = None
            for cf, t in parts:
                d, v = cf(page)
                d = self._coerce(d, t, out_t)
                if data is None:
                    data, valid = d, v
                elif out_t.is_long_decimal:
                    from presto_tpu.ops import decimal128 as d128

                    lt, _, _ = d128.compare(d, data)
                    take_d = ~lt if take_max else lt  # ties keep either
                    data = _where_rows(take_d, d, data)
                    valid = valid & v
                elif out_t.is_raw_string:
                    from presto_tpu.ops import rawstring as rs

                    lt, eq = rs.compare(d, data)
                    take_d = ~(lt | eq) if take_max else lt
                    data = _where_rows(take_d, d, data)
                    valid = valid & v
                else:
                    data = jnp.maximum(data, d) if take_max else jnp.minimum(data, d)
                    valid = valid & v  # NULL if any argument is NULL (Presto)
            return data, valid

        return run_gl

    # ------------------------------------------------------------------
    def _compile_literal(self, expr: Literal) -> CompiledExpr:
        t = expr.type
        if t.is_string and expr.value is not None:
            # projected constant: code 0 of the literal's singleton
            # dictionary (expr_dictionary supplies the mapping)
            def run_const_str(page):
                n = page.capacity
                return (jnp.zeros(n, dtype=jnp.int32),
                        jnp.ones(n, dtype=jnp.bool_))

            return run_const_str
        val = expr.value
        if val is None:

            def run_null(page):
                n = page.capacity
                return (
                    jnp.zeros((n,) + t.value_shape, dtype=t.np_dtype),
                    jnp.zeros(n, dtype=jnp.bool_),
                )

            return run_null

        if t.is_long_decimal:
            from presto_tpu.ops.decimal128 import encode_py

            limbs = encode_py([int(val)], 1,
                              limbs=expr.type.value_shape[0])[0]

            width = expr.type.value_shape[0]

            def run_llit(page):
                n = page.capacity
                return (
                    jnp.broadcast_to(jnp.asarray(limbs), (n, width)),
                    jnp.ones(n, dtype=jnp.bool_),
                )

            return run_llit

        def run_lit(page):
            n = page.capacity
            return (
                jnp.full(n, val, dtype=t.np_dtype),
                jnp.ones(n, dtype=jnp.bool_),
            )

        return run_lit

    def _compile_logic(self, expr: Call) -> CompiledExpr:
        a, b = [self.compile(x) for x in expr.args]
        is_and = expr.fn == "and"

        def run_logic(page):
            (da, va), (db, vb) = a(page), b(page)
            if is_and:
                # false AND anything = false; else null if any null
                false_a = va & jnp.logical_not(da)
                false_b = vb & jnp.logical_not(db)
                definite_false = false_a | false_b
                valid = (va & vb) | definite_false
                data = jnp.logical_not(definite_false) & da & db
            else:
                true_a = va & da
                true_b = vb & db
                definite_true = true_a | true_b
                valid = (va & vb) | definite_true
                data = definite_true | (da | db)
            return data, valid

        return run_logic

    def _string_code(self, column: Expr, s: str) -> int:
        d = self._dict_of(column)
        if d is None:
            raise ValueError(f"no dictionary for string column {column}")
        return d.code_of(s)

    def _dict_of(self, e: Expr) -> Optional[Dictionary]:
        return expr_dictionary(e, self.dictionaries)

    def _compile_cmp(self, expr: Call) -> CompiledExpr:
        lhs, rhs = expr.args
        # string comparison -> dictionary codes (eq/ne direct; ordered
        # comparisons use a host-side rank LUT since codes aren't sorted)
        if lhs.type.is_string or rhs.type.is_string:
            return self._compile_string_cmp(expr)
        a, b = self.compile(lhs), self.compile(rhs)
        ta, tb = lhs.type, rhs.type
        op = expr.fn

        if (ta.is_long_decimal or tb.is_long_decimal) \
                and "double" not in (ta.name, tb.name):
            # (a double operand compares in double space via _align_pair)
            from presto_tpu.ops import decimal128 as d128

            s = max(ta.scale if ta.is_decimal else 0, tb.scale if tb.is_decimal else 0)

            def run_lcmp(page):
                (da, va), (db, vb) = a(page), b(page)
                w = _decimal_limbs(ta, tb)
                la = _to_long_limbs(da, ta, ta.scale if ta.is_decimal else 0,
                                    s, limbs=w)
                lb = _to_long_limbs(db, tb, tb.scale if tb.is_decimal else 0,
                                    s, limbs=w)
                lt, eq, gt = d128.compare(la, lb)
                d = {"eq": eq, "ne": ~eq, "lt": lt, "le": lt | eq,
                     "gt": gt, "ge": gt | eq}[op]
                return d, va & vb

            return run_lcmp

        def run_cmp(page):
            (da, va), (db, vb) = a(page), b(page)
            da, db = self._align_pair(da, ta, db, tb)
            d = {
                "eq": lambda: da == db,
                "ne": lambda: da != db,
                "lt": lambda: da < db,
                "le": lambda: da <= db,
                "gt": lambda: da > db,
                "ge": lambda: da >= db,
            }[op]()
            return d, va & vb

        return run_cmp

    def _compile_string_cmp(self, expr: Call) -> CompiledExpr:
        lhs, rhs = expr.args
        op = expr.fn
        if lhs.type.is_raw_string or rhs.type.is_raw_string:
            return self._compile_raw_cmp(expr)
        if isinstance(rhs, Literal):
            colref, s = lhs, rhs.value
        elif isinstance(lhs, Literal):
            colref, s = rhs, lhs.value
            op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}.get(op, op)
        else:
            # col-col string compare: only eq/ne on same dictionary
            a, b = self.compile(lhs), self.compile(rhs)
            da_ = self._dict_of(lhs)
            db_ = self._dict_of(rhs)
            if da_ is None or db_ is None or op not in ("eq", "ne"):
                # ordered col-col comparison would need a merged
                # collation — unsupported, not silently wrong
                raise ValueError(
                    f"string column {op} comparison unsupported")
            # canonical-value-id comparison: both sides' codes map to a
            # shared value-id space host-side (the DictionaryBlock
            # id-remap analog). Robust to duplicate values in derived
            # dictionaries (upper/substr map many codes to one value).
            canon: dict = {}
            lut_a = jnp.asarray(
                [canon.setdefault(v, len(canon)) for v in da_.values],
                dtype=jnp.int32)
            lut_b = jnp.asarray(
                [canon.setdefault(v, len(canon)) for v in db_.values],
                dtype=jnp.int32)

            def run_cc(page):
                (da, va), (db, vb) = a(page), b(page)
                ca = lut_a[jnp.clip(da, 0, lut_a.shape[0] - 1)]
                cb = lut_b[jnp.clip(db, 0, lut_b.shape[0] - 1)]
                d = (ca == cb) if op == "eq" else (ca != cb)
                return d, va & vb

            return run_cc
        cf = self.compile(colref)
        d = self._dict_of(colref)
        if op in ("eq", "ne"):
            # LUT, not code equality: derived dictionaries (substr) may
            # map many codes to the same value
            if d is None:
                raise ValueError(f"no dictionary for string column {colref}")
            want_eq = op == "eq"
            lut = jnp.asarray(d.lut(lambda v: (v == s) == want_eq))

            def run_eq(page):
                dd, v = cf(page)
                return lut[jnp.clip(dd, 0, lut.shape[0] - 1)], v

            return run_eq
        # ordered: LUT of predicate over dictionary values
        import operator as _op

        cmpf = {"lt": _op.lt, "le": _op.le, "gt": _op.gt, "ge": _op.ge}[op]
        if d is None:
            raise ValueError(f"no dictionary for string column {colref}")
        lut = jnp.asarray(d.lut(lambda v: cmpf(v, s)))

        def run_ord(page):
            dd, v = cf(page)
            return lut[jnp.clip(dd, 0, lut.shape[0] - 1)], v

        return run_ord

    # ------------------------------------------------------------------
    # raw (non-dictionary) varchar paths
    # ------------------------------------------------------------------

    def _compile_raw_cmp(self, expr: Call) -> CompiledExpr:
        from presto_tpu.ops import rawstring as rs

        lhs, rhs = expr.args
        op = expr.fn
        if isinstance(rhs, Literal):
            col, lit = lhs, rhs
        elif isinstance(lhs, Literal):
            col, lit = rhs, lhs
            op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}.get(op, op)
        else:
            if not (lhs.type.is_raw_string and rhs.type.is_raw_string):
                raise ValueError("raw-vs-dictionary string comparison unsupported")
            a, b = self.compile(lhs), self.compile(rhs)

            def run_rcc(page):
                (da, va), (db, vb) = a(page), b(page)
                lt, eq = rs.compare(da, db)
                d = {"eq": eq, "ne": ~eq, "lt": lt, "le": lt | eq,
                     "gt": ~(lt | eq), "ge": ~lt}[op]
                return d, va & vb

            return run_rcc
        cf = self.compile(col)
        width = col.type.value_shape[0]
        lit_bytes = rs.encode_literal(str(lit.value), max(width, len(str(lit.value).encode())))

        def run_rcl(page):
            d, v = cf(page)
            lt, eq = rs.compare(d, lit_bytes[None, :])
            out = {"eq": eq, "ne": ~eq, "lt": lt, "le": lt | eq,
                   "gt": ~(lt | eq), "ge": ~lt}[op]
            return out, v

        return run_rcl

    def _compile_raw_transform(self, expr: Call) -> CompiledExpr:
        """Value transforms on raw varchar: substr/upper/lower run on
        device; everything else reuses the host transform through a
        per-page callback."""
        from presto_tpu.ops import rawstring as rs

        fn = expr.fn
        col = _transform_column(expr)
        if col is None:
            raise KeyError(f"cannot compile string transform {expr}")
        cf = self.compile(col)
        if fn == "substr":
            start = int(expr.args[1].value)
            length = int(expr.args[2].value) if len(expr.args) > 2 else None
            return lambda page: ((lambda dv: (rs.substr_chars(dv[0], start, length), dv[1]))(cf(page)))
        if fn in ("upper", "lower"):
            up = fn == "upper"
            return lambda page: ((lambda dv: (rs.change_case(dv[0], up), dv[1]))(cf(page)))
        tf = _string_transform(expr)
        if tf is None:
            raise KeyError(f"cannot compile string transform {expr}")
        f, _ = tf
        width = expr.type.value_shape[0]

        def run_cb(page):
            d, v = cf(page)

            def cb(arr):
                vals = [f(s) for s in rs.decode_strings(arr)]
                data = rs.encode_strings(["" if x is None else x for x in vals], width)
                nulls = np.asarray([x is None for x in vals], dtype=np.bool_)
                return data, nulls

            out, nulls = jax.pure_callback(
                cb,
                (jax.ShapeDtypeStruct(d.shape[:-1] + (width,), jnp.uint8),
                 jax.ShapeDtypeStruct(d.shape[:-1], jnp.bool_)),
                d, vmap_method="sequential",
            )
            return out, v & ~nulls

        return run_cb

    def _compile_raw_bool(self, expr: Call) -> CompiledExpr:
        """LIKE/regexp_like/starts_with/ends_with on raw varchar via the
        host-predicate callback."""
        from presto_tpu.ops import rawstring as rs

        fn = expr.fn
        colref = expr.args[0]
        cf = self.compile(colref)
        if fn == "like":
            rx = _like_to_regex(expr.args[1].value)
            pred = lambda s: rx.match(s) is not None
        elif fn == "regexp_like":
            rx = re.compile(expr.args[1].value)
            pred = lambda s: rx.search(s) is not None
        elif fn == "starts_with":
            prefix = expr.args[1].value
            pred = lambda s: s.startswith(prefix)
        else:
            suffix = expr.args[1].value
            pred = lambda s: s.endswith(suffix)
        runner = rs.host_predicate(pred)

        def run_rb(page):
            d, v = cf(page)
            return runner(d), v

        return run_rb

    def _compile_raw_int_fn(self, expr: Call) -> CompiledExpr:
        from presto_tpu.ops import rawstring as rs

        fn = expr.fn
        cf = self.compile(expr.args[0])
        if fn == "length":
            # code points, matching the dictionary path (byte counts
            # diverge on non-ASCII; rs.lengths stays the internal
            # byte-level helper)
            runner_pred = len
        elif fn == "strpos":
            needle = expr.args[1].value
            runner_pred = lambda s: s.find(needle) + 1
        elif fn == "codepoint":
            runner_pred = lambda s: ord(s[0]) if s else 0
        else:
            raise KeyError(fn)

        def run_ri(page):
            d, v = cf(page)

            def cb(arr):
                return np.asarray([runner_pred(s) for s in rs.decode_strings(arr)],
                                  dtype=np.int64)

            out = jax.pure_callback(
                cb, jax.ShapeDtypeStruct(d.shape[:-1], jnp.int64), d,
                vmap_method="sequential",
            )
            return out, v

        return run_ri

    def _compile_raw_concat(self, expr: Call) -> CompiledExpr:
        from presto_tpu.ops import rawstring as rs

        parts = []
        for a in expr.args:
            if isinstance(a, Literal):
                lit = rs.encode_literal(str(a.value), len(str(a.value).encode()) or 1)
                parts.append(("lit", lit))
            elif a.type.is_raw_string:
                parts.append(("col", self.compile(a)))
            else:
                raise ValueError("concat mixes raw and dictionary strings")

        def run_rcat(page):
            data = None
            valid = None
            for kind, p in parts:
                if kind == "lit":
                    d = jnp.broadcast_to(p[None, :], (page.capacity, p.shape[0]))
                    v = jnp.ones(page.capacity, dtype=jnp.bool_)
                else:
                    d, v = p(page)
                if data is None:
                    data, valid = d, v
                else:
                    data = rs.concat(data, d)
                    valid = valid & v
            return data, valid

        return run_rcat

    def _compile_like(self, expr: Call) -> CompiledExpr:
        colref, pat = expr.args
        assert isinstance(pat, Literal), "LIKE pattern must be a literal"
        if colref.type.is_raw_string:
            return self._compile_raw_bool(expr)
        cf = self.compile(colref)
        d = self._dict_of(colref)
        if d is None:
            raise ValueError(f"no dictionary for string column {colref}")
        rx = _like_to_regex(pat.value)
        lut = jnp.asarray(d.lut(lambda v: rx.match(v) is not None))

        def run_like(page):
            dd, v = cf(page)
            return lut[jnp.clip(dd, 0, lut.shape[0] - 1)], v

        return run_like

    def _compile_in(self, expr: Call) -> CompiledExpr:
        colref = expr.args[0]
        values = expr.args[1:]
        cf = self.compile(colref)
        if colref.type.is_raw_string:
            from presto_tpu.ops import rawstring as rs

            lits = [rs.encode_literal(
                str(v.value),
                max(colref.type.value_shape[0], len(str(v.value).encode())))
                for v in values]

            def run_in_raw(page):
                d, v = cf(page)
                hit = jnp.zeros(page.capacity, dtype=jnp.bool_)
                for lb in lits:
                    _, eq = rs.compare(d, lb[None, :])
                    hit = hit | eq
                return hit, v

            return run_in_raw
        if colref.type.is_string:
            d = self._dict_of(colref)
            if d is None:
                raise ValueError(f"no dictionary for string column {colref}")
            wanted = {v.value for v in values}
            lut = jnp.asarray(d.lut(lambda s: s in wanted))

            def run_in_str(page):
                dd, v = cf(page)
                return lut[jnp.clip(dd, 0, lut.shape[0] - 1)], v

            return run_in_str
        lits = [v.value for v in values]

        def run_in(page):
            dd, v = cf(page)
            hit = jnp.zeros(dd.shape, dtype=jnp.bool_)
            for c in lits:
                hit = hit | (dd == c)
            return hit, v

        return run_in

    def _compile_arith(self, expr: Call) -> CompiledExpr:
        lhs, rhs = expr.args
        a, b = self.compile(lhs), self.compile(rhs)
        ta, tb, tr = lhs.type, rhs.type, expr.type
        op = expr.fn
        # proven by the plan's intervals: no guard of this call can
        # fire, so none is emitted (analysis/ranges.site_proven)
        free = self._guard_free(expr)

        def run_arith(page):
            (da, va), (db, vb) = a(page), b(page)
            valid = va & vb
            if tr.name == "real":
                da2 = _to_double(da, ta).astype(jnp.float32)
                db2 = _to_double(db, tb).astype(jnp.float32)
                d = {
                    "add": lambda: da2 + db2,
                    "sub": lambda: da2 - db2,
                    "mul": lambda: da2 * db2,
                    "div": lambda: da2 / jnp.where(db2 == 0, 1.0, db2),
                    "mod": lambda: jnp.mod(da2, jnp.where(db2 == 0, 1.0, db2)),
                }[op]()
                if op in ("div", "mod") and not free:
                    valid = valid & (db2 != 0)
                return d, valid
            if tr.name == "double":
                da2, db2 = _to_double(da, ta), _to_double(db, tb)
                d = {
                    "add": lambda: da2 + db2,
                    "sub": lambda: da2 - db2,
                    "mul": lambda: da2 * db2,
                    "div": lambda: da2 / jnp.where(db2 == 0, 1.0, db2),
                    "mod": lambda: jnp.mod(da2, jnp.where(db2 == 0, 1.0, db2)),
                }[op]()
                if op in ("div", "mod") and not free:
                    valid = valid & (db2 != 0)
                return d, valid
            if tr.is_long_decimal:
                from presto_tpu.ops import decimal128 as d128

                sa = ta.scale if ta.is_decimal else 0
                sb = tb.scale if tb.is_decimal else 0
                if op == "mul":
                    # long x short: exact (result scale = sa + sb);
                    # long x long products exceed p=36
                    if ta.is_long_decimal and not tb.is_long_decimal:
                        return d128.mul_long_short(da, db.astype(jnp.int64)), valid
                    if tb.is_long_decimal and not ta.is_long_decimal:
                        return d128.mul_long_short(db, da.astype(jnp.int64)), valid
                    raise ValueError("long-decimal x long-decimal mul unsupported")
                w = _decimal_limbs(ta, tb, tr)
                da2 = _to_long_limbs(da, ta, sa, tr.scale, limbs=w)
                db2 = _to_long_limbs(db, tb, sb, tr.scale, limbs=w)
                d = {
                    "add": lambda: d128.add(da2, db2),
                    "sub": lambda: d128.sub(da2, db2),
                }.get(op)
                if d is None:
                    raise ValueError(f"long-decimal {op} unsupported")
                return d(), valid
            if tr.is_decimal:
                sa = ta.scale if ta.is_decimal else 0
                sb = tb.scale if tb.is_decimal else 0
                da2 = da.astype(jnp.int64)
                db2 = db.astype(jnp.int64)
                if op == "mul":
                    d = da2 * db2  # scale sa+sb == tr.scale
                    if not free:
                        valid = valid & jnp.logical_not(_ovf_mul(da2, db2, d))
                else:
                    da2, oa = _rescale_guard(da2, sa, tr.scale)
                    db2, ob = _rescale_guard(db2, sb, tr.scale)
                    if not free:
                        valid = valid & jnp.logical_not(oa | ob)
                    d = {
                        "add": lambda: da2 + db2,
                        "sub": lambda: da2 - db2,
                        "mod": lambda: _trunc_mod(da2, db2),
                    }[op]()
                    if free:
                        return d, valid
                    if op == "add":
                        valid = valid & jnp.logical_not(_ovf_add(da2, db2, d))
                    elif op == "sub":
                        valid = valid & jnp.logical_not(_ovf_sub(da2, db2, d))
                    elif op == "mod":
                        valid = valid & (db2 != 0)
                return d, valid
            # integer arithmetic (SQL truncating div/mod); wrapped
            # add/sub/mul lanes NULL (deviation: reference raises)
            d = {
                "add": lambda: da + db,
                "sub": lambda: da - db,
                "mul": lambda: da * db,
                "div": lambda: _trunc_div(da, db),
                "mod": lambda: _trunc_mod(da, db),
            }[op]()
            if free and d.dtype == tr.np_dtype:
                # the proof is of the declared lane (``site_proven``
                # refuses operand types that promote to another; this
                # is the same for the lanes as compiled)
                return d, valid
            if op == "add":
                valid = valid & jnp.logical_not(_ovf_add(da, db, d))
            elif op == "sub":
                valid = valid & jnp.logical_not(_ovf_sub(da, db, d))
            elif op == "mul":
                valid = valid & jnp.logical_not(_ovf_mul(da, db, d))
            elif op == "div":
                imin = jnp.iinfo(d.dtype).min
                valid = valid & (db != 0) \
                    & jnp.logical_not((da == imin) & (db == -1))
            elif op == "mod":
                valid = valid & (db != 0)
            return d, valid

        return run_arith

    def _compile_datepart(self, expr: Call) -> CompiledExpr:
        (a,) = [self.compile(x) for x in expr.args]
        part = expr.fn
        is_ts = expr.args[0].type.name == "timestamp"

        def run_datepart(page):
            d, v = a(page)
            if is_ts:
                micros = d.astype(jnp.int64)
                days = micros // MICROS_PER_DAY
                tod = micros - days * MICROS_PER_DAY
                if part in ("hour", "minute", "second", "millisecond"):
                    out = {
                        "hour": tod // 3_600_000_000,
                        "minute": (tod // 60_000_000) % 60,
                        "second": (tod // 1_000_000) % 60,
                        "millisecond": (tod // 1_000) % 1000,
                    }[part]
                    return out.astype(jnp.int64), v
            else:
                days = d.astype(jnp.int64)
                if part in ("hour", "minute", "second", "millisecond"):
                    return jnp.zeros_like(days), v
            y, m, day = _civil_from_days(days)
            if part in ("year", "month", "day"):
                out = {"year": y, "month": m, "day": day}[part]
            elif part == "quarter":
                out = (m - 1) // 3 + 1
            elif part == "day_of_week":
                # ISO: Monday=1..Sunday=7; 1970-01-01 was a Thursday
                out = (days + 3) % 7 + 1
            elif part == "day_of_year":
                jan1 = days - _days_from_civil(y, jnp.ones_like(m), jnp.ones_like(day))
                out = jan1 + 1
            elif part in ("week", "year_of_week"):
                # ISO 8601: the week containing a date's Thursday
                # belongs to the Thursday's civil year (the reference's
                # Joda weekOfWeekyear/weekyear)
                th = days - (days + 3) % 7 + 3
                y_th, _, _ = _civil_from_days(th)
                if part == "year_of_week":
                    out = y_th
                else:
                    jan1 = _days_from_civil(
                        y_th, jnp.ones_like(m), jnp.ones_like(day))
                    out = (th - jan1) // 7 + 1
            elif part == "last_day_of_month":
                nxt_y = jnp.where(m == 12, y + 1, y)
                nxt_m = jnp.where(m == 12, 1, m + 1)
                out = _days_from_civil(nxt_y, nxt_m, jnp.ones_like(day)) - 1
            else:
                raise KeyError(part)
            return out.astype(jnp.int64), v

        return run_datepart

    def _compile_datetime(self, expr: Call) -> CompiledExpr:
        """Timestamp/date kernels (reference: operator/scalar/DateTimeFunctions.java;
        here vectorized integer civil-calendar math on device).

        Deviation from the reference's Joda-based date_diff('month'|'year'):
        this engine counts calendar-field differences ((y2*12+m2)-(y1*12+m1)),
        not complete elapsed periods."""
        fn = expr.fn

        if fn in ("date_trunc", "date_add", "date_diff"):
            unit_lit = expr.args[0]
            if not isinstance(unit_lit, Literal):
                raise KeyError(f"{fn}: unit must be a literal")
            unit = str(unit_lit.value).lower().rstrip("s")
            arg_fs = [self.compile(x) for x in expr.args[1:]]
            arg_ts = [x.type for x in expr.args[1:]]
            if fn == "date_trunc":
                return self._datetime_trunc(unit, arg_fs[0], arg_ts[0])
            if fn == "date_add":
                return self._datetime_add(unit, arg_fs[0], arg_fs[1], arg_ts[1])
            return self._datetime_diff(unit, arg_fs, arg_ts)

        (afn,) = [self.compile(x) for x in expr.args[:1]]
        t0 = expr.args[0].type
        if fn == "cast_timestamp":
            def run(page):
                d, v = afn(page)
                if t0.name == "date":
                    return d.astype(jnp.int64) * MICROS_PER_DAY, v
                return d.astype(jnp.int64), v
            return run
        if fn == "cast_date":
            def run(page):
                d, v = afn(page)
                if t0.name == "timestamp":
                    return (d.astype(jnp.int64) // MICROS_PER_DAY).astype(jnp.int32), v
                return d.astype(jnp.int32), v
            return run
        if fn == "to_unixtime":
            def run(page):
                d, v = afn(page)
                micros = d.astype(jnp.float64)
                if t0.name == "date":
                    micros = micros * MICROS_PER_DAY
                return micros / 1e6, v
            return run
        if fn == "from_unixtime":
            def run(page):
                d, v = afn(page)
                return (_to_double(d, t0) * 1e6).astype(jnp.int64), v
            return run
        if fn == "ts_add_micros":
            bfn = self.compile(expr.args[1])
            def run(page):
                (da, va), (db, vb) = afn(page), bfn(page)
                return da.astype(jnp.int64) + db.astype(jnp.int64), va & vb
            return run
        if fn in ("ts_add_months", "date_add_months"):
            bfn = self.compile(expr.args[1])
            if fn == "ts_add_months":
                def run(page):
                    (da, va), (db, vb) = afn(page), bfn(page)
                    micros = da.astype(jnp.int64)
                    days = micros // MICROS_PER_DAY
                    tod = micros - days * MICROS_PER_DAY
                    return _add_months(days, db) * MICROS_PER_DAY + tod, va & vb
            else:
                def run(page):
                    (da, va), (db, vb) = afn(page), bfn(page)
                    return _add_months(da.astype(jnp.int64), db).astype(jnp.int32), va & vb
            return run
        raise KeyError(fn)

    def _datetime_trunc(self, unit: str, f, t: Type) -> CompiledExpr:
        is_ts = t.name == "timestamp"

        def run_trunc(page):
            d, v = f(page)
            if is_ts:
                micros = d.astype(jnp.int64)
                step = {"second": 1_000_000, "minute": 60_000_000,
                        "hour": 3_600_000_000, "day": MICROS_PER_DAY}.get(unit)
                if step is not None:
                    return (micros // step) * step, v
                days = micros // MICROS_PER_DAY
            else:
                days = d.astype(jnp.int64)
                if unit in ("second", "minute", "hour", "day"):
                    return d, v
            y, m, _day = _civil_from_days(days)
            one = jnp.ones_like(m)
            if unit == "week":
                dow = (days + 3) % 7  # Monday=0
                out_days = days - dow
            elif unit == "month":
                out_days = _days_from_civil(y, m, one)
            elif unit == "quarter":
                qm = ((m - 1) // 3) * 3 + 1
                out_days = _days_from_civil(y, qm, one)
            elif unit == "year":
                out_days = _days_from_civil(y, one, one)
            else:
                raise KeyError(f"date_trunc unit {unit}")
            if is_ts:
                return out_days * MICROS_PER_DAY, v
            return out_days.astype(jnp.int32), v

        return run_trunc

    def _datetime_add(self, unit: str, nf, xf, t: Type) -> CompiledExpr:
        is_ts = t.name == "timestamp"
        micros_per = {"millisecond": 1_000, "second": 1_000_000,
                      "minute": 60_000_000, "hour": 3_600_000_000,
                      "day": MICROS_PER_DAY, "week": 7 * MICROS_PER_DAY}

        def run_add(page):
            (dn, vn), (dx, vx) = nf(page), xf(page)
            valid = vn & vx
            n = dn.astype(jnp.int64)
            if is_ts:
                micros = dx.astype(jnp.int64)
                if unit in micros_per:
                    return micros + n * micros_per[unit], valid
                days = micros // MICROS_PER_DAY
                tod = micros - days * MICROS_PER_DAY
                months = n * (12 if unit == "year" else 3 if unit == "quarter" else 1)
                return _add_months(days, months) * MICROS_PER_DAY + tod, valid
            days = dx.astype(jnp.int64)
            if unit == "day":
                return (days + n).astype(jnp.int32), valid
            if unit == "week":
                return (days + 7 * n).astype(jnp.int32), valid
            if unit in ("month", "quarter", "year"):
                months = n * (12 if unit == "year" else 3 if unit == "quarter" else 1)
                return _add_months(days, months).astype(jnp.int32), valid
            raise KeyError(f"date_add unit {unit} on date")

        return run_add

    def _datetime_diff(self, unit: str, fs, ts_) -> CompiledExpr:
        micros_per = {"millisecond": 1_000, "second": 1_000_000,
                      "minute": 60_000_000, "hour": 3_600_000_000,
                      "day": MICROS_PER_DAY, "week": 7 * MICROS_PER_DAY}

        def to_micros(d, t):
            d = d.astype(jnp.int64)
            return d * MICROS_PER_DAY if t.name == "date" else d

        def run_diff(page):
            (d1, v1), (d2, v2) = fs[0](page), fs[1](page)
            valid = v1 & v2
            m1, m2 = to_micros(d1, ts_[0]), to_micros(d2, ts_[1])
            if unit in micros_per:
                return _trunc_div(m2 - m1, jnp.asarray(micros_per[unit], jnp.int64)), valid
            y1, mo1, _ = _civil_from_days(m1 // MICROS_PER_DAY)
            y2, mo2, _ = _civil_from_days(m2 // MICROS_PER_DAY)
            months = (y2 * 12 + mo2) - (y1 * 12 + mo1)
            if unit == "month":
                out = months
            elif unit == "quarter":
                out = _trunc_div(months, jnp.asarray(3, months.dtype))
            elif unit == "year":
                out = y2 - y1
            else:
                raise KeyError(f"date_diff unit {unit}")
            return out.astype(jnp.int64), valid

        return run_diff

    def _is_dict_string_case(self, expr: Call) -> bool:
        t = expr.type
        return (getattr(t, "is_string", False)
                and not getattr(t, "is_raw_string", False))

    def _compile_string_case(self, expr: Call) -> CompiledExpr:
        """case/if/coalesce producing dictionary varchar: each branch's
        codes remap into the union dictionary (merged_string_dictionary
        — the channel metadata layer attaches the same object), so
        SELECT CASE ... THEN 'big' ELSE 'small' END decodes correctly
        instead of emitting branch-local code 0s."""
        merged = merged_string_dictionary(expr, self.dictionaries)
        if merged is None:
            raise ValueError(
                "string-valued case/if/coalesce branch has no resolvable "
                "dictionary")
        index = {v: i for i, v in enumerate(merged.values)}

        def branch_fn(b: Expr) -> CompiledExpr:
            if isinstance(b, Literal):
                code = index.get(b.value, 0)
                ok = b.value is not None

                def run_lit(page, code=code, ok=ok):
                    n = page.capacity
                    return (jnp.full(n, code, dtype=jnp.int32),
                            jnp.full(n, ok, dtype=jnp.bool_))

                return run_lit
            inner = self.compile(b)
            bdict = expr_dictionary(b, self.dictionaries)
            lut = jnp.asarray(
                [index.get(v, 0) for v in bdict.values], dtype=jnp.int32)

            def run_remap(page, inner=inner, lut=lut):
                d, v = inner(page)
                codes = jnp.clip(d.astype(jnp.int32), 0, lut.shape[0] - 1)
                return lut[codes], v

            return run_remap

        if expr.fn == "coalesce":
            parts = [branch_fn(b) for b in expr.args]

            def run_coalesce_s(page):
                data = valid = None
                for f in parts:
                    d, v = f(page)
                    if data is None:
                        data, valid = d, v
                    else:
                        data = _where_rows(jnp.logical_not(valid), d, data)
                        valid = valid | v
                return data, valid

            return run_coalesce_s

        if expr.fn == "if":
            c = self.compile(expr.args[0])
            t_f = branch_fn(expr.args[1])
            f_f = branch_fn(expr.args[2])

            def run_if_s(page):
                (dc, vc), (dt, vt), (df, vf) = c(page), t_f(page), f_f(page)
                cond = dc & vc
                return _where_rows(cond, dt, df), jnp.where(cond, vt, vf)

            return run_if_s

        # case: [when1, then1, ..., else]
        args = expr.args
        pairs = [(self.compile(args[i]), branch_fn(args[i + 1]))
                 for i in range(0, len(args) - 1, 2)]
        else_f = branch_fn(args[-1])

        def run_case_s(page):
            data, valid = else_f(page)
            taken = jnp.zeros(page.capacity, dtype=jnp.bool_)
            for wf, tf in pairs:
                wd, wv = wf(page)
                td, tv = tf(page)
                cond = wd & wv & jnp.logical_not(taken)
                data = _where_rows(cond, td, data)
                valid = jnp.where(cond, tv, valid)
                taken = taken | (wd & wv)
            return data, valid

        return run_case_s

    def _compile_case(self, expr: Call) -> CompiledExpr:
        # args = [when1, then1, when2, then2, ..., else]
        args = expr.args
        out_t = expr.type
        pairs = [(self.compile(args[i]),
                  self._compile_operand(args[i + 1], out_t), args[i + 1].type)
                 for i in range(0, len(args) - 1, 2)]
        else_f = self._compile_operand(args[-1], out_t)
        else_t = args[-1].type

        def run_case(page):
            data, valid = else_f(page)
            data = self._coerce(data, else_t, out_t)
            taken = jnp.zeros(page.capacity, dtype=jnp.bool_)
            for wf, tf, tt in pairs:
                (wd, wv) = wf(page)
                (td, tv) = tf(page)
                td = self._coerce(td, tt, out_t)
                cond = wd & wv & jnp.logical_not(taken)
                data = _where_rows(cond, td, data)
                valid = jnp.where(cond, tv, valid)
                taken = taken | (wd & wv)
            return data, valid

        return run_case

    # ------------------------------------------------------------------
    def _align_pair(self, da, ta: Type, db, tb: Type):
        """Coerce a comparison pair to a common representation."""
        if ta.name == "double" or tb.name == "double":
            return _to_double(da, ta), _to_double(db, tb)
        if ta.name == "real" or tb.name == "real":
            # REAL op decimal/integer runs in float32 (REAL result type)
            return (_to_double(da, ta).astype(jnp.float32),
                    _to_double(db, tb).astype(jnp.float32))
        if {ta.name, tb.name} == {"date", "timestamp"}:
            if ta.name == "date":
                return da.astype(jnp.int64) * MICROS_PER_DAY, db
            return da, db.astype(jnp.int64) * MICROS_PER_DAY
        if ta.is_decimal or tb.is_decimal:
            sa = ta.scale if ta.is_decimal else 0
            sb = tb.scale if tb.is_decimal else 0
            s = max(sa, sb)
            return _rescale(da.astype(jnp.int64), sa, s), _rescale(
                db.astype(jnp.int64), sb, s
            )
        return da, db

    def _coerce(self, data, from_t: Type, to_t: Type):
        if from_t == to_t:
            return data
        if to_t.name == "timestamp" and from_t.name == "date":
            return data.astype(jnp.int64) * MICROS_PER_DAY
        if to_t.name == "double":
            return _to_double(data, from_t)
        if to_t.is_long_decimal:
            fs = from_t.scale if from_t.is_decimal else 0
            return _to_long_limbs(data, from_t, fs, to_t.scale,
                                  limbs=to_t.value_shape[0])
        if to_t.is_decimal:
            if from_t.is_long_decimal:
                from presto_tpu.ops import decimal128 as d128

                limbs = d128.rescale(data, from_t.scale, to_t.scale)
                return _narrow_to_int64(limbs)
            fs = from_t.scale if from_t.is_decimal else 0
            return _rescale(data.astype(jnp.int64), fs, to_t.scale)
        if to_t.name == "bigint":
            if from_t.is_long_decimal:
                from presto_tpu.ops import decimal128 as d128

                limbs = d128.rescale(data, from_t.scale or 0, 0)
                return _narrow_to_int64(limbs)  # exact in range
            return data.astype(jnp.int64)
        return data


def _narrow_to_int64(limbs: jax.Array) -> jax.Array:
    """Collapse limb vectors to a single int64 (exact only when the
    value fits — same contract as the reference's narrowing casts)."""
    from presto_tpu.ops import decimal128 as d128

    if limbs.shape[-1] == 2:
        return limbs[..., 0] * d128.BASE + limbs[..., 1]
    acc = limbs[..., 0]
    for i in range(1, limbs.shape[-1]):
        acc = acc * d128._B9 + limbs[..., i]
    return acc


def _unwrap_geomtext(e: Expr) -> Expr:
    """ST_GeometryFromText is representation-transparent (WKT in, WKT
    out): peel it so accessors see the underlying literal/column."""
    while isinstance(e, Call) and e.fn == "st_geometryfromtext":
        e = e.args[0]
    return e


def _civil_from_days(z: jax.Array):
    """Epoch days -> (year, month, day). Howard Hinnant's public-domain
    civil_from_days algorithm, integer-only so it vectorizes on TPU."""
    z = z + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d


def _add_months(days: jax.Array, n: jax.Array) -> jax.Array:
    """Shift epoch days by n calendar months, clamping the day-of-month
    (2020-01-31 + 1 month = 2020-02-29)."""
    # built per-trace (a cached jnp constant would leak tracers); XLA
    # constant-folds it.
    month_len = jnp.asarray([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31],
                            dtype=jnp.int64)
    y, m, d = _civil_from_days(days)
    months = y * 12 + (m - 1) + n.astype(y.dtype)
    y2 = months // 12
    m2 = months % 12 + 1
    leap = (y2 % 4 == 0) & ((y2 % 100 != 0) | (y2 % 400 == 0))
    mlen = month_len[m2 - 1] + ((m2 == 2) & leap)
    d2 = jnp.minimum(d, mlen)
    return _days_from_civil(y2, m2, d2)


def _days_from_civil(y: jax.Array, m: jax.Array, d: jax.Array) -> jax.Array:
    """(year, month, day) -> epoch days (inverse of _civil_from_days,
    same public-domain algorithm)."""
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


# -- module-level helpers ----------------------------------------------------

def proven_sites(exprs: Sequence[Optional[Expr]],
                 proven: Optional[Sequence[bool]]) -> Optional[dict]:
    """``ExprCompiler``'s ``proven`` table for the expressions a stage
    compiles and the outcomes its lowering signed
    (``analysis.ranges.proven_table``); None where nothing was proved."""
    if not proven:
        return None
    from presto_tpu.analysis.ranges import proven_table

    return proven_table(exprs, proven)


def compile_expr(expr: Expr, page_or_types, dictionaries=None,
                 proven=None) -> CompiledExpr:
    if isinstance(page_or_types, Page):
        c = ExprCompiler.for_page(page_or_types, proven=proven)
    else:
        c = ExprCompiler(page_or_types,
                         dictionaries or [None] * len(page_or_types),
                         proven=proven)
    return c.compile(expr)


def compile_filter(expr: Expr, page_or_types, dictionaries=None, proven=None):
    """Compile a predicate to ``page -> bool mask`` (NULL -> excluded),
    the PageFilter analog."""
    f = compile_expr(expr, page_or_types, dictionaries, proven)

    def run(page: Page) -> jax.Array:
        d, v = f(page)
        return d & v & page.row_mask

    return run
