"""Compile expression trees into jax kernels over Batches.

Reference: the vectorized evaluators pkg/expression/builtin_*_vec.go
(VecEvalInt/Real/... over chunk.Column). The TPU analog compiles the whole
tree into one function Batch -> DevCol; XLA fuses it with the surrounding
operator (scan/filter/agg), like unistore's closure executor fuses
scan+selection+agg (cophandler/closure_exec.go:470).

Null semantics are MySQL three-valued logic carried in validity masks.

Strings are dictionary codes on device. Because each dictionary is sorted,
order comparisons against string literals become integer-code comparisons
via binary search in the dictionary at *compile* time; arbitrary string
predicates (LIKE) become a host-computed boolean lookup table gathered by
code on device — O(|dict|) host work regardless of row count.
"""

from __future__ import annotations

import re
import threading
from typing import Callable, Dict, Optional

import jax.numpy as jnp
import numpy as np

from tidb_tpu.chunk import Batch, DevCol
from tidb_tpu.dtypes import FLOAT64, Kind, SQLType
from tidb_tpu.expression.expr import (
    ARITH,
    BITOPS,
    COMPARE,
    ColumnRef,
    Expr,
    Func,
    Literal,
)

# column name -> sorted dictionary (np object array) for STRING columns.
DictContext = Dict[str, np.ndarray]

_CompiledExpr = Callable[[Batch], DevCol]


def _rescale(data, diff: int):
    if diff > 0:
        return data * (10**diff)
    if diff < 0:
        return data // (10**-diff)
    return data


def _to_float(data, t: SQLType):
    if t.kind == Kind.DECIMAL:
        return data.astype(jnp.float64) / (10**t.scale)
    return data.astype(jnp.float64)


def _to_bigint(data, t: SQLType):
    """Coerce one operand to BIGINT the way MySQL does for bit
    operators: decimals/floats round HALF AWAY FROM ZERO (the engine's
    DECIMAL rounding rule — jnp.round's half-to-even would turn
    2.5 & 7 into 2). Decimals stay in exact integer math: a float64
    round-trip would lose the low-order bits a bit operator reads."""
    if t is not None and t.kind == Kind.DECIMAL and t.scale:
        d = data.astype(jnp.int64)
        q = jnp.int64(10 ** t.scale)
        return jnp.sign(d) * ((jnp.abs(d) + q // 2) // q)
    if jnp.issubdtype(data.dtype, jnp.floating):
        return (jnp.sign(data) * jnp.floor(jnp.abs(data) + 0.5)).astype(
            jnp.int64
        )
    return data.astype(jnp.int64)


def _numeric_align(a, ta: SQLType, b, tb: SQLType, target: SQLType):
    """Bring two physical arrays to the target type's representation."""
    if target.kind == Kind.FLOAT:
        return _to_float(a, ta), _to_float(b, tb)
    if target.kind == Kind.DECIMAL:
        a = a.astype(jnp.int64) if ta.kind != Kind.DECIMAL else a
        b = b.astype(jnp.int64) if tb.kind != Kind.DECIMAL else b
        sa = ta.scale if ta.kind == Kind.DECIMAL else 0
        sb = tb.scale if tb.kind == Kind.DECIMAL else 0
        return _rescale(a, target.scale - sa), _rescale(b, target.scale - sb)
    if target.kind == Kind.DATETIME:
        # DATE promotes to midnight micros; an INT operand is a day count
        # (INTERVAL n DAY lowers to add(base, n)) and scales the same way
        from tidb_tpu.dtypes import US_PER_DAY

        def _cv(x, t):
            x = x.astype(jnp.int64)
            return x if t.kind == Kind.DATETIME else x * US_PER_DAY

        return _cv(a, ta), _cv(b, tb)
    # INT-ish: keep 64-bit (DATE int32 promotes)
    return a.astype(jnp.int64), b.astype(jnp.int64)


def _like_to_regex(pattern: str) -> "re.Pattern":
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return re.compile("^" + "".join(out) + "$", re.DOTALL)


# ---------------------------------------------------------------------------
# prepared-statement parameters (reference: pkg/planner/core/plan_cache.go:231
# parameterized plans). A Literal carrying param_slot compiles — in the
# generic value path — to a read of a runtime input made visible during
# tracing by param_scope, so one compiled program serves every EXECUTE.
# Compile-time consumers of literal VALUES (LIKE patterns, IN sets,
# dictionary merges, ROUND digits, pushed PK ranges, ...) call
# baked_value()/note_baked_param() instead: the active registry records
# the slot as BAKED and the session replans when that parameter changes.
# The registry is also fed by the generic path itself whenever no
# param_scope is active (e.g. a host-assisted or streamed stage that
# didn't thread parameters): baked-by-default keeps every untracked
# execution path sound.
# ---------------------------------------------------------------------------

_param_tls = threading.local()


class param_scope:
    """Makes bound parameter scalars (slot -> array) visible to compiled
    literal readers for the duration of a trace/eager execution."""

    def __init__(self, values):
        self.values = values or {}

    def __enter__(self):
        self._old = getattr(_param_tls, "vals", None)
        _param_tls.vals = self.values
        return self

    def __exit__(self, *exc):
        _param_tls.vals = self._old


class param_registry:
    """Collects, across one statement execution, which parameter slots
    were read as runtime inputs vs baked into the compiled artifact."""

    def __init__(self):
        self.runtime = set()
        self.baked = set()

    def __enter__(self):
        self._old = getattr(_param_tls, "reg", None)
        _param_tls.reg = self
        return self

    def __exit__(self, *exc):
        _param_tls.reg = self._old


def note_baked_param(e) -> None:
    slot = getattr(e, "param_slot", None)
    if slot is not None:
        reg = getattr(_param_tls, "reg", None)
        if reg is not None:
            reg.baked.add(slot)


def _note_runtime_param(slot: int) -> None:
    reg = getattr(_param_tls, "reg", None)
    if reg is not None:
        reg.runtime.add(slot)


def baked_value(e):
    """Read a literal's value for compile-time use, registering its
    parameter slot (if any) as baked."""
    note_baked_param(e)
    return e.value


def phys_dtype(t):
    """numpy/jnp dtype of a literal's physical device encoding."""
    if t is None:
        return jnp.float64
    if t.kind == Kind.FLOAT:
        return jnp.float64
    if t.kind == Kind.BOOL:
        return jnp.bool_
    if t.kind == Kind.DATE:
        return jnp.int32
    return jnp.int64


def literal_phys(v, t):
    """Literal -> the column's physical on-device encoding (shared by
    IN / FIELD / eq-literal paths; scaled decimals, epoch days/micros,
    MySQL double coercion of string-vs-numeric)."""
    if t is not None and t.kind == Kind.DECIMAL:
        return round(float(v) * 10**t.scale)
    if t is not None and t.kind == Kind.DATE:
        from tidb_tpu.dtypes import date_to_days

        return date_to_days(v) if isinstance(v, str) else int(v)
    if t is not None and t.kind == Kind.DATETIME:
        from tidb_tpu.dtypes import datetime_to_micros

        return datetime_to_micros(v) if isinstance(v, str) else int(v)
    if t is not None and t.kind == Kind.TIME:
        from tidb_tpu.dtypes import time_to_micros

        return time_to_micros(v) if isinstance(v, str) else int(v)
    if isinstance(v, str):
        try:
            return float(v)  # MySQL double coercion
        except ValueError:
            return 0.0
    return v


# keep in sync with planner.physical._BOUNDS_PREFIX (defined there; not
# imported to avoid a kernels <- physical cycle)
_BOUNDS_PREFIX_K = "\x00b\x00"


def _int_bounds(e, dicts):
    """(lo, hi) bounds of a plain integer column from the dicts map's
    reserved entries (Table.col_bounds via the planner), or None."""
    if not isinstance(e, ColumnRef):
        return None
    ent = dicts.get(_BOUNDS_PREFIX_K + e.name)
    if ent is None:
        return None
    get = getattr(ent, "get", None)
    return get() if callable(get) else ent


def _null_col(dtype):
    def _f(b):
        return DevCol(
            jnp.zeros(b.capacity, dtype=dtype),
            jnp.zeros(b.capacity, dtype=bool),
        )

    return _f


def _string_literal_code(dictionary: np.ndarray, value: str):
    """(code position, exact_match) for a literal against a sorted dict."""
    pos = int(np.searchsorted(dictionary, value))
    exact = pos < len(dictionary) and dictionary[pos] == value
    return pos, exact


def compile_expr(e: Expr, dicts: Optional[DictContext] = None) -> _CompiledExpr:
    dicts = dicts or {}
    fn = _compile(e, dicts)
    return fn


def expr_dictionary(e: Expr, dicts: DictContext) -> np.ndarray:
    """The dictionary a string-typed expression's output codes refer to.
    Deterministic and shared with compilation (string_expr)."""
    return string_expr(e, dicts)[1]


def string_expr(e: Expr, dicts: DictContext):
    """Compile a string-typed expression to (fn yielding codes, dictionary).

    Computed string values (CASE/COALESCE over string columns and
    literals) get a merged sorted dictionary; each branch's codes are
    remapped via a host-built LUT gathered on device."""
    if isinstance(e, ColumnRef):
        if e.name not in dicts:
            raise NotImplementedError(f"string column {e.name} has no dictionary")
        return _compile(e, dicts), dicts[e.name]
    if isinstance(e, Literal):
        note_baked_param(e)
        if e.value is None:
            def _null(b):
                z = jnp.zeros(b.capacity, dtype=jnp.int32)
                return DevCol(z, jnp.zeros(b.capacity, dtype=bool))
            return _null, np.array([], dtype=object)
        d = np.array([str(e.value)], dtype=object)

        def _lit(b):
            return DevCol(
                jnp.zeros(b.capacity, dtype=jnp.int32),
                jnp.ones(b.capacity, dtype=bool),
            )

        return _lit, d
    if isinstance(e, Func) and e.op == "_force_bin":
        return string_expr(e.args[0], dicts)  # passthrough marker
    if isinstance(e, Func) and (
        e.op in _STR_TRANSFORMS or e.op in _JSON_STR_FUNCS
    ):
        # string->string ops as dictionary transforms: run the python
        # function once per DISTINCT value on host (O(|dict|)), gather
        # codes on device — the LIKE cost model. A pyfn returning None
        # yields SQL NULL via the ok-mask (JSON missing paths; reference
        # pkg/types/json_binary.go walks rows, the dictionary makes it a
        # compile-time LUT here).
        for a in e.args[1:]:
            if not isinstance(a, Literal):
                raise NotImplementedError(
                    f"{e.op}: non-literal extra arguments not supported"
                )
        fn, d = string_expr(e.args[0], dicts)
        pyfn = (
            _json_pyfn(e) if e.op in _JSON_STR_FUNCS else _str_transform_pyfn(e)
        )
        outs = [pyfn(str(v)) for v in d.tolist()]
        present = sorted({str(o) for o in outs if o is not None})
        new_dict = np.array(present, dtype=object)
        codes = np.array(
            [
                np.searchsorted(new_dict, str(o)) if o is not None else 0
                for o in outs
            ],
            dtype=np.int32,
        )
        okm = np.array([o is not None for o in outs], dtype=bool)
        lut = jnp.asarray(codes if len(codes) else np.zeros(1, np.int32))
        ok_j = jnp.asarray(okm if len(okm) else np.ones(1, bool))

        def _tf(b):
            c = fn(b)
            cl = jnp.clip(c.data, 0, lut.shape[0] - 1)
            return DevCol(lut[cl], c.valid & ok_j[cl])

        return _tf, new_dict
    if isinstance(e, Func) and e.op in ("dayname", "monthname"):
        # date -> name: device index math + a fixed sorted dictionary
        names = (
            ["Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
             "Saturday", "Sunday"]
            if e.op == "dayname"
            else ["January", "February", "March", "April", "May", "June",
                  "July", "August", "September", "October", "November",
                  "December"]
        )
        new_dict = np.array(sorted(names), dtype=object)
        idx_to_code = jnp.asarray(
            np.searchsorted(new_dict, np.array(names, dtype=object)).astype(
                np.int32
            )
        )
        f = _compile(e.args[0], dicts)
        t0 = e.args[0].type
        is_day = e.op == "dayname"

        def _dn(b):
            c = f(b)
            days = _to_days(c.data, t0)
            if is_day:
                idx = (days + 3) % 7  # Monday=0 matches names order
            else:
                _y, m, _d = _civil_from_days(days)
                idx = m - 1
            return DevCol(idx_to_code[idx], c.valid)

        return _dn, new_dict
    if isinstance(e, Func) and e.op in ("hex", "bin", "oct"):
        t0 = e.args[0].type
        if t0 is not None and t0.kind == Kind.STRING:
            if e.op != "hex":
                raise NotImplementedError(f"{e.op.upper()} of a string")
            return string_expr(
                Func(type=e.type, op="hex_str", args=e.args), dicts
            )
        if isinstance(e.args[0], Literal):
            # e.g. HEX(-5): negation folds post-lowering, so the const
            # arrives here as a bound literal
            v = baked_value(e.args[0])
            if v is None:
                lit = Literal(type=e.type, value=None)
            else:
                fmt0 = {"hex": "X", "bin": "b", "oct": "o"}[e.op]
                iv = int(v)
                if iv < 0:
                    iv &= (1 << 64) - 1
                lit = Literal(type=e.type, value=format(iv, fmt0))
            return string_expr(lit, dicts)
        # bounded integer column -> base-converted strings via a range
        # LUT (bounds from Table.col_bounds riding the dicts map; see
        # planner.physical._BOUNDS_PREFIX)
        cb = _int_bounds(e.args[0], dicts)
        if cb is None or cb[1] - cb[0] > (1 << 16):
            raise NotImplementedError(
                f"{e.op.upper()} needs a string or narrowly-bounded "
                "integer column"
            )
        lo, hi = int(cb[0]), int(cb[1])
        fmt = {"hex": "X", "bin": "b", "oct": "o"}[e.op]
        # negatives render as 64-bit two's complement, like MySQL
        outs = [
            format(v & ((1 << 64) - 1) if v < 0 else v, fmt)
            for v in range(lo, hi + 1)
        ]
        new_dict = np.array(sorted(set(outs)), dtype=object)
        codes = np.searchsorted(new_dict, np.array(outs, dtype=object))
        lut = jnp.asarray(codes.astype(np.int32))
        f = _compile(e.args[0], dicts)

        def _i2s(b):
            c = f(b)
            idx = jnp.clip(c.data.astype(jnp.int64) - lo, 0, hi - lo)
            return DevCol(lut[idx], c.valid)

        return _i2s, new_dict
    if isinstance(e, Func) and e.op == "date_format":
        # DATE_FORMAT over a bounded practical range: precomputed
        # day->string LUT for 1900-01-01..2155-12-31 (the engine's
        # supported formatting window; values outside clamp)
        import datetime as _dt

        raw_fmt_v = baked_value(e.args[1])
        if raw_fmt_v is None:
            f0, d0 = string_expr(Literal(type=e.type, value=None), dicts)
            return f0, d0
        raw_fmt = str(raw_fmt_v)
        t0 = e.args[0].type
        if t0 is not None and t0.kind == Kind.DATETIME and any(
            tok in raw_fmt
            for tok in ("%H", "%i", "%s", "%S", "%T", "%r", "%f", "%h",
                        "%I", "%k", "%l", "%p")
        ):
            # the LUT is day-granular; rendering time-of-day tokens as
            # midnight would silently return wrong data
            raise NotImplementedError(
                "DATE_FORMAT with time tokens over DATETIME"
            )
        fmt = _mysql_fmt_to_py(raw_fmt)
        f = _compile(e.args[0], dicts)
        lo = _dt.date(1900, 1, 1).toordinal() - _dt.date(1970, 1, 1).toordinal()
        hi = _dt.date(2155, 12, 31).toordinal() - _dt.date(1970, 1, 1).toordinal()
        epoch = _dt.date(1970, 1, 1).toordinal()
        outs = [
            _dt.date.fromordinal(epoch + d).strftime(fmt)
            for d in range(lo, hi + 1)
        ]
        new_dict = np.array(sorted(set(outs)), dtype=object)
        codes = np.searchsorted(new_dict, np.array(outs, dtype=object))
        lut = jnp.asarray(codes.astype(np.int32))

        def _df(b):
            c = f(b)
            days = jnp.clip(_to_days(c.data, t0), lo, hi) - lo
            return DevCol(lut[days], c.valid)

        return _df, new_dict
    if isinstance(e, Func) and e.op == "concat":
        return _concat_expr(e, dicts)
    if isinstance(e, Func) and e.op == "concat_ws":
        return _concat_ws_expr(e, dicts)
    if isinstance(e, Func) and e.op in ("case", "coalesce", "ifnull"):
        if e.op == "case":
            args = list(e.args)
            has_else = len(args) % 2 == 1
            else_e = args.pop() if has_else else None
            conds = [args[i] for i in range(0, len(args), 2)]
            vals = [args[i] for i in range(1, len(args), 2)]
        else:
            conds, vals, else_e = None, list(e.args), None
        branches = vals + ([else_e] if else_e is not None else [])
        compiled = [string_expr(v, dicts) for v in branches]
        merged = np.array(
            sorted({s for _, d in compiled for s in d.tolist()}), dtype=object
        )
        luts = [
            jnp.asarray(
                np.searchsorted(merged, d).astype(np.int32)
                if len(d)
                else np.zeros(1, np.int32)
            )
            for _, d in compiled
        ]

        def remap(fn, lut):
            def g(b):
                c = fn(b)
                codes = jnp.clip(c.data, 0, lut.shape[0] - 1)
                return DevCol(lut[codes], c.valid)
            return g

        rfns = [remap(fn, lut) for (fn, _), lut in zip(compiled, luts)]
        if e.op == "case":
            cond_fns = [_compile(c, dicts) for c in conds]
            else_fn = rfns[-1] if else_e is not None else None
            val_fns = rfns[: len(vals)]

            def _case(b):
                if else_fn is not None:
                    ec = else_fn(b)
                    out_d, out_v = ec.data, ec.valid
                else:
                    out_d = jnp.zeros(b.capacity, dtype=jnp.int32)
                    out_v = jnp.zeros(b.capacity, dtype=bool)
                for cf, vf in zip(reversed(cond_fns), reversed(val_fns)):
                    c, v = cf(b), vf(b)
                    take = c.valid & c.data.astype(bool)
                    out_d = jnp.where(take, v.data, out_d)
                    out_v = jnp.where(take, v.valid, out_v)
                return DevCol(out_d, out_v)

            return _case, merged

        def _coal(b):
            cols = [f(b) for f in rfns]
            out_d, out_v = cols[-1].data, cols[-1].valid
            for c in reversed(cols[:-1]):
                out_d = jnp.where(c.valid, c.data, out_d)
                out_v = c.valid | out_v
            return DevCol(out_d, out_v)

        return _coal, merged
    raise NotImplementedError(f"string-valued expression {e!r}")


# String->string builtins evaluated on the dictionary: O(|dict|) host work
# regardless of row count, codes remapped on device (reference: the
# per-row builtin_string_vec.go loops; the dictionary makes them LUTs).
_JSON_MISSING = object()


def _json_path_get(doc, path: str):
    """Walk a MySQL-ish JSON path ($.a.b[0], $[1]."q k")."""
    if not path.startswith("$"):
        return _JSON_MISSING
    toks = re.findall(
        r'\.([A-Za-z_][A-Za-z0-9_]*)|\[(\d+)\]|\."([^"]+)"', path[1:]
    )
    consumed = sum(len(m) for m in re.findall(
        r'\.[A-Za-z_][A-Za-z0-9_]*|\[\d+\]|\."[^"]+"', path[1:]
    ))
    if consumed != len(path) - 1:
        return _JSON_MISSING  # unparsable path
    cur = doc
    for name, idx, qname in toks:
        key = name or qname
        if key:
            if isinstance(cur, dict) and key in cur:
                cur = cur[key]
            else:
                return _JSON_MISSING
        else:
            i = int(idx)
            if isinstance(cur, list) and i < len(cur):
                cur = cur[i]
            else:
                return _JSON_MISSING
    return cur


_JSON_STR_FUNCS = {
    "json_extract", "json_unquote", "json_type", "json_keys",
    # mutation family (reference pkg/expression/builtin_json.go): the
    # doc rides a dictionary column; paths and new values are baked
    # constants, so each function is one host pass over the dictionary
    "json_set", "json_insert", "json_replace", "json_remove",
    "json_merge_patch", "json_merge_preserve", "json_merge",
    "json_array_append", "json_array_insert", "json_pretty",
    "json_search",
}


def _json_path_parts(path: str):
    """'$.a[0].b' -> ['a', 0, 'b']; '$' -> []. Raises on wildcards."""
    import re as _re

    if not path.startswith("$"):
        raise NotImplementedError(f"bad JSON path {path!r}")
    if "*" in path:
        raise NotImplementedError("JSON path wildcards")
    parts: list = []
    pos = 0
    body = path[1:]
    # segments must tile the whole path — a partial match would silently
    # mutate the wrong location (MySQL raises ER_INVALID_JSON_PATH)
    seg = _re.compile(r"\.(\w+)|\.\"([^\"]+)\"|\[(\d+)\]")
    while pos < len(body):
        m = seg.match(body, pos)
        if m is None:
            raise NotImplementedError(f"invalid JSON path {path!r}")
        if m.group(3) is not None:
            parts.append(int(m.group(3)))
        else:
            parts.append(m.group(1) or m.group(2))
        pos = m.end()
    return parts


def _json_set_path(doc, parts, value, mode):
    """Set/insert/replace `value` at `parts` in doc (in place where
    possible); mode in {'set','insert','replace','array_insert',
    'array_append'}. MySQL semantics: missing intermediate paths are
    created only for trailing member sets; out-of-range array indexes
    append."""
    if not parts:
        if mode in ("set", "replace"):
            return value
        if mode == "array_append":
            # root append: MySQL appends to a root array, autowraps a
            # root scalar/object
            return doc + [value] if isinstance(doc, list) else [doc, value]
        return doc
    cur = doc
    for p in parts[:-1]:
        nxt = None
        if isinstance(p, int):
            if isinstance(cur, list) and p < len(cur):
                nxt = cur[p]
        elif isinstance(cur, dict) and p in cur:
            nxt = cur[p]
        if nxt is None or not isinstance(nxt, (dict, list)):
            return doc  # unreachable path: no-op (MySQL)
        cur = nxt
    last = parts[-1]
    if mode == "array_append":
        tgt = None
        if isinstance(last, int):
            tgt = cur[last] if isinstance(cur, list) and last < len(cur) else None
        elif isinstance(cur, dict):
            tgt = cur.get(last)
        if tgt is None:
            return doc
        if isinstance(tgt, list):
            tgt.append(value)
        else:  # autowrap scalar
            cur[last] = [tgt, value]
        return doc
    if isinstance(last, int):
        if not isinstance(cur, list):
            return doc
        if mode == "array_insert":
            cur.insert(min(last, len(cur)), value)
        elif last < len(cur):
            if mode in ("set", "replace"):
                cur[last] = value
        elif mode in ("set", "insert"):
            cur.append(value)
    else:
        if not isinstance(cur, dict):
            return doc
        exists = last in cur
        if (
            mode == "set"
            or (mode == "insert" and not exists)
            or (mode == "replace" and exists)
        ):
            cur[last] = value
    return doc


def _json_remove_path(doc, parts):
    if not parts:
        return doc
    cur = doc
    for p in parts[:-1]:
        if isinstance(p, int):
            if not (isinstance(cur, list) and p < len(cur)):
                return doc
            cur = cur[p]
        else:
            if not (isinstance(cur, dict) and p in cur):
                return doc
            cur = cur[p]
    last = parts[-1]
    if isinstance(last, int):
        if isinstance(cur, list) and last < len(cur):
            del cur[last]
    elif isinstance(cur, dict):
        cur.pop(last, None)
    return doc


def _json_merge_patch(a, b):
    """RFC 7396 (reference json_merge_patch)."""
    if not isinstance(b, dict):
        return b
    if not isinstance(a, dict):
        a = {}
    out = dict(a)
    for k, v in b.items():
        if v is None:
            out.pop(k, None)
        else:
            out[k] = _json_merge_patch(out.get(k), v)
    return out


def _json_merge_preserve(a, b):
    """MySQL JSON_MERGE_PRESERVE: arrays concatenate, objects merge
    recursively, scalars wrap into arrays."""
    if isinstance(a, dict) and isinstance(b, dict):
        out = dict(a)
        for k, v in b.items():
            out[k] = _json_merge_preserve(out[k], v) if k in out else v
        return out
    la = a if isinstance(a, list) else [a]
    lb = b if isinstance(b, list) else [b]
    return la + lb


def _json_const(v):
    """A baked argument as a JSON value: strings stay strings (MySQL
    treats non-JSON-typed args as literal strings)."""
    return v


def _json_pyfn(e: Func):
    import json as _json

    op = e.op
    if op == "json_extract":
        if len(e.args) != 2:
            raise NotImplementedError(
                "json_extract supports exactly one path"
            )
        path = str(baked_value(e.args[1]))

        def f(s):
            try:
                doc = _json.loads(s)
            except Exception:
                return None
            v = _json_path_get(doc, path)
            return None if v is _JSON_MISSING else _json.dumps(v)

        return f
    if op == "json_keys":
        def f(s):
            try:
                v = _json.loads(s)
            except Exception:
                return None
            if not isinstance(v, dict):
                return None
            return _json.dumps(list(v.keys()))

        return f
    if op == "json_unquote":
        def f(s):
            if len(s) >= 2 and s[0] == '"' and s[-1] == '"':
                try:
                    return str(_json.loads(s))
                except Exception:
                    return s
            return s

        return f
    if op in ("json_set", "json_insert", "json_replace", "json_remove",
              "json_array_append", "json_array_insert"):
        mode = {
            "json_set": "set", "json_insert": "insert",
            "json_replace": "replace", "json_array_append": "array_append",
            "json_array_insert": "array_insert",
        }.get(op)
        if op == "json_remove":
            paths = [
                _json_path_parts(str(baked_value(a))) for a in e.args[1:]
            ]

            def f(s):
                try:
                    doc = _json.loads(s)
                except Exception:
                    return None
                for parts in paths:
                    doc = _json_remove_path(doc, parts)
                return _json.dumps(doc)

            return f
        rest = e.args[1:]
        if len(rest) % 2:
            raise NotImplementedError(f"{op} needs (path, value) pairs")
        pairs = [
            (_json_path_parts(str(baked_value(rest[i]))),
             _json_const(baked_value(rest[i + 1])))
            for i in range(0, len(rest), 2)
        ]

        def f(s):
            try:
                doc = _json.loads(s)
            except Exception:
                return None
            for parts, val in pairs:
                doc = _json_set_path(doc, parts, val, mode)
            return _json.dumps(doc)

        return f
    if op in ("json_merge_patch", "json_merge_preserve", "json_merge"):
        merge = (
            _json_merge_patch if op == "json_merge_patch"
            else _json_merge_preserve
        )
        others = []
        for a in e.args[1:]:
            try:
                others.append(_json.loads(str(baked_value(a))))
            except Exception:
                others.append(None)

        def f(s):
            try:
                doc = _json.loads(s)
            except Exception:
                return None
            for o in others:
                doc = merge(doc, o)
            return _json.dumps(doc)

        return f
    if op == "json_pretty":
        def f(s):
            try:
                return _json.dumps(_json.loads(s), indent=2)
            except Exception:
                return None

        return f
    if op == "json_search":
        # JSON_SEARCH(doc, 'one'|'all', search_str): path of matching
        # string values ('one' -> first, 'all' -> array of paths)
        one = str(baked_value(e.args[1])).lower() != "all"
        needle = str(baked_value(e.args[2]))
        from tidb_tpu.utils.checkeval import sql_like_match

        def f(s):
            try:
                doc = _json.loads(s)
            except Exception:
                return None
            hits: list = []

            def walk(v, path):
                if isinstance(v, str) and sql_like_match(v, needle):
                    hits.append(path)
                elif isinstance(v, dict):
                    for k, vv in v.items():
                        seg = (
                            f".{k}" if re.fullmatch(r"\w+", k)
                            else f'."{k}"'
                        )
                        walk(vv, path + seg)
                elif isinstance(v, list):
                    for i, vv in enumerate(v):
                        walk(vv, f"{path}[{i}]")

            walk(doc, "$")
            if not hits:
                return None
            if one:
                return _json.dumps(hits[0])
            return _json.dumps(hits if len(hits) > 1 else hits[0])

        return f
    # json_type
    def f(s):
        try:
            v = _json.loads(s)
        except Exception:
            return None
        if v is None:
            return "NULL"
        if isinstance(v, bool):
            return "BOOLEAN"
        if isinstance(v, int):
            return "INTEGER"
        if isinstance(v, float):
            return "DOUBLE"
        if isinstance(v, str):
            return "STRING"
        if isinstance(v, list):
            return "ARRAY"
        return "OBJECT"

    return f


_STR_TRANSFORMS = {
    "upper", "lower", "trim", "ltrim", "rtrim", "replace", "substring",
    "left", "right", "reverse", "lpad", "rpad", "repeat",
    "quote", "insert_str", "regexp_substr", "regexp_replace",
    "md5", "sha1", "sha2", "hex_str", "substring_index",
    "soundex", "to_base64", "from_base64", "json_quote",
    "weight_string", "unhex",
    # binary-yielding transforms: bytes ride latin-1-mapped strings (a
    # lossless byte<->str bijection; HEX()/decrypt round-trips exactly)
    "aes_encrypt", "aes_decrypt", "compress", "uncompress",
    "inet6_aton", "inet6_ntoa", "uuid_to_bin", "bin_to_uuid",
}


def _b2s(b: bytes) -> str:
    return b.decode("latin-1")


def _s2b(s: str) -> bytes:
    try:
        return s.encode("latin-1")
    except UnicodeEncodeError:
        return s.encode("utf-8")


def _mysql_aes_key(key: bytes, bits: int = 128) -> bytes:
    """MySQL's key folding: XOR the key bytes cyclically into a
    bits/8-byte buffer (reference pkg/util/encrypt/aes.go DeriveKeyMySQL)."""
    n = bits // 8
    out = bytearray(n)
    for i, b in enumerate(key):
        out[i % n] ^= b
    return bytes(out)


def _str_transform_pyfn(e: Func):
    op = e.op
    ex = [baked_value(a) for a in e.args[1:]]
    if op == "upper":
        return lambda s: s.upper()
    if op == "lower":
        return lambda s: s.lower()
    if op == "trim":
        return lambda s: s.strip()
    if op == "ltrim":
        return lambda s: s.lstrip()
    if op == "rtrim":
        return lambda s: s.rstrip()
    if op == "reverse":
        return lambda s: s[::-1]
    if op == "soundex":
        def _soundex(s):
            # classic Soundex (builtin_string.go soundex): letter +
            # 3 digits, adjacent duplicates collapsed, vowels dropped
            codes = {"b": "1", "f": "1", "p": "1", "v": "1",
                     "c": "2", "g": "2", "j": "2", "k": "2", "q": "2",
                     "s": "2", "x": "2", "z": "2",
                     "d": "3", "t": "3", "l": "4",
                     "m": "5", "n": "5", "r": "6"}
            letters = [c for c in s.lower() if c.isalpha()]
            if not letters:
                return ""
            out = letters[0].upper()
            prev = codes.get(letters[0], "")
            for c in letters[1:]:
                d = codes.get(c, "")
                if d and d != prev:
                    out += d
                prev = d
            return (out + "000")[:4]

        return _soundex
    if op == "unhex":
        def _unhex(s):
            try:
                return bytes.fromhex(s).decode("utf-8", errors="replace")
            except ValueError:
                return ""

        return _unhex
    if op == "to_base64":
        import base64

        return lambda s: base64.b64encode(s.encode()).decode()
    if op == "from_base64":
        import base64

        def _fb64(s):
            try:
                return base64.b64decode(s.encode(), validate=True).decode(
                    "utf-8", errors="replace"
                )
            except Exception:
                return ""  # MySQL returns NULL; dictionary LUTs carry
                # values only — documented divergence

        return _fb64
    if op == "json_quote":
        import json as _json

        return lambda s: _json.dumps(s)
    if op in ("aes_encrypt", "aes_decrypt"):
        # MySQL default block_encryption_mode = aes-128-ecb with PKCS7
        # padding (reference pkg/expression/builtin_encryption.go +
        # pkg/util/encrypt); ciphertext rides a latin-1 byte-string
        from cryptography.hazmat.primitives.ciphers import (
            Cipher, algorithms, modes,
        )

        key = _mysql_aes_key(_s2b(str(ex[0])))

        if op == "aes_encrypt":
            def _aes_e(s):
                data = _s2b(s)
                pad = 16 - len(data) % 16
                data += bytes([pad]) * pad
                enc = Cipher(algorithms.AES(key), modes.ECB()).encryptor()
                return _b2s(enc.update(data) + enc.finalize())

            return _aes_e

        def _aes_d(s):
            data = _s2b(s)
            if not data or len(data) % 16:
                return None  # MySQL: NULL on malformed ciphertext
            dec = Cipher(algorithms.AES(key), modes.ECB()).decryptor()
            out = dec.update(data) + dec.finalize()
            pad = out[-1] if out else 0
            if not (1 <= pad <= 16) or out[-pad:] != bytes([pad]) * pad:
                return None
            # mirror _s2b (latin-1-first): round-trips every latin-1-
            # encodable plaintext exactly; >U+00FF inputs took the utf-8
            # fallback on encrypt and come back byte-identical but
            # latin-1-rendered (documented carrier divergence)
            return _b2s(out[:-pad])

        return _aes_d
    if op == "compress":
        import struct
        import zlib

        def _comp(s):
            data = _s2b(s)
            if not data:
                return ""  # MySQL: empty in, empty out
            # MySQL format: 4-byte LE uncompressed length + deflate
            return _b2s(struct.pack("<I", len(data)) + zlib.compress(data))

        return _comp
    if op == "uncompress":
        import struct
        import zlib

        def _uncomp(s):
            data = _s2b(s)
            if not data:
                return ""
            if len(data) <= 4:
                return None
            try:
                n = struct.unpack("<I", data[:4])[0]
                out = zlib.decompress(data[4:])
            except Exception:
                return None
            if len(out) != n:
                return None
            return _b2s(out)  # mirrors _s2b's latin-1-first mapping

        return _uncomp
    if op == "inet6_aton":
        import ipaddress

        def _i6a(s):
            try:
                return _b2s(ipaddress.ip_address(s).packed)
            except ValueError:
                return None

        return _i6a
    if op == "inet6_ntoa":
        import ipaddress

        def _i6n(s):
            b = _s2b(s)
            try:
                if len(b) == 4:
                    return str(ipaddress.IPv4Address(b))
                if len(b) == 16:
                    return str(ipaddress.IPv6Address(b))
            except ValueError:
                pass
            return None

        return _i6n
    if op == "uuid_to_bin":
        import uuid as _uuid

        def _u2b(s):
            try:
                return _b2s(_uuid.UUID(s).bytes)
            except ValueError:
                return None

        return _u2b
    if op == "bin_to_uuid":
        import uuid as _uuid

        def _bu(s):
            b = _s2b(s)
            if len(b) != 16:
                return None
            return str(_uuid.UUID(bytes=b))

        return _bu
    if op == "weight_string":
        # the collation sort key itself (reference WEIGHT_STRING reveals
        # the Key() bytes; here the key IS a string)
        from tidb_tpu.utils import collate as _coll

        coll = (
            e.args[0].type.collation
            if e.args[0].type is not None else None
        )
        kf = _coll.key_fn(coll)
        return lambda s: kf(s)
    if op == "replace":
        frm, to = str(ex[0]), str(ex[1])
        return lambda s: s.replace(frm, to) if frm else s
    if op == "left":
        n = max(int(ex[0]), 0)
        return lambda s: s[:n]
    if op == "right":
        n = max(int(ex[0]), 0)
        return lambda s: s[-n:] if n else ""
    if op == "repeat":
        n = max(int(ex[0]), 0)
        return lambda s: s * n
    if op == "lpad":
        n, pad = int(ex[0]), str(ex[1])
        def _lpad(s):
            if len(s) >= n or not pad:
                return s[:n]
            fill = (pad * n)[: n - len(s)]
            return fill + s
        return _lpad
    if op == "rpad":
        n, pad = int(ex[0]), str(ex[1])
        def _rpad(s):
            if len(s) >= n or not pad:
                return s[:n]
            return s + (pad * n)[: n - len(s)]
        return _rpad
    if op == "substring":
        pos = int(ex[0])
        ln = int(ex[1]) if len(ex) > 1 else None
        def _sub(s):
            if pos > 0:
                i = pos - 1
            elif pos < 0:
                i = max(len(s) + pos, 0)
            else:
                return ""  # MySQL: SUBSTRING(s, 0) = ''
            if ln is None:
                return s[i:]
            return s[i : i + max(ln, 0)]
        return _sub
    if op == "substring_index":
        delim, cnt = str(ex[0]), int(ex[1])

        def _si(s):
            if cnt == 0 or not delim:
                return ""
            parts = s.split(delim)
            if cnt > 0:
                return delim.join(parts[:cnt])
            return delim.join(parts[cnt:])

        return _si
    if op == "quote":
        return lambda s: "'" + s.replace("\\", "\\\\").replace("'", "\\'") + "'"
    if op == "insert_str":
        pos, ln, repl = int(ex[0]), int(ex[1]), str(ex[2])

        def _ins(s):
            if pos < 1 or pos > len(s):
                return s
            if ln < 0 or pos - 1 + ln >= len(s):
                return s[: pos - 1] + repl  # MySQL: replace to the end
            return s[: pos - 1] + repl + s[pos - 1 + ln:]

        return _ins
    if op == "regexp_substr":
        if ex[0] is None:
            return lambda s: None
        rx = re.compile(str(ex[0]))

        def _rs(s):
            m = rx.search(s)
            return m.group(0) if m else None  # no match -> SQL NULL

        return _rs
    if op == "regexp_replace":
        if ex[0] is None or ex[1] is None:
            return lambda s: None
        rx = re.compile(str(ex[0]))
        # MySQL capture refs are $N; python's re wants \N
        repl = re.sub(r"\$(\d)", r"\\\1", str(ex[1]))
        return lambda s: rx.sub(repl, s)
    if op == "md5":
        import hashlib

        return lambda s: hashlib.md5(s.encode()).hexdigest()
    if op == "sha1":
        import hashlib

        return lambda s: hashlib.sha1(s.encode()).hexdigest()
    if op == "sha2":
        import hashlib

        bits = int(ex[0]) if ex else 256
        algo = {224: "sha224", 256: "sha256", 384: "sha384", 512: "sha512",
                0: "sha256"}.get(bits)
        if algo is None:
            return lambda s: None  # MySQL: invalid hash length -> NULL
        return lambda s: getattr(hashlib, algo)(s.encode()).hexdigest()
    if op == "hex_str":
        return lambda s: s.encode().hex().upper()
    raise AssertionError(op)


def _string_parts(args, dicts: DictContext, what: str):
    """(fn, dictionary) per argument; non-string literals coerce to
    text, non-string columns are rejected (no per-row host work)."""
    from tidb_tpu.dtypes import Kind as _K

    parts = []
    for a in args:
        if a.type is not None and a.type.kind == _K.STRING:
            parts.append(string_expr(a, dicts))
        elif isinstance(a, Literal):
            v = baked_value(a)
            lit = Literal(type=None, value=None if v is None else _fmt_scalar(v, a.type))
            parts.append(string_expr(lit, {}))
        else:
            raise NotImplementedError(
                f"{what} over non-string columns: CAST ... AS CHAR first"
            )
    return parts


def _mixed_radix(parts_sizes):
    strides = []
    acc = 1
    for s in reversed(parts_sizes):
        strides.append(acc)
        acc *= s
    strides.reverse()
    return strides, acc


def _concat_expr(e: Func, dicts: DictContext):
    """CONCAT over string expressions and literals: the output dictionary
    is the (deduped) mixed-radix product of the input dictionaries; codes
    combine arithmetically on device and remap through one LUT."""
    parts = _string_parts(e.args, dicts, "CONCAT")
    sizes = [max(len(d), 1) for _, d in parts]
    strides, total = _mixed_radix(sizes)
    if total > (1 << 20):
        raise NotImplementedError(
            f"CONCAT dictionary product too large ({total} combos)"
        )
    strs = [[str(x) for x in d.tolist()] or [""] for _, d in parts]
    combos = [""]
    for ss in strs:
        combos = [c + s for c in combos for s in ss]
    merged = np.array(sorted(set(combos)), dtype=object)
    lut = jnp.asarray(np.searchsorted(merged, np.array(combos, dtype=object)).astype(np.int32))

    def _cc(b):
        idx = jnp.zeros(b.capacity, dtype=jnp.int64)
        valid = jnp.ones(b.capacity, dtype=bool)
        for (fn, d), size, stride in zip(parts, sizes, strides):
            c = fn(b)
            idx = idx + jnp.clip(c.data, 0, size - 1).astype(jnp.int64) * stride
            valid = valid & c.valid
        return DevCol(lut[idx], valid)

    return _cc, merged


def _concat_ws_expr(e: Func, dicts: DictContext):
    """CONCAT_WS(sep, ...): NULL arguments are SKIPPED, not propagated
    (MySQL semantics); each argument gets an extra dictionary slot
    meaning NULL, and the combo table joins the non-NULL values."""
    sep_e = e.args[0]
    if not isinstance(sep_e, Literal):
        raise NotImplementedError("CONCAT_WS separator must be a literal")
    note_baked_param(sep_e)
    if sep_e.value is None:
        # NULL separator -> NULL result
        def _null(b):
            z = jnp.zeros(b.capacity, dtype=jnp.int32)
            return DevCol(z, jnp.zeros(b.capacity, dtype=bool))

        return _null, np.array([], dtype=object)
    sep = str(sep_e.value)
    parts = _string_parts(e.args[1:], dicts, "CONCAT_WS")
    sizes = [len(d) + 1 for _, d in parts]  # last slot = NULL
    strides, total = _mixed_radix(sizes)
    if total > (1 << 20):
        raise NotImplementedError(
            f"CONCAT_WS dictionary product too large ({total} combos)"
        )
    options = [[str(x) for x in d.tolist()] + [None] for _, d in parts]
    combos: list = [[]]
    for opts in options:
        combos = [c + [o] for c in combos for o in opts]
    joined = [sep.join(v for v in c if v is not None) for c in combos]
    merged = np.array(sorted(set(joined)), dtype=object)
    lut = jnp.asarray(np.searchsorted(merged, np.array(joined, dtype=object)).astype(np.int32))

    def _cw(b):
        idx = jnp.zeros(b.capacity, dtype=jnp.int64)
        for (fn, d), size, stride in zip(parts, sizes, strides):
            c = fn(b)
            null_slot = size - 1
            code = jnp.where(
                c.valid, jnp.clip(c.data, 0, max(null_slot - 1, 0)), null_slot
            )
            idx = idx + code.astype(jnp.int64) * stride
        return DevCol(lut[idx], jnp.ones(b.capacity, dtype=bool))

    return _cw, merged


def _fmt_scalar(v, t: Optional[SQLType]) -> str:
    if isinstance(v, float) and v == int(v):
        return str(int(v)) if abs(v) < 1e15 else repr(v)
    return str(v)


def _compile(e: Expr, dicts: DictContext) -> _CompiledExpr:
    if isinstance(e, ColumnRef):
        name = e.name
        return lambda b: b.cols[name]

    if isinstance(e, Literal):
        return _compile_literal(e)

    assert isinstance(e, Func)
    op = e.op

    if op in ARITH or op in COMPARE or op in BITOPS or op == "nulleq":
        return _compile_binary(e, dicts)
    if op == "bit_neg":
        (a,) = [_compile(x, dicts) for x in e.args]
        ta = e.args[0].type

        def _bneg(b):
            c = a(b)
            return DevCol(~_to_bigint(c.data, ta), c.valid)

        return _bneg
    if op == "bit_count":
        (a,) = [_compile(x, dicts) for x in e.args]
        ta = e.args[0].type

        def _bcnt(b):
            c = a(b)
            u = _to_bigint(c.data, ta).astype(jnp.uint64)
            # SWAR popcount over 64 bits
            u = u - ((u >> 1) & jnp.uint64(0x5555555555555555))
            u = (u & jnp.uint64(0x3333333333333333)) + (
                (u >> 2) & jnp.uint64(0x3333333333333333)
            )
            u = (u + (u >> 4)) & jnp.uint64(0x0F0F0F0F0F0F0F0F)
            n = (u * jnp.uint64(0x0101010101010101)) >> 56
            return DevCol(n.astype(jnp.int64), c.valid)

        return _bcnt
    if op in ("and", "or"):
        return _compile_logic(e, dicts)
    if op == "not":
        (a,) = [_compile(x, dicts) for x in e.args]

        def _not(b):
            c = a(b)
            return DevCol(~c.data.astype(bool), c.valid)

        return _not
    if op == "neg":
        (a,) = [_compile(x, dicts) for x in e.args]
        return lambda b: DevCol(-a(b).data, a(b).valid)
    if op == "isnull":
        (a,) = [_compile(x, dicts) for x in e.args]
        return lambda b: DevCol(~a(b).valid, jnp.ones_like(a(b).valid))
    if op == "isnotnull":
        (a,) = [_compile(x, dicts) for x in e.args]
        return lambda b: DevCol(a(b).valid, jnp.ones_like(a(b).valid))
    if op in ("coalesce", "ifnull"):
        if e.type is not None and e.type.kind == Kind.STRING:
            return string_expr(e, dicts)[0]
        return _compile_coalesce(e, dicts)
    if op == "case":
        if e.type is not None and e.type.kind == Kind.STRING:
            return string_expr(e, dicts)[0]
        return _compile_case(e, dicts)
    if op == "cast":
        return _compile_cast(e, dicts)
    if op == "like":
        return _compile_like(e, dicts)
    if op == "in":
        return _compile_in(e, dicts)
    if op in (
        "year", "month", "day", "dayofweek", "weekday", "dayofyear", "quarter",
    ):
        return _compile_extract(e, dicts)
    if op in ("hour", "minute", "second", "microsecond"):
        return _compile_time_part(e, dicts)
    if op == "add_months":
        return _compile_add_months(e, dicts)
    if op == "add_us":
        # DATETIME/TIME +/- a literal microsecond count (sub-day INTERVAL
        # units lower to this; DATE operands promote to midnight)
        fa, fb = (_compile(a, dicts) for a in e.args)
        ta = e.args[0].type

        def _aus(b):
            a, c = fa(b), fb(b)
            return DevCol(
                _to_micros(a.data, ta) + c.data.astype(jnp.int64),
                a.valid & c.valid,
            )

        return _aus
    if op == "date_part_days":
        # DATE(datetime_expr): truncate micros to days
        (f,) = [_compile(a, dicts) for a in e.args]
        st = e.args[0].type

        def _dpd(b):
            c = f(b)
            if st is not None and st.kind == Kind.DATETIME:
                from tidb_tpu.dtypes import US_PER_DAY

                return DevCol(
                    (c.data // US_PER_DAY).astype(jnp.int32), c.valid
                )
            return c

        return _dpd
    if op == "datediff":
        fa, fb = (_compile(a, dicts) for a in e.args)
        ta, tb = (a.type for a in e.args)

        def _dd(b):
            a, c = fa(b), fb(b)
            return DevCol(
                _to_days(a.data, ta) - _to_days(c.data, tb),
                a.valid & c.valid,
            )

        return _dd
    if op == "json_contains":
        import json as _json

        if not all(isinstance(a, Literal) for a in e.args[1:]):
            raise NotImplementedError(
                "JSON_CONTAINS candidate/path must be literals"
            )
        cand = baked_value(e.args[1])
        path = baked_value(e.args[2]) if len(e.args) > 2 else None

        def _contains(s):
            try:
                doc = _json.loads(s)
                target = _json.loads(str(cand))
            except Exception:
                return False
            if path and str(path).startswith("$."):
                for part in str(path)[2:].split("."):
                    if isinstance(doc, dict) and part in doc:
                        doc = doc[part]
                    else:
                        return False

            def has(d, t):
                if d == t:
                    return True
                if isinstance(d, list):
                    return any(has(x, t) for x in d)
                return False

            return has(doc, target)

        return _compile_strlut(e.args[0], dicts, _contains, jnp.bool_)
    if op == "json_valid":
        import json as _json

        def _jv(s):
            try:
                _json.loads(s)
                return 1
            except Exception:
                return 0

        return _compile_strlut(e.args[0], dicts, _jv, jnp.int64)
    if op == "json_length":
        import json as _json

        jpath = None
        if len(e.args) > 1:
            if not isinstance(e.args[1], Literal):
                raise NotImplementedError("json_length path must be literal")
            jpath = str(baked_value(e.args[1]))

        def _jl(s):
            try:
                v = _json.loads(s)
            except Exception:
                return 0
            if jpath is not None:
                v = _json_path_get(v, jpath)
                if v is _JSON_MISSING:
                    return 0
            return len(v) if isinstance(v, (list, dict)) else 1

        return _compile_strlut(e.args[0], dicts, _jl, jnp.int64)
    if op == "field":
        # FIELD(x, v1, v2, ...): 1-based index of x among the values,
        # 0 when absent or when x is NULL; NULL needles never match
        # (builtin_string.go fieldFunctionClass)
        x = e.args[0]
        needles = []  # (original 1-based position, value)
        for pos, a in enumerate(e.args[1:], 1):
            if not isinstance(a, Literal):
                raise NotImplementedError("FIELD values must be literals")
            if baked_value(a) is None:
                continue  # a NULL needle matches nothing
            needles.append((pos, a.value))
        if _is_string_col(x):
            if all(isinstance(v, str) for _p, v in needles):
                sn = {str(v): pos for pos, v in reversed(needles)}
                lut_fn = lambda s: sn.get(s, 0)
            else:
                # mixed string/numeric arguments compare as doubles
                # (MySQL coercion)
                def _f(xv):
                    try:
                        return float(xv)
                    except (TypeError, ValueError):
                        return 0.0

                def lut_fn(sv, _n=needles):
                    for pos, v in _n:
                        if (isinstance(v, str) and v == sv) or (
                            not isinstance(v, str) and _f(sv) == _f(v)
                        ):
                            return pos
                    return 0

            inner = _compile_strlut(x, dicts, lut_fn, jnp.int64)

            def _sfield(b):
                c = inner(b)
                # FIELD(NULL, ...) is 0, not NULL (MySQL)
                return DevCol(
                    jnp.where(c.valid, c.data, jnp.int64(0)),
                    jnp.ones_like(c.valid),
                )

            return _sfield
        fx = _compile(x, dicts)
        t = x.type
        pneedles = [(pos, literal_phys(v, t)) for pos, v in needles]

        def _field(b):
            c = fx(b)
            out = jnp.zeros(b.capacity, dtype=jnp.int64)
            for pos, v in reversed(pneedles):
                out = jnp.where(
                    c.valid & (c.data == v), jnp.int64(pos), out
                )
            return DevCol(out, jnp.ones(b.capacity, dtype=bool))

        return _field
    if op == "_force_bin":
        return _compile(e.args[0], dicts)
    if op == "_collation_rank":
        # ORDER BY on a CI-collated column: sort by the dense collation
        # rank of each value (equal-under-collation values tie; the
        # stable sort keeps their stored order)
        f, dictionary = string_expr(e.args[0], dicts)
        coll = (
            e.args[0].type.collation
            if e.args[0].type is not None else None
        )
        lut, _keys, _kf = _collation_rank_lut(dictionary, coll)

        def _rank(b):
            c = f(b)
            return DevCol(
                lut[jnp.clip(c.data, 0, lut.shape[0] - 1)], c.valid
            )

        return _rank
    if op == "is_uuid":
        import re as _re

        # MySQL: fully-dashed, dash-free, or braced fully-dashed only
        _uuid_re = _re.compile(
            r"^(\{[0-9a-f]{8}-[0-9a-f]{4}-[0-9a-f]{4}-[0-9a-f]{4}-"
            r"[0-9a-f]{12}\}|[0-9a-f]{8}-[0-9a-f]{4}-[0-9a-f]{4}-"
            r"[0-9a-f]{4}-[0-9a-f]{12}|[0-9a-f]{32})$", _re.I,
        )
        return _compile_strlut(
            e.args[0], dicts, lambda s: bool(_uuid_re.match(s)), jnp.bool_
        )
    if op in ("is_ipv4", "is_ipv6", "is_ipv4_compat", "is_ipv4_mapped"):
        import ipaddress

        def _ipfn(s, _op=op):
            if _op == "is_ipv4":
                try:
                    ipaddress.IPv4Address(s)
                    return True
                except ValueError:
                    return False
            if _op == "is_ipv6":
                try:
                    ipaddress.IPv6Address(s)
                    return True
                except ValueError:
                    return False
            # *_compat / *_mapped take the BINARY form (INET6_ATON output)
            b = _s2b(s)
            if len(b) != 16:
                return False
            if _op == "is_ipv4_compat":
                return b[:12] == b"\x00" * 12 and b[12:] != b"\x00\x00\x00\x00"
            return b[:10] == b"\x00" * 10 and b[10:12] == b"\xff\xff"

        return _compile_strlut(e.args[0], dicts, _ipfn, jnp.bool_)
    if op == "uncompressed_length":
        import struct

        def _ul(s):
            b = _s2b(s)
            if len(b) <= 4:
                return 0
            return struct.unpack("<I", b[:4])[0]

        return _compile_strlut(e.args[0], dicts, _ul, jnp.int64)
    if op == "inet_aton":
        def _aton(s):
            parts = s.split(".")
            if not 1 <= len(parts) <= 4 or not all(
                p.isdigit() and int(p) <= 255 for p in parts
            ):
                return 0  # MySQL: NULL; LUT carries values only
            # MySQL short forms: leading parts fill the TOP bytes, the
            # last part fills everything remaining ('1.2' = 1<<24 | 2)
            v = 0
            for p in parts[:-1]:
                v = (v << 8) | int(p)
            return (v << (8 * (5 - len(parts)))) | int(parts[-1])

        return _compile_strlut(e.args[0], dicts, _aton, jnp.int64)
    if op == "json_depth":
        import json as _json

        def _depth(s):
            try:
                v = _json.loads(s)
            except Exception:
                return 0

            def d(x):
                if isinstance(x, dict):
                    return 1 + max((d(v2) for v2 in x.values()), default=0)
                if isinstance(x, list):
                    return 1 + max((d(v2) for v2 in x), default=0)
                return 1

            return d(v)

        return _compile_strlut(e.args[0], dicts, _depth, jnp.int64)
    if op == "json_contains_path":
        import json as _json

        one = str(baked_value(e.args[1])).lower() != "all"
        paths = [_json_path_parts(str(baked_value(a))) for a in e.args[2:]]

        def _jcp(s):
            try:
                doc = _json.loads(s)
            except Exception:
                return False
            hits = []
            for parts in paths:
                cur, ok = doc, True
                for p in parts:
                    if isinstance(p, int):
                        if isinstance(cur, list) and p < len(cur):
                            cur = cur[p]
                        else:
                            ok = False
                            break
                    elif isinstance(cur, dict) and p in cur:
                        cur = cur[p]
                    else:
                        ok = False
                        break
                hits.append(ok)
            return any(hits) if one else all(hits)

        return _compile_strlut(e.args[0], dicts, _jcp, jnp.bool_)
    if op == "json_storage_size":
        import json as _json

        def _jss(s):
            try:
                return len(_json.dumps(_json.loads(s)).encode())
            except Exception:
                return 0

        return _compile_strlut(e.args[0], dicts, _jss, jnp.int64)
    if op == "json_overlaps":
        import json as _json

        try:
            other = _json.loads(str(baked_value(e.args[1])))
        except Exception:
            other = None

        def _jov(s):
            try:
                doc = _json.loads(s)
            except Exception:
                return False
            a, b = doc, other
            if isinstance(a, list) and isinstance(b, list):
                return any(x in b for x in a)
            if isinstance(a, dict) and isinstance(b, dict):
                return any(k in b and b[k] == v for k, v in a.items())
            if isinstance(a, list):
                return b in a
            if isinstance(b, list):
                return a in b
            return a == b

        return _compile_strlut(e.args[0], dicts, _jov, jnp.bool_)
    if op in ("period_add", "period_diff"):
        fa, fb = (_compile(a, dicts) for a in e.args)

        def _period(b, _op=op):
            a, c = fa(b), fb(b)
            y1, m1 = a.data // 100, a.data % 100
            months1 = y1 * 12 + (m1 - 1)
            if _op == "period_add":
                t = months1 + c.data
                d = (t // 12) * 100 + (t % 12) + 1
            else:
                y2, m2 = c.data // 100, c.data % 100
                d = months1 - (y2 * 12 + (m2 - 1))
            return DevCol(d.astype(jnp.int64), a.valid & c.valid)

        return _period
    if op == "length":
        return _compile_strlut(e.args[0], dicts, lambda s: len(s.encode()), jnp.int64)
    if op == "char_length":
        return _compile_strlut(e.args[0], dicts, lambda s: len(s), jnp.int64)
    if op == "bit_length":
        return _compile_strlut(
            e.args[0], dicts, lambda s: len(s.encode()) * 8, jnp.int64
        )
    if op == "ascii":
        return _compile_strlut(
            e.args[0], dicts, lambda s: s.encode()[0] if s else 0, jnp.int64
        )
    if op == "ord":
        # MySQL ORD: leading byte sequence value of the first character
        def _ord(s):
            if not s:
                return 0
            bs = s[0].encode()
            v = 0
            for byte in bs:
                v = v * 256 + byte
            return v

        return _compile_strlut(e.args[0], dicts, _ord, jnp.int64)
    if op == "crc32":
        import zlib

        return _compile_strlut(
            e.args[0], dicts, lambda s: zlib.crc32(s.encode()), jnp.int64
        )
    if op == "find_in_set":
        needle_e, setcol = e.args
        if not isinstance(needle_e, Literal):
            raise NotImplementedError("FIND_IN_SET needle must be a literal")
        needle = baked_value(needle_e)
        if needle is None:
            return lambda b: DevCol(
                jnp.zeros(b.capacity, dtype=jnp.int64),
                jnp.zeros(b.capacity, dtype=bool),
            )
        nv = str(needle)

        def _fis(s):
            parts = s.split(",")
            return parts.index(nv) + 1 if nv in parts else 0

        return _compile_strlut(setcol, dicts, _fis, jnp.int64)
    if op in ("regexp", "regexp_like"):
        col, pat = e.args[0], e.args[1]
        if not isinstance(pat, Literal):
            raise NotImplementedError("REGEXP pattern must be a literal")
        pv = baked_value(pat)
        if pv is None:
            return _null_col(jnp.bool_)  # MySQL: NULL pattern -> NULL
        rx = re.compile(str(pv))
        return _compile_strlut(
            col, dicts, lambda s: rx.search(s) is not None, jnp.bool_
        )
    if op == "regexp_instr":
        col, pat = e.args[0], e.args[1]
        if not isinstance(pat, Literal):
            raise NotImplementedError("REGEXP pattern must be a literal")
        pv = baked_value(pat)
        if pv is None:
            return _null_col(jnp.int64)
        rx = re.compile(str(pv))

        def _ri(s):
            m = rx.search(s)
            return (m.start() + 1) if m else 0

        return _compile_strlut(col, dicts, _ri, jnp.int64)
    if op == "interval_fn":
        # INTERVAL(N, a, b, ...): index of the last arg <= N (args
        # assumed ascending, per MySQL); NULL N -> -1
        fns = [_compile(a, dicts) for a in e.args]

        def _ivl(b):
            n = fns[0](b)
            cnt = jnp.zeros(b.capacity, dtype=jnp.int64)
            for f in fns[1:]:
                c = f(b)
                le = c.valid & (c.data.astype(jnp.float64) <= n.data.astype(jnp.float64))
                cnt = cnt + le.astype(jnp.int64)
            return DevCol(jnp.where(n.valid, cnt, -1), jnp.ones(b.capacity, bool))

        return _ivl
    if op == "locate":
        s, sub = e.args
        if not isinstance(sub, Literal):
            raise NotImplementedError("LOCATE needle must be a literal")
        note_baked_param(sub)
        if sub.value is None:
            return lambda b: DevCol(
                jnp.zeros(b.capacity, dtype=jnp.int64),
                jnp.zeros(b.capacity, dtype=bool),
            )
        needle = str(sub.value)
        return _compile_strlut(s, dicts, lambda v: v.find(needle) + 1, jnp.int64)
    if op in _STR_TRANSFORMS or op in _JSON_STR_FUNCS or op in (
        "concat", "concat_ws", "dayname", "monthname", "date_format",
        "hex", "bin", "oct",
    ):
        return string_expr(e, dicts)[0]
    if op in _MATH_UNARY_FLOAT or op in (
        "abs", "sign", "floor", "ceil", "round", "truncate",
    ):
        return _compile_math(e, dicts)
    if op in ("pow", "atan2", "log"):
        return _compile_math2(e, dicts)
    if op == "pi":
        return lambda b: DevCol(
            jnp.full(b.capacity, np.pi, dtype=jnp.float64),
            jnp.ones(b.capacity, dtype=bool),
        )
    if op in ("greatest", "least"):
        return _compile_extremum(e, dicts)
    if op in (
        "to_days", "from_days", "last_day", "week", "weekofyear",
        "makedate", "unix_timestamp", "from_unixtime", "time_to_sec",
        "sec_to_time", "timestampdiff",
    ):
        return _compile_date_misc(e, dicts)
    if op == "str_to_date":
        return _compile_str_to_date(e, dicts)
    raise NotImplementedError(f"compile op {op!r}")


def _compile_literal(e: Literal) -> _CompiledExpr:
    t = e.type
    v = e.value
    if (
        e.param_slot is not None
        and v is not None
        and t is not None
        and t.kind not in (Kind.STRING, Kind.NULL)
    ):
        # runtime parameter slot: the CANONICAL numeric value (python
        # int/float as an array) arrives as a traced input (param_scope)
        # and the physical transform — decimal scaling, dtype — runs
        # inside the program, so one compiled plan serves every bound
        # value. Without an active scope (or a non-numeric binding) the
        # baked value runs AND the slot registers as baked, so any
        # execution path that didn't thread parameters stays sound.
        slot = e.param_slot
        np_dt = phys_dtype(t)
        baked = np.asarray(literal_phys(v, t), dtype=np_dt)
        scale = t.scale if t.kind == Kind.DECIMAL else None

        def _param(b):
            vals = getattr(_param_tls, "vals", None)
            pv = vals.get(slot) if vals else None
            if pv is None:
                note_baked_param(e)
                arr = jnp.asarray(baked, dtype=np_dt)
            else:
                _note_runtime_param(slot)
                raw = jnp.asarray(pv)
                if scale is not None:
                    arr = jnp.round(
                        raw.astype(jnp.float64) * (10**scale)
                    ).astype(jnp.int64)
                else:
                    arr = raw.astype(np_dt)
            data = jnp.broadcast_to(arr, (b.capacity,))
            return DevCol(data, jnp.ones(b.capacity, dtype=bool))

        return _param
    note_baked_param(e)
    if v is None:
        # typed NULL (e.g. the NULL left side of a FULL OUTER JOIN's
        # anti branch): carry the declared type's physical dtype so
        # union concatenation doesn't promote the column
        np_dt = (
            jnp.int64
            if t is None or t.kind == Kind.NULL
            else t.np_dtype
        )

        def _null(b):
            z = jnp.zeros(b.capacity, dtype=np_dt)
            return DevCol(z, jnp.zeros(b.capacity, dtype=bool))

        return _null
    if t.kind == Kind.DECIMAL:
        phys = round(float(v) * 10**t.scale)
        np_dt = jnp.int64
    elif t.kind == Kind.FLOAT:
        phys, np_dt = float(v), jnp.float64
    elif t.kind == Kind.BOOL:
        phys, np_dt = bool(v), jnp.bool_
    elif t.kind == Kind.DATE:
        from tidb_tpu.dtypes import date_to_days

        phys, np_dt = (date_to_days(v) if isinstance(v, str) else int(v)), jnp.int32
    elif t.kind == Kind.DATETIME:
        from tidb_tpu.dtypes import datetime_to_micros

        phys = datetime_to_micros(v) if isinstance(v, str) else int(v)
        np_dt = jnp.int64
    elif t.kind == Kind.TIME:
        from tidb_tpu.dtypes import time_to_micros

        phys = time_to_micros(v) if isinstance(v, str) else int(v)
        np_dt = jnp.int64
    elif t.kind == Kind.STRING:
        # string literal as a value: codes into its own one-entry
        # dictionary (string_expr supplies the dictionary to consumers)
        return string_expr(e, {})[0]
    else:
        phys, np_dt = int(v), jnp.int64

    def _lit(b):
        return DevCol(
            jnp.full(b.capacity, phys, dtype=np_dt), jnp.ones(b.capacity, dtype=bool)
        )

    return _lit


def _is_string_col(e: Expr) -> bool:
    return e.type is not None and e.type.kind == Kind.STRING


def _as_decimal_literal(lit, other_t):
    """A plain float literal compared with a DECIMAL/INT operand, when
    it is a short exact decimal (0.05, 24.5): the same literal typed
    DECIMAL(k), so the comparison runs on exact scaled integers (what
    the literal is in MySQL). The float-space form (col / 10^s vs 0.05)
    leans on float64 division being exact to the last bit, and the
    TPU's emulated float64 is not: `l_discount >= 0.05` dropped every
    0.05 row on the v5e (PR 23). None = keep the float comparison."""
    import dataclasses
    from decimal import Decimal

    from tidb_tpu.dtypes import DECIMAL

    if not isinstance(lit, Literal) or lit.param_slot is not None:
        return None
    if (
        lit.type is None or lit.type.kind != Kind.FLOAT or other_t is None
        or other_t.kind not in (Kind.DECIMAL, Kind.INT)
    ):
        return None
    v = lit.value
    if not isinstance(v, float) or v != v or abs(v) >= 1e15:
        return None
    k = max(-Decimal(repr(v)).as_tuple().exponent, 0)
    s = other_t.scale if other_t.kind == Kind.DECIMAL else 0
    if k > s + 4:  # the column would rescale by 10^(k-s): keep it small
        return None
    return dataclasses.replace(lit, type=DECIMAL(k))


def _compile_binary(e: Func, dicts: DictContext) -> _CompiledExpr:
    op, (ea, eb) = e.op, e.args
    if op in COMPARE or op == "nulleq":
        ea = _as_decimal_literal(ea, eb.type) or ea
        eb = _as_decimal_literal(eb, ea.type) or eb
    # string comparisons: column vs literal -> integer code compare.
    if (op in COMPARE or op == "nulleq") and _is_string_col(ea) and isinstance(eb, Literal):
        return _compile_strcmp(e, dicts, flipped=False)
    if (op in COMPARE or op == "nulleq") and _is_string_col(eb) and isinstance(ea, Literal):
        return _compile_strcmp(e, dicts, flipped=True)
    if (op in COMPARE or op == "nulleq") and _is_string_col(ea) and _is_string_col(eb):
        # general string comparison: remap both sides into a merged sorted
        # dictionary, then compare codes as integers. A CI collation on
        # EITHER side makes the comparison CI (MySQL collation coercion):
        # the merge happens in sort-KEY space, so equal-under-collation
        # values land on equal merged codes.
        from tidb_tpu.utils import collate as _coll

        coll = (ea.type.collation if ea.type is not None else None) or (
            eb.type.collation if eb.type is not None else None
        )
        fa_s, da = string_expr(ea, dicts)
        fb_s, db = string_expr(eb, dicts)
        _m, la, lb = _coll.merge_rank_luts(da, db, coll)
        lut_a, lut_b = jnp.asarray(la), jnp.asarray(lb)

        def _strstr(b):
            a, c = fa_s(b), fb_s(b)
            x = lut_a[jnp.clip(a.data, 0, lut_a.shape[0] - 1)]
            y = lut_b[jnp.clip(c.data, 0, lut_b.shape[0] - 1)]
            valid = a.valid & c.valid
            d = {
                "eq": x == y, "ne": x != y, "lt": x < y,
                "le": x <= y, "gt": x > y, "ge": x >= y,
                "nulleq": x == y,
            }[op]
            if op == "nulleq":
                d = (valid & d) | (~a.valid & ~c.valid)
                return DevCol(d, jnp.ones_like(valid))
            return DevCol(d, valid)

        return _strstr

    fa, fb = _compile(ea, dicts), _compile(eb, dicts)
    ta, tb = ea.type, eb.type
    from tidb_tpu.dtypes import common_type

    if op in COMPARE or op == "nulleq":
        if _is_string_col(ea) and _is_string_col(eb):
            target = None  # compare raw codes
        else:
            target = common_type(ta, tb)
    elif op in ("intdiv", "mod"):
        # align operands at their common type; equal decimal scales cancel
        # in the quotient and are preserved in the remainder.
        target = common_type(ta, tb)
    elif op in BITOPS:
        target = None  # each operand coerces to BIGINT independently
    else:
        target = e.type

    def _bin(b):
        a, c = fa(b), fb(b)
        valid = a.valid & c.valid
        if op in BITOPS:
            x, y = _to_bigint(a.data, ta), _to_bigint(c.data, tb)
        elif target is None:
            x, y = a.data, c.data
        elif op == "div":
            x, y = _to_float(a.data, ta), _to_float(c.data, tb)
        elif op == "mul" and target.kind == Kind.DECIMAL:
            x, y = a.data.astype(jnp.int64), c.data.astype(jnp.int64)
        else:
            x, y = _numeric_align(a.data, ta, c.data, tb, target)
        if op == "add":
            d = x + y
        elif op == "bit_and":
            d = x & y
        elif op == "bit_or":
            d = x | y
        elif op == "bit_xor":
            d = x ^ y
        elif op in ("shl", "shr"):
            # MySQL: shift counts outside [0, 63] yield 0, not UB
            inrange = (y >= 0) & (y < 64)
            ys = jnp.where(inrange, y, 0)
            d = jnp.where(
                inrange,
                (x << ys) if op == "shl" else
                # logical (unsigned) right shift, MySQL semantics
                ((x.astype(jnp.uint64) >> ys.astype(jnp.uint64))
                 .astype(jnp.int64)),
                0,
            )
        elif op == "sub":
            d = x - y
        elif op == "mul":
            d = x * y
        elif op == "div":
            valid = valid & (y != 0)  # MySQL: division by zero -> NULL
            d = x / jnp.where(y == 0, 1.0, y)
        elif op == "intdiv":
            valid = valid & (y != 0)
            ys = jnp.where(y == 0, 1, y)
            if jnp.issubdtype(x.dtype, jnp.floating):
                d = jnp.trunc(x / ys).astype(jnp.int64)
            else:
                # MySQL DIV truncates toward zero; // floors.
                q = x // ys
                d = q + ((x % ys != 0) & ((x < 0) ^ (ys < 0)))
                # decimal operands: the quotient of raw scaled ints over
                # equal scales is already the integer quotient only when
                # scales match; align was done by _numeric_align.
        elif op == "mod":
            valid = valid & (y != 0)
            ys = jnp.where(y == 0, 1, y)
            if jnp.issubdtype(x.dtype, jnp.floating):
                d = x - jnp.trunc(x / ys) * ys
            else:
                # truncated-division remainder (sign follows dividend)
                q = x // ys
                q = q + ((x % ys != 0) & ((x < 0) ^ (ys < 0)))
                d = x - q * ys
        elif op in ("eq", "nulleq"):
            d = x == y
        elif op == "ne":
            d = x != y
        elif op == "lt":
            d = x < y
        elif op == "le":
            d = x <= y
        elif op == "gt":
            d = x > y
        elif op == "ge":
            d = x >= y
        else:  # pragma: no cover
            raise AssertionError(op)
        if op == "add" and e.type and e.type.kind == Kind.DATE:
            d = d.astype(jnp.int32)
        if op == "sub" and e.type and e.type.kind == Kind.DATE:
            d = d.astype(jnp.int32)
        if op == "nulleq":
            # null-safe equality (<=>): never NULL — TRUE when both
            # operands are NULL, FALSE when exactly one is
            d = (valid & d) | (~a.valid & ~c.valid)
            valid = jnp.ones_like(valid)
        return DevCol(d, valid)

    return _bin


def _collation_rank_lut(dictionary, coll):
    """(rank LUT array, sorted distinct key list) for a CI-collated
    dictionary: rank[code] = dense rank of the entry's collation sort
    key — equal keys share a rank, so rank comparison IS the collation
    comparison (reference: collate.go Key()-based compares)."""
    import bisect

    from tidb_tpu.utils import collate as _coll

    kf = _coll.key_fn(coll)
    if not len(dictionary):
        return jnp.zeros(1, jnp.int64), [], kf
    keys = sorted({kf(str(s)) for s in dictionary})
    ranks = np.array(
        [bisect.bisect_left(keys, kf(str(s))) for s in dictionary],
        dtype=np.int64,
    )
    return jnp.asarray(ranks), keys, kf


def _compile_strcmp(e: Func, dicts: DictContext, flipped: bool) -> _CompiledExpr:
    op = e.op
    col, lit = (e.args[1], e.args[0]) if flipped else (e.args[0], e.args[1])
    if flipped:
        op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}.get(op, op)
    assert isinstance(lit, Literal)
    f, dictionary = string_expr(col, dicts)
    note_baked_param(lit)
    if lit.value is None:
        if op == "nulleq":
            # col <=> NULL: TRUE exactly where the column is NULL
            def _nullsafe(b):
                c = f(b)
                return DevCol(~c.valid, jnp.ones_like(c.valid))

            return _nullsafe

        # comparison with NULL is NULL for every row
        def _nullcmp(b):
            c = f(b)
            z = jnp.zeros_like(c.data, dtype=bool)
            return DevCol(z, z)

        return _nullcmp
    from tidb_tpu.utils import collate as _coll

    coll = col.type.collation if col.type is not None else None
    rank_lut = None
    if not _coll.is_binary(coll):
        # CI column: compare dense collation ranks, not raw codes
        import bisect

        rank_lut, keys, kf = _collation_rank_lut(dictionary, coll)
        kl = kf(str(lit.value))
        pos = bisect.bisect_left(keys, kl)
        exact = pos < len(keys) and keys[pos] == kl
    else:
        pos, exact = _string_literal_code(dictionary, str(lit.value))

    def _cmp(b):
        c = f(b)
        code = c.data
        if rank_lut is not None:
            code = rank_lut[jnp.clip(code, 0, rank_lut.shape[0] - 1)]
        if op in ("eq", "nulleq"):
            d = (code == pos) if exact else jnp.zeros_like(code, dtype=bool)
        elif op == "ne":
            d = (code != pos) if exact else jnp.ones_like(code, dtype=bool)
        elif op == "lt":
            d = code < pos
        elif op == "le":
            d = code < (pos + 1 if exact else pos)
        elif op == "gt":
            d = code >= (pos + 1 if exact else pos)
        elif op == "ge":
            d = code >= pos
        else:  # pragma: no cover
            raise AssertionError(op)
        if op == "nulleq":
            # non-NULL literal: TRUE only where the column is non-NULL
            # and equal; never NULL itself
            return DevCol(d & c.valid, jnp.ones_like(c.valid))
        return DevCol(d, c.valid)

    return _cmp


def _compile_logic(e: Func, dicts: DictContext) -> _CompiledExpr:
    op = e.op
    fa, fb = (_compile(a, dicts) for a in e.args)

    def _logic(b):
        a, c = fa(b), fb(b)
        at, ct = a.data.astype(bool), c.data.astype(bool)
        if op == "and":
            true = (a.valid & at) & (c.valid & ct)
            false = (a.valid & ~at) | (c.valid & ~ct)
        else:
            true = (a.valid & at) | (c.valid & ct)
            false = (a.valid & ~at) & (c.valid & ~ct)
        return DevCol(true, true | false)

    return _logic


def _compile_coalesce(e: Func, dicts: DictContext) -> _CompiledExpr:
    fns = [_compile(a, dicts) for a in e.args]
    types = [a.type for a in e.args]
    target = e.type

    def _coal(b):
        cols = [f(b) for f in fns]
        datas = []
        for c, t in zip(cols, types):
            if target.kind == Kind.FLOAT:
                datas.append(_to_float(c.data, t))
            elif target.kind == Kind.DECIMAL and t.kind in (Kind.DECIMAL, Kind.INT):
                datas.append(
                    _rescale(
                        c.data.astype(jnp.int64),
                        target.scale - (t.scale if t.kind == Kind.DECIMAL else 0),
                    )
                )
            else:
                datas.append(c.data)
        out_d, out_v = datas[-1], cols[-1].valid
        for d, c in zip(reversed(datas[:-1]), reversed(cols[:-1])):
            out_d = jnp.where(c.valid, d, out_d)
            out_v = c.valid | out_v
        return DevCol(out_d, out_v)

    return _coal


def _compile_case(e: Func, dicts: DictContext) -> _CompiledExpr:
    args = list(e.args)
    has_else = len(args) % 2 == 1
    else_e = args.pop() if has_else else None
    pairs = [(args[i], args[i + 1]) for i in range(0, len(args), 2)]
    cond_fns = [_compile(c, dicts) for c, _ in pairs]
    val_fns = [_compile(v, dicts) for _, v in pairs]
    val_ts = [v.type for _, v in pairs]
    else_fn = _compile(else_e, dicts) if else_e is not None else None
    else_t = else_e.type if else_e is not None else None
    target = e.type

    def _conv(data, t):
        if target.kind == Kind.FLOAT:
            return _to_float(data, t)
        if target.kind == Kind.DECIMAL:
            s = t.scale if t.kind == Kind.DECIMAL else 0
            return _rescale(data.astype(jnp.int64), target.scale - s)
        return data

    def _case(b):
        if else_fn is not None:
            ec = else_fn(b)
            out_d, out_v = _conv(ec.data, else_t), ec.valid
        else:
            out_d = _conv(jnp.zeros(b.capacity, dtype=jnp.int64), FLOAT64 if target.kind == Kind.FLOAT else target)
            out_v = jnp.zeros(b.capacity, dtype=bool)
        for cf, vf, vt in zip(reversed(cond_fns), reversed(val_fns), reversed(val_ts)):
            c, v = cf(b), vf(b)
            take = c.valid & c.data.astype(bool)
            out_d = jnp.where(take, _conv(v.data, vt), out_d)
            out_v = jnp.where(take, v.valid, out_v)
        return DevCol(out_d, out_v)

    return _case


def _compile_cast(e: Func, dicts: DictContext) -> _CompiledExpr:
    (a,) = e.args
    f = _compile(a, dicts)
    src, dst = a.type, e.type

    if src.kind == Kind.STRING and dst.kind == Kind.DATE:
        # parse the dictionary once on host; bad dates -> NULL
        f, dictionary = string_expr(a, dicts)
        from tidb_tpu.dtypes import date_to_days

        days = np.zeros(max(len(dictionary), 1), dtype=np.int32)
        ok = np.zeros(max(len(dictionary), 1), dtype=bool)
        for i, s in enumerate(dictionary.tolist()):
            try:
                days[i] = date_to_days(str(s))
                ok[i] = True
            except Exception:
                pass
        days_j, ok_j = jnp.asarray(days), jnp.asarray(ok)

        def _cast_d(b):
            c = f(b)
            codes = jnp.clip(c.data, 0, days_j.shape[0] - 1)
            return DevCol(days_j[codes], c.valid & ok_j[codes])

        return _cast_d

    if src.kind == Kind.STRING and dst.kind in (Kind.DATETIME, Kind.TIME):
        # parse the dictionary once on host; bad values -> NULL
        f, dictionary = string_expr(a, dicts)
        from tidb_tpu.dtypes import datetime_to_micros, time_to_micros

        parse = datetime_to_micros if dst.kind == Kind.DATETIME else time_to_micros
        us = np.zeros(max(len(dictionary), 1), dtype=np.int64)
        ok = np.zeros(max(len(dictionary), 1), dtype=bool)
        for i, s in enumerate(dictionary.tolist()):
            try:
                us[i] = parse(str(s))
                ok[i] = True
            except Exception:
                pass
        us_j, ok_j = jnp.asarray(us), jnp.asarray(ok)

        def _cast_dt(b):
            c = f(b)
            codes = jnp.clip(c.data, 0, us_j.shape[0] - 1)
            return DevCol(us_j[codes], c.valid & ok_j[codes])

        return _cast_dt

    if src.kind == Kind.DATE and dst.kind == Kind.DATETIME:

        def _cast_d2dt(b):
            from tidb_tpu.dtypes import US_PER_DAY

            c = f(b)
            return DevCol(c.data.astype(jnp.int64) * US_PER_DAY, c.valid)

        return _cast_d2dt

    if src.kind == Kind.DATETIME and dst.kind == Kind.DATE:

        def _cast_dt2d(b):
            from tidb_tpu.dtypes import US_PER_DAY

            c = f(b)
            return DevCol((c.data // US_PER_DAY).astype(jnp.int32), c.valid)

        return _cast_dt2d

    if src.kind == Kind.STRING and dst.kind in (Kind.FLOAT, Kind.INT, Kind.DECIMAL):
        # host LUT over the dictionary: string -> numeric
        f, dictionary = string_expr(a, dicts)

        def _tonum(s):
            try:
                return float(s)
            except ValueError:
                m = re.match(r"\s*-?\d+(\.\d+)?", s)
                return float(m.group(0)) if m else 0.0

        lut = (
            np.array([_tonum(s) for s in dictionary], dtype=np.float64)
            if len(dictionary)
            else np.zeros(1, dtype=np.float64)
        )
        if dst.kind == Kind.INT:
            lut_j = jnp.asarray(np.round(lut).astype(np.int64))
        elif dst.kind == Kind.DECIMAL:
            lut_j = jnp.asarray(np.round(lut * 10**dst.scale).astype(np.int64))
        else:
            lut_j = jnp.asarray(lut)

        def _cast_s(b):
            c = f(b)
            return DevCol(lut_j[jnp.clip(c.data, 0, lut_j.shape[0] - 1)], c.valid)

        return _cast_s

    def _cast(b):
        c = f(b)
        d = c.data
        if dst.kind == Kind.FLOAT:
            d = _to_float(d, src)
        elif dst.kind == Kind.INT:
            if src.kind == Kind.DECIMAL:
                d = _rescale(d, -src.scale)
            elif src.kind == Kind.FLOAT:
                d = jnp.round(d).astype(jnp.int64)
            else:
                d = d.astype(jnp.int64)
        elif dst.kind == Kind.DECIMAL:
            if src.kind == Kind.DECIMAL:
                d = _rescale(d, dst.scale - src.scale)
            elif src.kind == Kind.FLOAT:
                d = jnp.round(d * 10**dst.scale).astype(jnp.int64)
            else:
                d = d.astype(jnp.int64) * (10**dst.scale)
        elif dst.kind == Kind.DATE:
            d = d.astype(jnp.int32)
        elif dst.kind == Kind.BOOL:
            d = d.astype(bool)
        else:
            raise NotImplementedError(f"cast {src} -> {dst}")
        return DevCol(d, c.valid)

    return _cast


def _compile_like(e: Func, dicts: DictContext) -> _CompiledExpr:
    col, pat = e.args
    assert isinstance(pat, Literal), "LIKE pattern must be a literal"
    negate = False
    rx = _like_to_regex(str(baked_value(pat)))
    return _compile_strlut(
        col, dicts, lambda s: bool(rx.match(s)) != negate, jnp.bool_
    )


def _compile_strlut(col: Expr, dicts: DictContext, pyfn, out_dtype) -> _CompiledExpr:
    f, dictionary = string_expr(col, dicts)
    lut = jnp.asarray(
        np.array([pyfn(str(s)) for s in dictionary]).astype(np.dtype(out_dtype))
        if len(dictionary)
        else np.zeros(1, dtype=np.dtype(out_dtype))
    )

    def _lutf(b):
        c = f(b)
        codes = jnp.clip(c.data, 0, lut.shape[0] - 1)
        return DevCol(lut[codes], c.valid)

    return _lutf


def _compile_in(e: Func, dicts: DictContext) -> _CompiledExpr:
    col, *lits = e.args
    # MySQL: x IN (a, b, NULL) is TRUE on match, otherwise NULL.
    for l in lits:
        note_baked_param(l)
    has_null = any(l.value is None for l in lits)
    lits = [l for l in lits if l.value is not None]
    if _is_string_col(col):
        vals = set(str(l.value) for l in lits)
        match_fn = _compile_strlut(col, dicts, lambda s: s in vals, jnp.bool_)
    else:
        f = _compile(col, dicts)
        t = col.type
        phys = [literal_phys(l.value, t) for l in lits]
        consts = jnp.asarray(np.array(phys)) if phys else None

        def match_fn(b):
            c = f(b)
            if consts is None:
                return DevCol(jnp.zeros(b.capacity, dtype=bool), c.valid)
            d = (c.data[:, None] == consts[None, :]).any(axis=1)
            return DevCol(d, c.valid)

    def _in(b):
        m = match_fn(b)
        valid = m.valid & m.data if has_null else m.valid
        return DevCol(m.data, valid)

    return _in


# math builtins (reference: pkg/expression/builtin_math_vec.go)
_MATH_UNARY_FLOAT = {
    "sqrt", "exp", "ln", "log2", "log10", "radians", "degrees",
    "sin", "cos", "tan", "asin", "acos", "atan", "cot",
}


def _compile_math(e: Func, dicts: DictContext) -> _CompiledExpr:
    op = e.op
    a0 = e.args[0]
    f = _compile(a0, dicts)
    src = a0.type

    if op in _MATH_UNARY_FLOAT:
        def _mf(b):
            c = f(b)
            x = _to_float(c.data, src)
            valid = c.valid
            if op == "sqrt":
                valid = valid & (x >= 0)  # MySQL: SQRT(neg) -> NULL
                d = jnp.sqrt(jnp.maximum(x, 0.0))
            elif op == "exp":
                d = jnp.exp(x)
            elif op in ("ln", "log2", "log10"):
                valid = valid & (x > 0)
                xs = jnp.where(x > 0, x, 1.0)
                d = {
                    "ln": jnp.log(xs),
                    "log2": jnp.log2(xs),
                    "log10": jnp.log10(xs),
                }[op]
            elif op == "radians":
                d = x * (np.pi / 180.0)
            elif op == "degrees":
                d = x * (180.0 / np.pi)
            elif op == "cot":
                d = 1.0 / jnp.tan(x)
            else:
                d = getattr(jnp, op)(x)
            return DevCol(d, valid)

        return _mf

    if op == "abs":
        return lambda b: (lambda c: DevCol(jnp.abs(c.data), c.valid))(f(b))
    if op == "sign":
        def _sgn(b):
            c = f(b)
            return DevCol(jnp.sign(c.data).astype(jnp.int64), c.valid)
        return _sgn

    if op in ("floor", "ceil"):
        def _fc(b):
            c = f(b)
            d = c.data
            if src.kind == Kind.FLOAT:
                d = (jnp.floor(d) if op == "floor" else jnp.ceil(d)).astype(jnp.int64)
            elif src.kind == Kind.DECIMAL:
                q = 10 ** src.scale
                d = d // q if op == "floor" else -((-d) // q)
            else:
                d = d.astype(jnp.int64)
            return DevCol(d, c.valid)
        return _fc

    # round/truncate with optional digits literal (default 0); rounding is
    # half-away-from-zero for exact types, matching MySQL DECIMAL rules.
    digits = 0
    if len(e.args) > 1:
        if not isinstance(e.args[1], Literal):
            raise NotImplementedError(
                f"{op.upper()} digits must be a literal"
            )
        note_baked_param(e.args[1])
        if e.args[1].value is None:
            # MySQL: ROUND(x, NULL) is NULL for every row
            ndt = jnp.float64 if e.type.kind == Kind.FLOAT else jnp.int64
            return lambda b: DevCol(
                jnp.zeros(b.capacity, dtype=ndt),
                jnp.zeros(b.capacity, dtype=bool),
            )
        digits = int(e.args[1].value)
    trunc = op == "truncate"

    def _round(b):
        c = f(b)
        d = c.data
        if src.kind == Kind.FLOAT:
            factor = 10.0 ** digits
            x = d * factor
            if trunc:
                x = jnp.trunc(x)
            else:
                x = jnp.where(x >= 0, jnp.floor(x + 0.5), jnp.ceil(x - 0.5))
            return DevCol(x / factor, c.valid)
        s = src.scale if src.kind == Kind.DECIMAL else 0
        if digits >= s:
            out = _rescale(d.astype(jnp.int64), max(digits, 0) - s if src.kind == Kind.DECIMAL else 0)
            return DevCol(out, c.valid)
        q = 10 ** (s - digits)
        av = jnp.abs(d.astype(jnp.int64))
        mag = av // q if trunc else (av + q // 2) // q
        out = jnp.sign(d).astype(jnp.int64) * mag
        # out is at scale `digits`; the inferred type is DECIMAL(digits)
        # for digits>0, else INT64 (scale 0) -> undo negative scales
        if digits < 0:
            out = out * (10 ** -digits)
        return DevCol(out, c.valid)

    return _round


def _compile_math2(e: Func, dicts: DictContext) -> _CompiledExpr:
    op = e.op
    if op == "log" and len(e.args) == 1:
        return _compile_math(Func(op="ln", args=e.args, type=e.type), dicts)
    fa, fb = (_compile(a, dicts) for a in e.args)
    ta, tb = e.args[0].type, e.args[1].type

    def _m2(b):
        a, c = fa(b), fb(b)
        x, y = _to_float(a.data, ta), _to_float(c.data, tb)
        valid = a.valid & c.valid
        if op == "pow":
            d = jnp.power(x, y)
        elif op == "atan2":
            d = jnp.arctan2(x, y)
        else:  # log(base, x) = ln(x)/ln(base)
            valid = valid & (x > 0) & (x != 1.0) & (y > 0)
            d = jnp.log(jnp.where(y > 0, y, 1.0)) / jnp.log(
                jnp.where((x > 0) & (x != 1.0), x, 2.0)
            )
        return DevCol(d, valid)

    return _m2


def _compile_extremum(e: Func, dicts: DictContext) -> _CompiledExpr:
    """GREATEST/LEAST: all args aligned at the inferred common type;
    NULL if any argument is NULL (MySQL semantics)."""
    fns = [_compile(a, dicts) for a in e.args]
    types = [a.type for a in e.args]
    target = e.type
    pick = jnp.maximum if e.op == "greatest" else jnp.minimum

    def _conv(data, t):
        if target.kind == Kind.FLOAT:
            return _to_float(data, t)
        if target.kind == Kind.DECIMAL:
            s = t.scale if t.kind == Kind.DECIMAL else 0
            return _rescale(data.astype(jnp.int64), target.scale - s)
        return data.astype(jnp.int64)

    def _ext(b):
        cols = [f(b) for f in fns]
        out = _conv(cols[0].data, types[0])
        valid = cols[0].valid
        for c, t in zip(cols[1:], types[1:]):
            out = pick(out, _conv(c.data, t))
            valid = valid & c.valid
        return DevCol(out, valid)

    return _ext


def _civil_from_days(days):
    """days-since-epoch -> (y, m, d), branchless civil calendar (same
    algorithm as _compile_extract; Howard Hinnant's public-domain
    civil_from_days)."""
    z = days + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d


def _days_from_civil(y, m, d):
    """(y, m, d) -> days-since-epoch (inverse of _civil_from_days)."""
    y = y - (m <= 2)
    era = y // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


# MySQL day 0 of TO_DAYS/FROM_DAYS is year 0; the engine's epoch is
# 1970-01-01, which is day 719528 in that reckoning
_MYSQL_DAY0 = 719528


def _compile_date_misc(e: Func, dicts: DictContext) -> _CompiledExpr:
    """Calendar builtins that reduce to civil-date arithmetic on device
    (reference: pkg/expression/builtin_time.go families)."""
    op = e.op
    from tidb_tpu.dtypes import US_PER_DAY

    fns = [_compile(a, dicts) for a in e.args]
    t0 = e.args[0].type if e.args else None

    def unary(fn):
        def _f(b):
            c = fns[0](b)
            data, valid = fn(c)
            return DevCol(data, valid & c.valid)

        return _f

    if op == "to_days":
        return unary(lambda c: (
            (_to_days(c.data, t0) + _MYSQL_DAY0).astype(jnp.int64),
            jnp.ones_like(c.valid),
        ))
    if op == "from_days":
        return unary(lambda c: (
            (c.data.astype(jnp.int64) - _MYSQL_DAY0).astype(jnp.int32),
            jnp.ones_like(c.valid),
        ))
    if op == "last_day":
        def _ld(c):
            days = _to_days(c.data, t0)
            y, m, _d = _civil_from_days(days)
            y2 = jnp.where(m == 12, y + 1, y)
            m2 = jnp.where(m == 12, 1, m + 1)
            out = _days_from_civil(y2, m2, jnp.ones_like(m2)) - 1
            return out.astype(jnp.int32), jnp.ones_like(c.valid)

        return unary(_ld)
    if op in ("week", "weekofyear"):
        # weekofyear == WEEK(d, 3): ISO 8601 week number. WEEK(d)
        # defaults to mode 0 (Sunday-start, weeks counted from 0);
        # WEEK(d, 3) maps to the ISO path, other modes are rejected
        # rather than silently computed as mode 0.
        iso = op == "weekofyear"
        if op == "week" and len(e.args) > 1:
            if not isinstance(e.args[1], Literal):
                raise NotImplementedError("WEEK mode must be a literal")
            mode = baked_value(e.args[1])
            if mode is None:
                return _null_col(jnp.int64)  # MySQL: NULL mode -> NULL
            if mode == 3:
                iso = True
            elif mode != 0:
                raise NotImplementedError(f"WEEK mode {mode}")

        def _week(c):
            days = _to_days(c.data, t0)
            y, _m, _d = _civil_from_days(days)
            jan1 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
            if iso:
                # ISO: week containing the year's first Thursday is 1
                dow = (days + 3) % 7  # Monday=0
                thursday = days - dow + 3
                ty, _tm, _td = _civil_from_days(thursday)
                tjan1 = _days_from_civil(
                    ty, jnp.ones_like(ty), jnp.ones_like(ty)
                )
                out = (thursday - tjan1) // 7 + 1
            else:
                # mode 0: weeks start Sunday; days before the first
                # Sunday are week 0
                jdow = (jan1 + 4) % 7  # Sunday=0
                first_sunday = jan1 + (7 - jdow) % 7
                out = jnp.where(
                    days < first_sunday, 0, (days - first_sunday) // 7 + 1
                )
            return out.astype(jnp.int64), jnp.ones_like(c.valid)

        return unary(_week)
    if op == "makedate":
        def _md(b):
            cy, cn = fns[0](b), fns[1](b)
            y = cy.data.astype(jnp.int64)
            n = cn.data.astype(jnp.int64)
            out = _days_from_civil(
                y, jnp.ones_like(y), jnp.ones_like(y)
            ) + n - 1
            valid = cy.valid & cn.valid & (n >= 1)
            return DevCol(out.astype(jnp.int32), valid)

        return _md
    if op == "unix_timestamp":
        return unary(lambda c: (
            _to_micros(c.data, t0) // 1_000_000,
            jnp.ones_like(c.valid),
        ))
    if op == "from_unixtime":
        return unary(lambda c: (
            (c.data.astype(jnp.int64) * 1_000_000),
            jnp.ones_like(c.valid),
        ))
    if op == "time_to_sec":
        return unary(lambda c: (
            c.data.astype(jnp.int64) // 1_000_000,
            jnp.ones_like(c.valid),
        ))
    if op == "sec_to_time":
        return unary(lambda c: (
            c.data.astype(jnp.int64) * 1_000_000,
            jnp.ones_like(c.valid),
        ))
    if op == "timestampdiff":
        unit = str(baked_value(e.args[0])).lower()
        fa, fb = fns[1], fns[2]
        ta, tb = e.args[1].type, e.args[2].type

        def _tsd(b):
            a, c = fa(b), fb(b)
            ua, ub = _to_micros(a.data, ta), _to_micros(c.data, tb)
            if unit in ("microsecond", "second", "minute", "hour", "day", "week"):
                div = {
                    "microsecond": 1,
                    "second": 1_000_000,
                    "minute": 60_000_000,
                    "hour": 3_600_000_000,
                    "day": US_PER_DAY,
                    "week": 7 * US_PER_DAY,
                }[unit]
                out = (ub - ua) // div
                # MySQL truncates toward zero, jnp // floors
                out = jnp.where(
                    (ub < ua) & ((ub - ua) % div != 0), out + 1, out
                )
            else:  # month / quarter / year: civil month distance,
                # decremented when the partial month is incomplete
                da, db_ = ua // US_PER_DAY, ub // US_PER_DAY
                ya, ma, dda = _civil_from_days(da)
                yb, mb, ddb = _civil_from_days(db_)
                months = (yb - ya) * 12 + (mb - ma)
                toa, tob = ua % US_PER_DAY, ub % US_PER_DAY
                fwd = (ddb < dda) | ((ddb == dda) & (tob < toa))
                bwd = (ddb > dda) | ((ddb == dda) & (tob > toa))
                months = jnp.where(
                    (months > 0) & fwd, months - 1,
                    jnp.where((months < 0) & bwd, months + 1, months),
                )
                out = {
                    "month": months,
                    "quarter": months // 3,
                    "year": months // 12,
                }.get(unit)
                if out is None:
                    raise NotImplementedError(f"TIMESTAMPDIFF unit {unit}")
                if unit in ("quarter", "year"):
                    d = 3 if unit == "quarter" else 12
                    out = jnp.where(
                        (months < 0) & (months % d != 0), out + 1, out
                    )
            return DevCol(out.astype(jnp.int64), a.valid & c.valid)

        return _tsd
    raise NotImplementedError(op)


_MYSQL_FMT = {
    "%Y": "%Y", "%y": "%y", "%m": "%m", "%d": "%d", "%H": "%H",
    "%i": "%M", "%s": "%S", "%S": "%S", "%M": "%B", "%b": "%b",
    "%a": "%a", "%W": "%A", "%p": "%p", "%f": "%f", "%j": "%j",
    "%T": "%H:%M:%S", "%r": "%I:%M:%S %p", "%%": "%%", "%h": "%I",
    "%I": "%I", "%e": "%d", "%c": "%m", "%k": "%H", "%l": "%I",
}


def _mysql_fmt_to_py(fmt: str) -> str:
    out = []
    i = 0
    while i < len(fmt):
        ch = fmt[i]
        if ch == "%" and i + 1 < len(fmt):
            tok = fmt[i:i + 2]
            py = _MYSQL_FMT.get(tok)
            if py is None:
                raise NotImplementedError(f"date format token {tok}")
            out.append(py)
            i += 2
        else:
            out.append(ch)
            i += 1
    return "".join(out)


def _compile_str_to_date(e: Func, dicts: DictContext) -> _CompiledExpr:
    """STR_TO_DATE over a string column: per-dictionary-entry strptime
    on the host, gathered by code on device (the LIKE-LUT pattern)."""
    import datetime as _dt

    col, fmt_e = e.args
    fmt_v = baked_value(fmt_e)
    is_dt0 = e.type is not None and e.type.kind == Kind.DATETIME
    if fmt_v is None:
        return _null_col(jnp.int64 if is_dt0 else jnp.int32)
    pyfmt = _mysql_fmt_to_py(str(fmt_v))
    is_dt = e.type is not None and e.type.kind == Kind.DATETIME
    from tidb_tpu.dtypes import date_to_days, datetime_to_micros

    def _parse(s):
        try:
            d = _dt.datetime.strptime(s, pyfmt)
        except ValueError:
            return np.iinfo(np.int64).min  # NULL marker
        if is_dt:
            return int(datetime_to_micros(d.strftime("%Y-%m-%d %H:%M:%S.%f")))
        return int(date_to_days(d.strftime("%Y-%m-%d")))

    f, dictionary = string_expr(col, dicts)
    vals = np.array(
        [_parse(str(s)) for s in dictionary], dtype=np.int64
    ) if len(dictionary) else np.zeros(1, dtype=np.int64)
    lut = jnp.asarray(vals)
    bad = jnp.asarray(vals == np.iinfo(np.int64).min)
    out_dt = jnp.int64 if is_dt else jnp.int32

    def _std(b):
        c = f(b)
        codes = jnp.clip(c.data, 0, lut.shape[0] - 1)
        return DevCol(
            lut[codes].astype(out_dt), c.valid & ~bad[codes]
        )

    return _std


def _compile_add_months(e: Func, dicts: DictContext) -> _CompiledExpr:
    """MySQL-exact month arithmetic on device: shift by N months, clamp
    day-of-month to the target month length (reference:
    pkg/types/time.go AddDate semantics; no 30-day approximation)."""
    col, nexpr = e.args
    f = _compile(col, dicts)
    fn = _compile(nexpr, dicts)
    _MLEN = jnp.asarray([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31])

    is_dt = col.type is not None and col.type.kind == Kind.DATETIME

    def _am(b):
        from tidb_tpu.dtypes import US_PER_DAY

        c = f(b)
        n = fn(b)
        raw = c.data.astype(jnp.int64)
        # DATETIME: month-shift the calendar day, carry time of day
        days = raw // US_PER_DAY if is_dt else raw
        tod = raw % US_PER_DAY if is_dt else None
        y, m, d = _civil_from_days(days)
        total = y * 12 + (m - 1) + n.data.astype(jnp.int64)
        y2 = total // 12
        m2 = total % 12 + 1
        leap = (y2 % 4 == 0) & ((y2 % 100 != 0) | (y2 % 400 == 0))
        mlen = _MLEN[m2 - 1] + jnp.where((m2 == 2) & leap, 1, 0)
        d2 = jnp.minimum(d, mlen)
        out = _days_from_civil(y2, m2, d2)
        if is_dt:
            out = out * US_PER_DAY + tod
        return DevCol(out.astype(c.data.dtype), c.valid & n.valid)

    return _am


def _to_days(data, t):
    """Temporal value -> days-since-epoch (DATETIME truncates micros)."""
    if t is not None and t.kind == Kind.DATETIME:
        from tidb_tpu.dtypes import US_PER_DAY

        return data.astype(jnp.int64) // US_PER_DAY
    return data.astype(jnp.int64)


def _to_micros(data, t):
    """Temporal value -> micros-since-epoch (DATE promotes to midnight)."""
    if t is not None and t.kind == Kind.DATE:
        from tidb_tpu.dtypes import US_PER_DAY

        return data.astype(jnp.int64) * US_PER_DAY
    return data.astype(jnp.int64)


def _compile_time_part(e: Func, dicts: DictContext) -> _CompiledExpr:
    """HOUR/MINUTE/SECOND/MICROSECOND of a DATETIME (time of day) or
    TIME (duration components, sign dropped like MySQL's HOUR())."""
    part = e.op
    (col,) = e.args
    f = _compile(col, dicts)
    t = col.type

    def _tp(b):
        from tidb_tpu.dtypes import US_PER_DAY, US_PER_SECOND

        c = f(b)
        us = c.data.astype(jnp.int64)
        if t is not None and t.kind == Kind.DATETIME:
            us = us % US_PER_DAY  # time of day (floor mod: correct pre-1970)
        elif t is not None and t.kind == Kind.TIME:
            us = jnp.abs(us)
        else:
            # DATE (or numeric) argument has no time part: MySQL returns 0
            us = jnp.zeros_like(us)
        if part == "hour":
            out = us // (3600 * US_PER_SECOND)
        elif part == "minute":
            out = (us // (60 * US_PER_SECOND)) % 60
        elif part == "second":
            out = (us // US_PER_SECOND) % 60
        else:  # microsecond
            out = us % US_PER_SECOND
        return DevCol(out, c.valid)

    return _tp


def _compile_extract(e: Func, dicts: DictContext) -> _CompiledExpr:
    """YEAR/MONTH/DAY from days-since-epoch, branchless civil calendar
    (integer algorithm; computes on device with no host round-trip)."""
    part = e.op
    (col,) = e.args
    f = _compile(col, dicts)

    def _ext(b):
        c = f(b)
        days = _to_days(c.data, col.type)
        z = days + 719468
        # jnp // already floors (unlike C), so no negative-z adjustment.
        era = z // 146097
        doe = z - era * 146097
        yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
        y = yoe + era * 400
        doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
        mp = (5 * doy + 2) // 153
        d = doy - (153 * mp + 2) // 5 + 1
        m = jnp.where(mp < 10, mp + 3, mp - 9)
        y = jnp.where(m <= 2, y + 1, y)
        if part == "year":
            out = y
        elif part == "month":
            out = m
        elif part == "day":
            out = d
        elif part == "quarter":
            out = (m + 2) // 3
        elif part == "dayofweek":
            # 1970-01-01 was a Thursday; MySQL numbers Sunday=1..Saturday=7
            out = (days + 4) % 7 + 1
        elif part == "weekday":
            # MySQL WEEKDAY: Monday=0..Sunday=6
            out = (days + 3) % 7
        else:  # dayofyear: days since Jan 1 of the civil year y
            y2 = y - 1
            era2 = y2 // 400
            yoe2 = y2 - era2 * 400
            doe2 = yoe2 * 365 + yoe2 // 4 - yoe2 // 100 + 306
            jan1 = era2 * 146097 + doe2 - 719468
            out = days - jan1 + 1
        return DevCol(out.astype(jnp.int64), c.valid)

    return _ext
