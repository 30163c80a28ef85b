"""ctypes bindings for the native loader (native/loader.cpp).

The shared library is not tracked: it is built on first use with g++
from native/loader.cpp (no pybind11 in the image; ctypes avoids any
build-time Python dependency), without -march flags, so a checkout
builds what it runs. Without a compiler the Python loader is the
defined behaviour (native_load returns None); a compiler that is there
and fails is an error. Arrays are wrapped as numpy views over the C++
vectors and copied once into HostColumns.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

from tidb_tpu.utils import racecheck

import numpy as np

from tidb_tpu.chunk import HostBlock, HostColumn, encode_strings
from tidb_tpu.dtypes import Kind

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "_native.so")
_SRC = os.path.join(os.path.dirname(os.path.dirname(_HERE)), "native", "loader.cpp")
_lock = racecheck.make_lock("storage.native")
_lib = None
_no_compiler = False

_TYPECODE = {
    Kind.INT: 0,
    Kind.FLOAT: 1,
    Kind.STRING: 2,
    Kind.DATE: 3,
    Kind.DECIMAL: 4,
    Kind.BOOL: 5,
}


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _no_compiler
    with _lock:
        if _lib is not None:
            return _lib
        if _no_compiler:
            return None
        if not os.path.exists(_SO):
            try:
                # lock-blocking-ok: the lazy one-shot native build
                # deliberately holds the module lock so racing loaders
                # compile once; the lock is leaf-level and every later
                # call takes the fast already-built path
                subprocess.run(
                    [
                        "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                        "-o", _SO, _SRC,
                    ],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
            except FileNotFoundError:
                _no_compiler = True  # no g++: the Python loader answers
                return None
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    "building native/loader.cpp failed:\n"
                    + e.stderr.decode(errors="replace")[-2000:]
                ) from e
        lib = ctypes.CDLL(_SO)
        lib.tt_parse_file.restype = ctypes.c_void_p
        lib.tt_parse_file.argtypes = [
            ctypes.c_char_p, ctypes.c_char, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ]
        lib.tt_error.restype = ctypes.c_char_p
        lib.tt_error.argtypes = [ctypes.c_void_p]
        lib.tt_nrows.restype = ctypes.c_int64
        lib.tt_nrows.argtypes = [ctypes.c_void_p]
        for name in ("tt_col_i64", "tt_col_stroffsets"):
            getattr(lib, name).restype = ctypes.POINTER(ctypes.c_int64)
            getattr(lib, name).argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.tt_col_f64.restype = ctypes.POINTER(ctypes.c_double)
        lib.tt_col_f64.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.tt_col_valid.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.tt_col_valid.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.tt_col_strbytes.restype = ctypes.POINTER(ctypes.c_char)
        lib.tt_col_strbytes.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64)
        ]
        lib.tt_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_load(table, path: str, sep: str) -> Optional[int]:
    """Parse with the C++ loader and append to the table. Returns None if
    the native library is unavailable (caller falls back to Python)."""
    lib = _load()
    if lib is None or len(sep) != 1:
        return None
    if any(t.kind not in _TYPECODE for _n, t in table.schema.columns):
        return None  # e.g. DATETIME/TIME: python parser handles these
    names = table.schema.names
    types = [t for _, t in table.schema.columns]
    n = len(names)
    codes = (ctypes.c_int * n)(*[_TYPECODE[t.kind] for t in types])
    scales = (ctypes.c_int * n)(*[t.scale for t in types])
    h = lib.tt_parse_file(path.encode(), sep.encode(), n, codes, scales)
    try:
        err = lib.tt_error(h)
        if err:
            raise ValueError(f"native load: {err.decode()}")
        nrows = lib.tt_nrows(h)
        if nrows == 0:
            return 0
        cols = {}
        for i, (name, typ) in enumerate(zip(names, types)):
            valid = np.ctypeslib.as_array(lib.tt_col_valid(h, i), (nrows,)).astype(bool)
            if typ.kind == Kind.STRING:
                blen = ctypes.c_int64()
                bptr = lib.tt_col_strbytes(h, i, ctypes.byref(blen))
                raw = ctypes.string_at(bptr, blen.value)
                offs = np.ctypeslib.as_array(lib.tt_col_stroffsets(h, i), (nrows + 1,))
                values = [
                    raw[offs[r]: offs[r + 1]].decode("utf-8", "replace")
                    if valid[r]
                    else None
                    for r in range(nrows)
                ]
                cols[name] = encode_strings(values)
            elif typ.kind == Kind.FLOAT:
                data = np.ctypeslib.as_array(lib.tt_col_f64(h, i), (nrows,)).copy()
                cols[name] = HostColumn(typ, data, valid.copy())
            else:
                data = np.ctypeslib.as_array(lib.tt_col_i64(h, i), (nrows,)).copy()
                data = data.astype(typ.np_dtype)
                cols[name] = HostColumn(typ, data, valid.copy())
        table.append_block(HostBlock.from_columns(cols))
        return int(nrows)
    finally:
        lib.tt_free(h)
