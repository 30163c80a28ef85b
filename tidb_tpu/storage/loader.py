"""Bulk file loading (LOAD DATA INFILE).

Reference: pkg/executor/load_data.go + Lightning's mydump parsers. The
hot path (byte scanning, field splitting, numeric parsing) belongs in
native code; tidb_tpu ships a C++ splitter (native/loader.cpp, built via
ctypes — see native/build.sh) with a pure-Python fallback so LOAD DATA
works even before the extension is compiled.
"""

from __future__ import annotations

from typing import List, Optional

from tidb_tpu.chunk import HostBlock, column_from_values
from tidb_tpu.dtypes import Kind


def _parse_value(text: str, typ):
    if text == "" or text == r"\N":
        return None
    k = typ.kind
    if k == Kind.INT:
        return int(float(text)) if "." in text or "e" in text.lower() else int(text)
    if k == Kind.FLOAT:
        return float(text)
    if k == Kind.DECIMAL:
        return float(text)
    if k == Kind.BOOL:
        return text.strip().lower() in ("1", "true", "on", "yes")
    return text  # STRING / DATE handled by column_from_values


def parse_block(table, lines: List[str], sep: str) -> Optional[HostBlock]:
    """Parse text rows into an (unappended) HostBlock — the Encode step
    shared by direct LOAD DATA and the DXF import pipeline's staged
    EncodeAndSort subtasks."""
    names = table.schema.names
    types = [t for _, t in table.schema.columns]
    cols: List[List] = [[] for _ in names]
    n = 0
    for line in lines:
        line = line.rstrip("\n").rstrip("\r")
        if not line:
            continue
        parts = line.split(sep)
        if parts and parts[-1] == "" and len(parts) == len(names) + 1:
            parts = parts[:-1]  # dbgen-style trailing separator
        if len(parts) != len(names):
            raise ValueError(
                f"row has {len(parts)} fields, table {table.name} has {len(names)}"
            )
        for i, (text, typ) in enumerate(zip(parts, types)):
            cols[i].append(_parse_value(text, typ))
        n += 1
    if n == 0:
        return None
    return HostBlock.from_columns(
        {name: column_from_values(vals, typ) for name, vals, typ in zip(names, cols, types)}
    )


def load_rows_python(table, lines: List[str], sep: str) -> int:
    block = parse_block(table, lines, sep)
    if block is None:
        return 0
    table.append_block(block)
    return block.nrows


def load_file(table, path: str, sep: str = "\t") -> int:
    """Load a delimited file; uses the native splitter when available
    (None = no compiler / unsupported column kinds: the Python loader is
    the defined behaviour). A native parse rejection falls through to the
    Python parser, which reports the row; a failed build raises."""
    from tidb_tpu.storage.native import native_load  # C++ fast path

    try:
        res = native_load(table, path, sep)
    except ValueError:
        res = None
    if res is not None:
        return res
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        return load_rows_python(table, f.readlines(), sep)
