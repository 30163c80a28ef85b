"""Share of the expanding joins' output tiles that holds a row: rows
emitted over the tiles' slots, over the traced statements. The rest of
a tile is padding that every later operator still passes over."""

import expansion


def read(run):
    flights = [f for f in expansion.expanding(run) if f.get("join_expand_slots")]
    if not flights:
        return None
    return (100.0 * sum(f["join_expand_rows"] for f in flights)
            / sum(f["join_expand_slots"] for f in flights))
