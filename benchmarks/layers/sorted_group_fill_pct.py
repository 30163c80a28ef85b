"""Share of the sorted group-bys' group tables that holds a group:
groups found over the tables' slots, over the traced statements. The
rest of a table is padding that every later operator still passes over."""

import grouping


def read(run):
    flights = [f for f in grouping.grouping(run) if f.get("sorted_group_slots")]
    if not flights:
        return None
    return (100.0 * sum(f["sorted_groups"] for f in flights)
            / sum(f["sorted_group_slots"] for f in flights))
