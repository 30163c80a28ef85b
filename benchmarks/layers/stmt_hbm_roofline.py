"""Share of the HBM roofline over the traced statements: the seconds
their base-table columns would take read once at the peak, over the
seconds the device was busy (roofline.py). Bound by bytes, not flops."""

import roofline
from readers import traced


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    total = sum(run["bytes"][s["name"]] for s in traced(run))
    return roofline.hbm_share_pct(total, run["trace"]["busy_s"], run["peaks"]["hbm_bytes_per_s"])
