"""Share of the interconnect's roofline over the traced statements: the
seconds a chip's share of their exchanged bytes would take at the
chip's ICI peak (peaks_ici.json), over the seconds the chips were busy
(ici.py). As `stmt_hbm_roofline` reads HBM: how far the whole statement
is from moving its rows once at the peak, not an all-to-all's own rate."""

import json
import os

import exchange
import ici

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    with open(os.path.join(HERE, "peaks.json")) as f:
        kinds = [kind for kind, row in json.load(f).items() if row == run["peaks"]]
    with open(os.path.join(HERE, "peaks_ici.json")) as f:
        peaks = json.load(f)
    if not kinds or kinds[0] not in peaks:
        return None
    total = sum(f["exchange_bytes"] for f in exchange.exchanging(run))
    return ici.ici_share_pct(total, int(run["cell"]["chips"]), run["trace"]["busy_s"],
                             peaks[kinds[0]]["ici_bytes_per_s"])
