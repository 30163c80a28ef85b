"""Share of the tested pairs that the residual passed, over the traced
statements' semi and anti joins with a residual."""

import residual


def read(run):
    flights = [f for f in residual.tested(run) if f["residual_pairs"]]
    if not flights:
        return None
    return (100.0 * sum(f["residual_kept"] for f in flights)
            / sum(f["residual_pairs"] for f in flights))
