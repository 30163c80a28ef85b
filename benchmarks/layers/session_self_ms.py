"""Mean per statement of the `session` span's self time: what
Session.execute spends outside parse, plan, execute, final-merge and
observe."""

import spans


def read(run):
    return spans.mean_ms(run, "session", of=spans.self_seconds_of)
