"""Mean per statement of `observe`: statement summary, the passive
tsdb sample, the slow log and its plan capture."""

import spans


def read(run):
    return spans.mean_ms(run, "observe")
