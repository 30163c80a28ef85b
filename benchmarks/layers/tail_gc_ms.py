"""`gc_s` of the farthest-off statement (the one `spans.tail` picks):
how much of its excess Python's collector could hold."""

import spans


def read(run):
    worst, _excess = spans.tail(run)
    if worst is None or not spans.has_spans(worst) or "gc_s" not in worst["flight"]:
        return None
    return 1e3 * worst["flight"]["gc_s"]
