"""Mean per statement of `execute/dispatch/enqueue-fetch`: the
device->host copies of a program's outputs queued behind it, the
second half of `dispatch` (the first is `launch`, the jitted call)."""

import served_path


def read(run):
    return served_path.mean_ms(run, "execute/dispatch/enqueue-fetch")
