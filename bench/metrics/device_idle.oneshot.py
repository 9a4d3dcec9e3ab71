"""Share of the window in which no op ran on the device (mean over chips),
from the traced one-shot run: 1 - busy / window."""

from bench import trace as btrace


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["devices"]:
        return None
    lo, hi = btrace.window(tr)
    busy = btrace.busy(tr)
    return 1.0 - sum(busy) / len(busy) / ((hi - lo) * 1e-9)
