"""Device milliseconds of the dominance kernel (`dominated_mask*` ops,
summed over chips) per completed query, from the traced run."""

from bench import trace as btrace

PREFIXES = ("dominated_mask",)


def read(ctx):
    tr = ctx["trace"]
    if not tr or not ctx["completed"]:
        return None
    s = btrace.op_seconds(tr, PREFIXES)
    return s * 1e3 / ctx["completed"] if s > 0 else None
