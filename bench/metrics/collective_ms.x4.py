"""Device milliseconds of the merge's collectives (all-gather,
all-reduce, collective-permute, all-to-all and reduce-scatter ops,
summed over chips) per completed query, from the traced run."""

from bench import trace as btrace

PREFIXES = ("all-gather", "all-reduce", "collective-permute", "all-to-all",
            "reduce-scatter")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not ctx["completed"]:
        return None
    s = btrace.op_seconds(tr, PREFIXES)
    return s * 1e3 / ctx["completed"] if s > 0 else None
