"""Device milliseconds of the fused SFS sweep kernel (`sfs_sweep*` ops,
summed over chips) per completed query, from the traced run."""

from bench import trace as btrace

PREFIXES = ("sfs_sweep",)


def read(ctx):
    tr = ctx["trace"]
    if not tr or not ctx["completed"]:
        return None
    s = btrace.op_seconds(tr, PREFIXES)
    return s * 1e3 / ctx["completed"] if s > 0 else None
