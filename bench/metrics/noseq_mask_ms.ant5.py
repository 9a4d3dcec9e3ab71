"""Device milliseconds of NoSeq's phase-2 dominance test (`noseq_mask*`
ops, summed over chips) per completed query, from the traced run.  A
program without NoSeq, or one that does not name the test, has no such
op: the reader then gives nothing."""

from bench import trace as btrace

PREFIXES = ("noseq_mask",)


def read(ctx):
    tr = ctx["trace"]
    if not tr or not ctx["completed"]:
        return None
    s = btrace.op_seconds(tr, PREFIXES)
    return s * 1e3 / ctx["completed"] if s > 0 else None
