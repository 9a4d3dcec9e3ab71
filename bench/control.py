"""The control of a cell's check: the plain reference put in the
program's place, computed one precision below the configuration's
(bfloat16 comparisons for float32 data).  The check has to read it as
not correct; its readings are the upper ones that the limits of
`bench/run.py` (all 0: the comparison is exact) lie below.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--answers N]

For each seed it makes the cell's inputs as a run does (the same pool
from the same seed), answers as many of them as a run checks with
`reference.skyline` in bfloat16, and prints one JSON line per seed with
the check's numbers.  It is not part of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--answers", type=int, default=0,
                    help="answers per seed (default: as many as a run "
                         "checks)")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    sys.path[:0] = [root]
    import numpy as np

    from bench import datagen, reference, run
    reg = run.Registry(root)
    conf = reg.config(reg.cell(args.workload)["config"])
    traffic = reg.traffic(reg.cell(args.workload)["traffic"])
    count = traffic["check_sample"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        tables = np.asarray(datagen.make_stack(
            conf["distribution"], seed, args.answers or count,
            conf["n"], conf["d"]))
        missing = extra = 0
        for x in tables:
            m, e = reference.check(x, reference.skyline(x, args.dtype))
            missing += m
            extra += e
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.dtype, "answers": len(tables),
                          "missing_rows": missing, "extra_rows": extra,
                          "seconds": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    if os.path.abspath(sys.path[0]) == HERE:
        sys.path.pop(0)
    sys.exit(main())
