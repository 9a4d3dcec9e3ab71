"""A throw-away benchmark root of tiny cells, made of new files only, for
the benchmark's CPU tests."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIGS = {
    "tiny": {"system": "oneshot", "n": 3000, "d": 3,
             "distribution": "uniform", "chips": 1,
             "sky_config": {"strategy": "sliced", "p": 4,
                            "rep_filter": "sorted", "capacity": 1024,
                            "block": 64}},
    "tiny4": {"system": "oneshot", "n": 4000, "d": 3,
              "distribution": "uniform", "chips": 4,
              "sky_config": {"strategy": "sliced", "p": 8,
                             "rep_filter": "sorted", "capacity": 1024,
                             "block": 64, "merge": "auto"}},
}
TRAFFIC = {
    "closedmix": {"loop": "closed", "in_flight": 3, "tables": 3,
                  "check_sample": 4},
}
CELLS = [("tiny.closedmix", "tiny", "closedmix"),
         ("tiny4.closedmix", "tiny4", "closedmix")]
READER = '''"""Device seconds per completed query: a throw-away metric."""

from bench import trace as btrace


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["devices"] or not ctx["completed"]:
        return None
    return sum(btrace.busy(tr)) / ctx["completed"]
'''


def make_root(path) -> str:
    """BENCHMARK.json, configs, traffic mixes and one metric reader of
    the tiny cells under ``path``; returns the root."""
    root = str(path)
    for sub in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(root, "bench", sub), exist_ok=True)
    for name, conf in CONFIGS.items():
        with open(os.path.join(root, "bench", "configs",
                               f"{name}.json"), "w") as f:
            json.dump(conf, f)
    for name, mix in TRAFFIC.items():
        with open(os.path.join(root, "bench", "traffic",
                               f"{name}.json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(root, "bench", "metrics",
                           "busy_ms.tiny.py"), "w") as f:
        f.write(READER)
    spec = {
        "configs": [{"name": n, "source": "test", "reduced": [],
                     "file": f"bench/configs/{n}.json", "why": "test"}
                    for n in CONFIGS],
        "workloads": [{"name": c, "config": cf, "traffic": t,
                       "chips": CONFIGS[cf]["chips"], "why": "test"}
                      for c, cf, t in CELLS],
        "end_to_end": [
            {"name": "tuples_per_s", "unit": "tuples/s", "better": "higher",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny.closedmix", "tiny4.closedmix"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "busy_ms.tiny", "unit": "s/query", "better": "lower",
             "source": "device_trace", "layer": "device",
             "moves": "tuples_per_s"}],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def copy_checkout_bench(dst) -> str:
    """BENCHMARK.json and the benchmark's own files, nothing else."""
    dst = str(dst)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst
