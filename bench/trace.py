"""Reduction of a profiler trace to the benchmark's device numbers.

`load` reads the `.xplane.pb` that `jax.profiler` writes and keeps what
the reductions need, as plain data: the events of each chip's "XLA Ops"
line (named by their HLO instruction, e.g. ``sfs_sweep.2``; a ``while``
op spans its body's ops) and the host spans that the benchmark itself
opens (names starting with ``bench.``, and the window span).  Each
event is ``[name, start_ns, duration_ns]``.  Other device planes (such
as ``/device:CUSTOM:...``) and the "Async XLA Ops" line, whose copies
run beside compute from start to done, are left out.  `save`/`load_json` keep that form as JSON, which is
also the form of the recorded fixture the tests read.

The reductions work on that form alone:

* `busy` - per device, the union of its op intervals inside the
  window; the idle share is 1 - busy / window.
* `op_seconds` - device time of the ops whose name starts with one of
  the given prefixes, summed over devices.
* `top_ops` - device time per op name (numbered suffixes stripped),
  averaged over devices, largest first.
* `idle_by_host_span` - every idle gap inside the window, labelled by
  the innermost benchmark host span open at its midpoint, summed per
  label and averaged over devices, largest first.
"""

from __future__ import annotations

import glob
import gzip
import heapq
import json
import os
import re

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
OP_LINES = ("XLA Ops",)
CHIP_PLANE = re.compile(r"/device:(TPU|GPU):\d+")


def op_name(text: str) -> str:
    """The instruction name of an op event, which the TPU profiler names
    by the instruction's whole HLO text (``%sfs_sweep.2 = (f32[...``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(profile_dir: str) -> dict:
    """The reduced form of the one `.xplane.pb` under ``profile_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {profile_dir}, "
                           f"found {len(paths)}")
    prof = ProfileData.from_file(paths[0])
    devices, host = [], []
    for plane in prof.planes:
        if CHIP_PLANE.fullmatch(plane.name):
            events = [[op_name(e.name), e.start_ns, e.duration_ns]
                      for line in plane.lines if line.name in OP_LINES
                      for e in line.events]
            devices.append({"name": plane.name, "events": events})
        elif plane.name.startswith("/host:"):
            host += [[e.name, e.start_ns, e.duration_ns]
                     for line in plane.lines for e in line.events
                     if e.name.startswith(HOST_PREFIX)]
    devices.sort(key=lambda p: p["name"])
    return {"devices": devices, "host": host}


def save(tr: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tr, f)


def load_json(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def window(tr: dict) -> tuple[float, float]:
    """(start_ns, end_ns) of the benchmark's window span."""
    spans = [(s, s + d) for n, s, d in tr["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"trace holds {len(spans)} window spans")
    return spans[0]


def _union(events, lo: float, hi: float) -> list[tuple[float, float]]:
    ivs = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                 if s < hi and s + d > lo)
    out: list[list[float]] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy(tr: dict) -> list[float]:
    """Seconds each device ran an op inside the window."""
    lo, hi = window(tr)
    return [sum(b - a for a, b in _union(p["events"], lo, hi)) * 1e-9
            for p in tr["devices"]]


def _in_window(tr: dict):
    lo, hi = window(tr)
    for p in tr["devices"]:
        yield [e for e in p["events"] if lo <= e[1] < hi]


def op_seconds(tr: dict, prefixes: tuple[str, ...]) -> float:
    """Device seconds of ops named with one of ``prefixes`` that started
    in the window, summed over devices."""
    return sum(d for events in _in_window(tr) for n, _, d in events
               if n.startswith(prefixes)) * 1e-9


_SUFFIX = re.compile(r"(\.\d+)+$")


def op_family(name: str) -> str:
    return _SUFFIX.sub("", name)


def top_ops(tr: dict, k: int = 10) -> list[list]:
    """[[op family, seconds per device], ...], the ``k`` largest."""
    tot: dict[str, float] = {}
    ndev = max(len(tr["devices"]), 1)
    for events in _in_window(tr):
        for n, _, d in events:
            fam = op_family(n)
            tot[fam] = tot.get(fam, 0.0) + d * 1e-9 / ndev
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(tr: dict) -> list[list[tuple[float, float]]]:
    """Per device, the idle intervals inside the window."""
    lo, hi = window(tr)
    out = []
    for p in tr["devices"]:
        gaps, t = [], lo
        for a, b in _union(p["events"], lo, hi):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        out.append(gaps)
    return out


def _labels(spans, times) -> list[str]:
    """For each time (sorted), the innermost (shortest) span open then."""
    spans = sorted(spans, key=lambda e: e[1])
    heap: list = []
    out, i = [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            n, s, d = spans[i]
            heapq.heappush(heap, (d, s + d, n))
            i += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else "none")
    return out


def idle_by_host_span(tr: dict, k: int = 10) -> list[list]:
    """[[host span, idle seconds per device], ...], the ``k`` largest."""
    spans = [e for e in tr["host"] if e[0] != WINDOW_SPAN]
    gaps = sorted(((a + b) / 2, b - a) for dev in idle_gaps(tr)
                  for a, b in dev)
    ndev = max(len(tr["devices"]), 1)
    tot: dict[str, float] = {}
    for lab, (_, g) in zip(_labels(spans, [m for m, _ in gaps]), gaps):
        tot[lab] = tot.get(lab, 0.0) + g * 1e-9 / ndev
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]
