"""The one general generator of traffic: it reads a traffic file.

A traffic file (``bench/traffic/<mix>.json``) names its ``loop``:

* ``closed`` - one client that keeps ``in_flight`` queries outstanding:
  it sends queries 0 .. in_flight - 1, then waits for the oldest answer
  and sends the next query, until the window's seconds are over.  Then it
  sends nothing more and waits for every query it sent.  Query i uses
  table ``i mod tables`` of the system's pool.  Queued queries keep the
  chip busy while the host stalls.  Reports ``tuples_per_s``: the input
  tuples of every query sent, over the time from the window's start to
  the last completion.

``span(name)`` opens a host span (a `jax.profiler.TraceAnnotation` in
traced runs).
"""

from __future__ import annotations

import collections
import dataclasses
import time

import jax

clock = time.monotonic


@dataclasses.dataclass
class Window:
    attempted: int
    failed: int
    seconds: float
    e2e: dict
    answers: list
    log: dict


def closed(system, traffic: dict, seed: int, seconds: float, span) -> Window:
    del seed
    depth = traffic["in_flight"]
    answers = []
    pending = collections.deque()
    with span("bench.window"):
        t0 = clock()
        end = t0 + seconds
        i = 0
        while clock() < end:
            with span("bench.dispatch"):
                pending.append((i, system.query(i)))
            i += 1
            if len(pending) >= depth:
                with span("bench.wait"):
                    jax.block_until_ready(pending[0][1])
                answers.append(pending.popleft())
        with span("bench.wait"):
            jax.block_until_ready([out for _, out in pending])
        answers.extend(pending)
        t1 = clock()
    return Window(attempted=i, failed=0, seconds=t1 - t0,
                  e2e={"tuples_per_s": i * system.n / (t1 - t0)},
                  answers=answers, log={"queries": i, "in_flight": depth,
                                        "drain_s": t1 - end})


LOOPS = {"closed": closed}
