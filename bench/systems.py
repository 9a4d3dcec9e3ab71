"""The systems under test, built from a configuration file.

A configuration names its ``system``:

* ``oneshot`` - a client that holds its tables on the device and calls
  `repro.core.parallel.parallel_skyline` on them, on one chip or, with
  ``chips`` > 1, on a 1-D ``workers`` mesh with the rows sharded over it.
  Table i of the pool is drawn from (seed, i).

Each system warms exactly the shapes its traffic uses, and checks the
answers of a window against `bench.reference` once the window has
closed.  The program is imported here and nowhere in the reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import datagen, reference


def _flags(buf, stats) -> bool:
    """Any overflow the answer or its stats report."""
    bad = bool(buf.overflow)
    for key in ("bucket_overflow", "local_overflow"):
        if key in stats:
            bad |= bool(np.any(np.asarray(stats[key])))
    return bad


def _rows(buf) -> tuple[np.ndarray, int]:
    """(valid rows, the count the answer claims)."""
    pts = np.asarray(buf.points)
    return pts[np.asarray(buf.mask)], int(buf.count)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """Host random numbers of the seed, one independent stream each."""
    seed = int(seed) % (1 << 64)
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


class Tally:
    """The numbers the check compares, each with its limit."""

    def __init__(self):
        self.missing = self.extra = self.overflow = 0
        self.miscounted = self.checked = 0

    def add(self, data, buf, stats) -> None:
        """Hold one answer to the reference."""
        got, count = _rows(buf)
        m, e = reference.check(data, got)
        self.missing += m
        self.extra += e
        self.miscounted += len(got) != count
        self.overflow += _flags(buf, stats)
        self.checked += 1

    def flags(self, buf, stats) -> None:
        """Count an answer's overflow and count without the reference."""
        self.miscounted += int(jnp.sum(buf.mask)) != int(buf.count)
        self.overflow += _flags(buf, stats)

    def compared(self) -> dict[str, dict[str, int]]:
        """name -> {value, limit}; every value must be <= its limit,
        except ``answers_checked``, which must reach its ``min``."""
        out = {k: {"value": int(getattr(self, a)), "limit": 0}
               for k, a in (("missing_rows", "missing"),
                            ("extra_rows", "extra"),
                            ("overflow_flags", "overflow"),
                            ("count_mismatch", "miscounted"))}
        out["answers_checked"] = {"value": int(self.checked), "min": 1}
        return out


def holds(compared: dict) -> bool:
    return all(v["value"] >= v["min"] if "min" in v
               else v["value"] <= v["limit"] for v in compared.values())


class OneShot:
    def __init__(self, conf: dict, traffic: dict, devices, seed: int):
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.core.parallel import SkyConfig
        self.n, self.d = conf["n"], conf["d"]
        self.devices = devices
        self.cfg = SkyConfig(**conf["sky_config"])
        self.mesh = sharding = None
        if len(devices) > 1:
            from repro.launch.mesh import make_worker_mesh
            self.mesh = make_worker_mesh(len(devices))
            sharding = NamedSharding(self.mesh, PartitionSpec("workers"))
        self.tables = datagen.make_pool(conf["distribution"], seed,
                                        traffic["tables"], self.n, self.d,
                                        sharding)
        self.mask = jax.device_put(jnp.ones((self.n,), bool),
                                   sharding or devices[0])
        self.key = jax.random.fold_in(datagen.seed_key(seed), 1 << 20)
        jax.block_until_ready((self.tables, self.mask))

    def query(self, i: int):
        from repro.core import parallel
        return parallel.parallel_skyline(
            self.tables[i % len(self.tables)], self.mask, cfg=self.cfg,
            key=self.key, mesh=self.mesh)

    def warm(self) -> None:
        jax.block_until_ready(self.query(0))

    def check(self, answers: list, seed: int, traffic: dict) -> Tally:
        """Every answer of the window is held to its own flags and count;
        ``check_sample`` of them, drawn from the seed, are held to the
        reference on the table they answered."""
        tally = Tally()
        k = min(traffic["check_sample"], len(answers))
        take = set(seed_rng(seed, 3).choice(len(answers), k,
                                            replace=False).tolist())
        for j, (i, (buf, stats)) in enumerate(answers):
            if j in take:
                table = self.tables[i % len(self.tables)]
                tally.add(jax.device_put(table, self.devices[0]), buf,
                          stats)
            else:
                tally.flags(buf, stats)
        return tally


SYSTEMS = {"oneshot": OneShot}
