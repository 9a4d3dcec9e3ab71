"""Tuned launch environment for reproducible benchmark/serve runs.

Reported numbers are only comparable when every run sees the same
allocator and logging configuration: python's default malloc fragments
under the engine's host-staging churn (tcmalloc is the standard fix on
TPU/GPU hosts), TF/XLA banner logging perturbs short benchmarks, and an
unpinned ``XLA_FLAGS`` silently changes the host device count between
runs.  `build_env` derives the canonical environment, `apply_env` merges
it into ``os.environ`` (without clobbering anything the user pinned),
and ``python -m repro.launch.env CMD ...`` exec's a command under it —
the launch-script idiom, as one auditable module instead of a shell
file per host:

    python -m repro.launch.env python -m benchmarks.run --quick

Also plumbed here: ``REPRO_KERNEL_TUNING`` — the path to a persisted
kernel-tuning table (`repro.kernels.tuning`), so a calibrated
(block, wtile) table travels to every child process of a launch the
same way the allocator settings do — and JAX's persistent compilation
cache (`use_compile_cache`): where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it and nothing here overrides it; otherwise the cache lives
at a fixed path inside the checkout (``.jax_cache``), so every run from
the same checkout finds what earlier runs compiled.

LD_PRELOAD only takes effect at process start, so `apply_env` cannot
retro-tune the *current* process's allocator — use the ``-m`` exec form
(or export the returned mapping from a shell) for that; everything else
(logging, XLA flags) applies to late importers too.
"""

from __future__ import annotations

import os
import sys

__all__ = ["TCMALLOC_PATHS", "CACHE_ENV", "build_env", "apply_env",
           "use_compile_cache", "main"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: fixed, so the cache key (which includes the
# path) is the same on every run from this checkout
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

# well-known tcmalloc locations (Debian/Ubuntu multiarch first — the
# path the TPU-host launch scripts preload)
TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def _find_tcmalloc() -> str | None:
    for path in TCMALLOC_PATHS:
        if os.path.exists(path):
            return path
    return None


def build_env(*, devices: int | None = None,
              tuning: str | None = None) -> dict[str, str]:
    """The canonical launch environment as a plain mapping.

    Args:
      devices: force this many host-platform devices via ``XLA_FLAGS``
        (None leaves the flag alone — the real accelerator count rules).
      tuning: path to a kernel-tuning table JSON to expose as
        ``REPRO_KERNEL_TUNING``.

    Returns only the variables this module owns; callers merge.
    """
    env: dict[str, str] = {
        # silence TF/XLA banner logging (perturbs short benchmarks)
        "TF_CPP_MIN_LOG_LEVEL": "4",
        # keep numpy's large-allocation warnings out of tcmalloc runs
        "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
    }
    tc = _find_tcmalloc()
    if tc is not None:
        env["LD_PRELOAD"] = tc
    if devices is not None:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={int(devices)}")
    if tuning is not None:
        env["REPRO_KERNEL_TUNING"] = tuning
    return env


def apply_env(*, devices: int | None = None, tuning: str | None = None,
              overwrite: bool = False) -> dict[str, str]:
    """Merge `build_env` into ``os.environ``; returns what was applied.

    User-pinned variables win unless ``overwrite=True``.  Note the
    LD_PRELOAD caveat in the module docstring — allocator preloading
    needs the exec form."""
    applied = {}
    for key, val in build_env(devices=devices, tuning=tuning).items():
        if overwrite or key not in os.environ:
            os.environ[key] = val
            applied[key] = val
    return applied


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads the
    variable itself; no directory is set in code), else the fixed
    in-checkout `REPO_CACHE_DIR`."""
    import jax
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv: list[str] | None = None) -> None:
    """``python -m repro.launch.env [--devices N] [--tuning PATH] CMD...``
    — exec CMD under the tuned environment (LD_PRELOAD included)."""
    argv = list(sys.argv[1:] if argv is None else argv)
    devices = tuning = None
    while argv and argv[0].startswith("--"):
        flag = argv.pop(0)
        if flag == "--devices":
            devices = int(argv.pop(0))
        elif flag == "--tuning":
            tuning = argv.pop(0)
        else:
            sys.exit(f"unknown flag {flag!r} "
                     f"(have --devices N, --tuning PATH)")
    if not argv:
        sys.exit("usage: python -m repro.launch.env [--devices N] "
                 "[--tuning PATH] CMD [ARG ...]")
    env = dict(os.environ)
    env.update(build_env(devices=devices, tuning=tuning))
    os.execvpe(argv[0], argv, env)


if __name__ == "__main__":
    main()
