"""Optional-hypothesis shim for the property-based tests.

`hypothesis` is a declared test dependency (pyproject.toml) but not a
hard one: when it is missing, the property tests must *skip at run time*
while every plain pytest test in the same module still collects and
runs. Test modules import `given`, `example`, `settings`, `st` from here
instead of from hypothesis directly; with hypothesis absent the stand-in
`given` produces a test whose body is `pytest.importorskip("hypothesis")`,
so it reports as skipped with the canonical reason.
"""

from __future__ import annotations

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # degrade: property tests skip, others run
    import pytest

    HAVE_HYPOTHESIS = False

    def given(*_args, **_kwargs):
        def deco(fn):
            # zero-arg stand-in: pytest must not mistake the property
            # test's hypothesis-drawn parameters for fixtures
            def skipper():
                pytest.importorskip("hypothesis")
            skipper.__name__ = fn.__name__
            skipper.__doc__ = fn.__doc__
            skipper.__module__ = fn.__module__
            return skipper
        return deco

    def settings(*_args, **_kwargs):
        def deco(fn):
            return fn
        return deco

    example = settings

    class _AnyStrategy:
        """st.<anything>(...) placeholder; only ever passed to the no-op
        `given` above, never executed."""

        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _AnyStrategy()

__all__ = ["given", "example", "settings", "st", "HAVE_HYPOTHESIS"]
