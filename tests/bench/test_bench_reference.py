"""The benchmark's plain reference against a naive O(n^2) skyline."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_tiny  # noqa: E402,F401  (puts the checkout on sys.path)

from bench import reference  # noqa: E402


def naive(x: np.ndarray) -> np.ndarray:
    le = np.all(x[None, :, :] <= x[:, None, :], axis=-1)
    lt = np.any(x[None, :, :] < x[:, None, :], axis=-1)
    return x[~np.any(le & lt, axis=1)]


def tiny_cases():
    rng = np.random.default_rng(5)
    ties = rng.integers(0, 4, (300, 3)).astype(np.float32) / 4
    dup = rng.random((120, 4), np.float32)
    dup = np.concatenate([dup, dup[:40], dup[:7]])
    anti = rng.random((400, 3), np.float32)
    anti[:, 2] = 1.5 - anti[:, 0] - anti[:, 1]
    return {"ties": ties, "duplicates": dup, "anti": anti,
            "one_row": dup[:1], "uniform": rng.random((700, 5), np.float32)}


CASES = tiny_cases()


@pytest.mark.parametrize("name", sorted(CASES))
def test_check_accepts_the_naive_skyline(name):
    x = CASES[name]
    assert reference.check(x, naive(x)) == (0, 0)


@pytest.mark.parametrize("name", sorted(CASES))
def test_check_refuses_a_dropped_or_added_row(name):
    x = CASES[name]
    sky = naive(x)
    missing, extra = reference.check(x, sky[1:])
    assert missing >= 1 and extra == 0
    dominated = x[~np.isin(np.arange(len(x)),
                           np.flatnonzero((x[:, None] == sky[None]).all(-1)
                                          .any(1)))]
    if len(dominated):
        _, extra = reference.check(x, np.concatenate([sky, dominated[:1]]))
        assert extra >= 1


def test_check_refuses_an_altered_value():
    x = CASES["uniform"]
    sky = naive(x).copy()
    sky[0, 0] = np.nextafter(sky[0, 0], np.float32(2))
    assert reference.check(x, sky) != (0, 0)


@pytest.mark.parametrize("name", sorted(CASES))
def test_skyline_from_scratch_matches_naive(name):
    x = CASES[name]
    got = reference.skyline(x, np.float32, block=64)
    assert reference.multiset_gap(got, naive(x)) == (0, 0)


def test_bfloat16_control_is_refused():
    """The control - the from-scratch reference in bfloat16 put in the
    program's place - must read as wrong on the check."""
    rng = np.random.default_rng(9)
    x = rng.random((4000, 7), np.float32)
    control = reference.skyline(x, "bfloat16", block=256)
    missing, extra = reference.check(x, control)
    assert missing + extra > 0
