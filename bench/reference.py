"""The plain reference that decides `correct`.

Nothing here imports the program.  A returned answer is held to the
data it was computed from: a row of the data belongs to the skyline iff
no returned member dominates it (<= in every attribute, < in one;
smaller is better), and the returned members must equal exactly those
rows, as a multiset of float32 bit patterns.  That equality holds only
for the true skyline: a missing member is dominated by no one and so
reappears among the wanted rows; a returned non-member is dominated by
a true member, which is then either returned (and excludes it) or
missing.  `undominated` is copied from the program's `chip_smoke.py`.

`skyline` computes a skyline from scratch, in a stated precision.  The
benchmark's runs do not use it: it is the control (`bench/control.py`),
the reference put in the program's place in bfloat16, which the check
above has to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _pow2(n: int, floor: int = 128) -> int:
    rows = floor
    while rows < n:
        rows *= 2
    return rows


def _pad_members(members: np.ndarray, d: int, rows: int) -> np.ndarray:
    """+inf rows dominate nothing."""
    pad = np.full((rows, d), np.inf, np.float32)
    pad[:len(members)] = members
    return pad


@functools.lru_cache(maxsize=None)
def _undominated_fn(block: int):
    def run(data, s):
        n, d = data.shape
        npad = -(-n // block) * block
        x = jnp.pad(data, ((0, npad - n), (0, 0))).reshape(-1, block, d)

        def one(xb):
            le = jnp.ones((block, s.shape[0]), bool)
            lt = jnp.zeros((block, s.shape[0]), bool)
            for k in range(d):
                a, b = s[None, :, k], xb[:, k, None]
                le = le & (a <= b)
                lt = lt | (a < b)
            return ~jnp.any(le & lt, axis=1)

        return jax.lax.map(one, x).reshape(-1)[:n]

    return jax.jit(run)


def undominated(data, members, *, block: int = 1024):
    """(N,) bool: rows of ``data`` (on the device) that no row of
    ``members`` dominates.  ``members`` is padded with +inf rows to a
    power-of-two row count, so few shapes compile."""
    members = np.asarray(members, np.float32)
    s = _pad_members(members, data.shape[1], _pow2(len(members)))
    return _undominated_fn(block)(data, jnp.asarray(s))


def _row_keys(rows: np.ndarray) -> np.ndarray:
    rows = np.ascontiguousarray(rows, np.float32)
    return rows.view(np.dtype((np.void, 4 * rows.shape[1]))).ravel()


def multiset_gap(got: np.ndarray, want: np.ndarray) -> tuple[int, int]:
    """(missing, extra): rows of ``want`` absent from ``got`` and rows of
    ``got`` absent from ``want``, as multisets of float32 bit patterns."""
    ug, cg = np.unique(_row_keys(got), return_counts=True)
    uw, cw = np.unique(_row_keys(want), return_counts=True)
    _, ig, iw = np.intersect1d(ug, uw, assume_unique=True,
                               return_indices=True)
    matched = int(np.minimum(cg[ig], cw[iw]).sum())
    return len(want) - matched, len(got) - matched


def check(data, got: np.ndarray) -> tuple[int, int]:
    """(missing, extra) of the answer ``got`` (valid rows only) on
    ``data``."""
    want = np.asarray(data)[np.asarray(undominated(data, got))]
    return multiset_gap(got, want)


# --------------------------------------------------------------------------
# from-scratch skyline (the control's engine)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _bnl_fn(n: int, d: int, block: int, cap: int):
    def dominated_by(x, s, smask):
        le = jnp.ones((x.shape[0], s.shape[0]), bool)
        lt = jnp.zeros((x.shape[0], s.shape[0]), bool)
        for k in range(d):
            a, b = s[None, :, k], x[:, k, None]
            le = le & (a <= b)
            lt = lt | (a < b)
        return jnp.any(le & lt & smask[None, :], axis=1)

    def run(xs, valid):
        def step(i, carry):
            s, sidx, cnt = carry
            xb = jax.lax.dynamic_slice(xs, (i * block, 0), (block, d))
            vb = jax.lax.dynamic_slice(valid, (i * block,), (block,))
            smask = jnp.arange(cap) < cnt
            keep = (vb & ~dominated_by(xb, s, smask)
                    & ~dominated_by(xb, xb, vb))
            pos = jnp.where(keep, cnt + jnp.cumsum(keep) - 1, cap)
            s = s.at[pos].set(xb, mode="drop")
            sidx = sidx.at[pos].set(i * block + jnp.arange(block),
                                    mode="drop")
            return s, sidx, cnt + jnp.sum(keep)

        s0 = jnp.zeros((cap, d), xs.dtype)
        i0 = jnp.zeros((cap,), jnp.int32)
        return jax.lax.fori_loop(0, xs.shape[0] // block, step,
                                 (s0, i0, jnp.int32(0)))[1:]

    return jax.jit(run)


def skyline(data, dtype=np.float32, *, block: int = 512) -> np.ndarray:
    """Skyline rows of ``data`` (float32, returned as given), with every
    comparison made on the values rounded to ``dtype``.

    A block-nested loop over the rows in lexicographic order of the
    rounded values: a dominator precedes what it dominates in that
    order, so a row that survives the members found so far and its own
    block is a member for good."""
    data = np.asarray(data, np.float32)
    n, d = data.shape
    vals = np.asarray(jnp.asarray(data).astype(dtype).astype(jnp.float32))
    order = np.lexsort(vals.T[::-1])
    npad = -(-n // block) * block
    xs = np.zeros((npad, d), np.float32)
    xs[:n] = vals[order]
    valid = np.arange(npad) < n
    cap = _pow2(max(n // 64, 1), floor=block)
    while True:
        cap = min(cap, npad)
        sidx, cnt = _bnl_fn(npad, d, block, cap)(
            jnp.asarray(xs).astype(dtype), jnp.asarray(valid))
        cnt = int(cnt)
        if cnt <= cap:
            return data[order[np.asarray(sidx)[:cnt]]]
        cap *= 4
