"""Fused local-phase SFS sweep vs the per-pair reference: bit-for-bit
equivalence across backends (random data, ties, duplicates, masked rows,
overflow), interpret-mode Pallas validation, overflow subset semantics,
and the backend-layer plumbing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import example, given, settings, st

from repro.core.dominance import SENTINEL
from repro.core.sfs import block_sfs, local_skyline_batch, naive_skyline_mask
from repro.kernels.backend import KernelSpec, resolve_spec
from repro.kernels.sfs import sfs_sweep
from repro.kernels.sfs.ops import sweep_blocks, tpu_geometry

# 'interpret' runs the Pallas kernel body in interpret mode — the CPU
# validation path for the TPU sweep; 'jnp' is the fused single-dispatch
# blocked sweep. Both must be bit-for-bit the seed per-pair scan.
SWEEP_IMPLS = ["jnp", "interpret"]

SHAPES = [  # (P, n, d, capacity, block)
    (1, 1, 2, 4, 8),
    (2, 7, 3, 8, 4),
    (1, 100, 2, 100, 64),
    (3, 257, 5, 300, 64),
    (2, 513, 3, 64, 32),        # overflow: capacity << n
    (4, 300, 7, 128, 128),
    (1, 1000, 4, 2048, 256),
]


def _assert_bitwise_equal(got, want, ctx=""):
    for g, w, name in zip(got, want, ("points", "mask", "count",
                                      "overflow")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{name} differs {ctx}")


def _batch(rng, p, n, d, levels=5, mask_frac=0.2):
    # quantized coords -> plenty of exact ties and duplicate points
    pts = jnp.asarray(rng.integers(0, levels, (p, n, d)) / levels,
                      jnp.float32)
    mask = jnp.asarray(rng.random((p, n)) > mask_frac)
    return pts, mask


@pytest.mark.parametrize("p,n,d,cap,blk", SHAPES)
@pytest.mark.parametrize("impl", SWEEP_IMPLS)
def test_sweep_matches_perpair_reference(p, n, d, cap, blk, impl):
    rng = np.random.default_rng(p * 10_000 + n * 10 + d)
    pts, mask = _batch(rng, p, n, d)
    want = local_skyline_batch(pts, mask, capacity=cap, block=blk,
                               impl="perpair")
    got = local_skyline_batch(pts, mask, capacity=cap, block=blk,
                              impl=impl)
    _assert_bitwise_equal(got, want, f"impl={impl} shape={(p, n, d)}")


@pytest.mark.parametrize("impl", SWEEP_IMPLS + ["perpair"])
def test_sweep_matches_oracle(impl):
    rng = np.random.default_rng(7)
    pts, mask = _batch(rng, 3, 200, 4)
    buf = local_skyline_batch(pts, mask, capacity=200, block=64, impl=impl)
    for i in range(3):
        oracle = np.asarray(naive_skyline_mask(pts[i], mask[i]))
        want = set(map(tuple, np.asarray(pts[i])[oracle]))
        got = set(map(tuple,
                      np.asarray(buf.points[i])[np.asarray(buf.mask[i])]))
        assert got == want, (impl, i)
        # count counts member *rows* (duplicates kept), not distinct points
        assert int(buf.count[i]) == int(oracle.sum())
        assert not bool(buf.overflow[i])


@pytest.mark.parametrize("impl", SWEEP_IMPLS + ["perpair"])
def test_overflow_subset_semantics(impl):
    """When capacity < |SKY| the buffer is a *subset* of the true skyline
    (extra members dropped, never spurious ones added) and the overflow
    flag is set — via the batched sweep entry point."""
    rng = np.random.default_rng(11)
    pts = jnp.asarray(rng.random((2, 400, 5)), jnp.float32)
    mask = jnp.ones((2, 400), jnp.bool_)
    full = local_skyline_batch(pts, mask, capacity=400, block=64, impl=impl)
    small_cap = max(int(full.count.min()) // 3, 1)
    sky = local_skyline_batch(pts, mask, capacity=small_cap, block=64,
                              impl=impl)
    for i in range(2):
        assert bool(sky.overflow[i]), impl
        got = set(map(tuple,
                      np.asarray(sky.points[i])[np.asarray(sky.mask[i])]))
        want = set(map(tuple,
                       np.asarray(full.points[i])[np.asarray(full.mask[i])]))
        assert got <= want, impl
        assert len(got) <= small_cap + 63  # wcap rounds up to the block
        # the count still reports the scan's keep total, past capacity
        assert int(sky.count[i]) >= len(got)


@pytest.mark.parametrize("impl", SWEEP_IMPLS)
def test_overflow_via_block_sfs_wrapper(impl):
    rng = np.random.default_rng(13)
    pts = jnp.asarray(rng.random((400, 5)), jnp.float32)
    full = block_sfs(pts, capacity=400, block=64, impl=impl)
    small_cap = max(int(full.count) // 3, 1)
    sky = block_sfs(pts, capacity=small_cap, block=64, impl=impl)
    assert bool(sky.overflow)
    got = set(map(tuple, np.asarray(sky.points)[np.asarray(sky.mask)]))
    want = set(map(tuple, np.asarray(full.points)[np.asarray(full.mask)]))
    assert got <= want
    ref = block_sfs(pts, capacity=small_cap, block=64, impl="perpair")
    _assert_bitwise_equal(sky, ref, f"impl={impl} (wrapper, overflow)")


def _tail_case(case):
    """A presorted batch for `sfs_sweep` whose valid rows end early in the
    padded rows (the tails the Pallas sweep skips):
    ``(pts, mask, capacity, block, wtile)``.  Coordinates hold -0.0 so
    the bit-exact copy is checked too."""
    rng = np.random.default_rng(sum(map(ord, case)))
    p, nblk, block, d = {"tail5": (2, 40, 32, 4), "tail5_tiled": (2, 40, 32, 4),
                         "empty_partition": (3, 8, 32, 3),
                         "masked_mid": (2, 10, 32, 3),
                         "overflow": (2, 40, 32, 2),
                         "chip_geometry": (2, 40, 128, 4)}[case]
    n = nblk * block
    pts = rng.integers(0, 6, (p, n, d)) / 6
    if case == "overflow":  # an antichain: every valid row is kept
        xs = rng.random((p, n))
        pts = np.stack([xs, 1.0 - xs], axis=-1)
    if case == "empty_partition":
        mask = np.ones((p, n), bool)
        mask[1] = False
    else:
        mask = rng.random((p, n)) < 0.05
    pts = np.where(pts == 0, -0.0, pts).astype(np.float32)
    # SFS presort: valid rows first, by a strictly monotone score
    order = np.argsort(np.where(mask, pts.sum(-1), np.inf), axis=1,
                       kind="stable")
    pts = np.take_along_axis(pts, order[..., None], 1)
    mask = np.take_along_axis(mask, order, 1)
    if case == "masked_mid":
        # whole invalid blocks between valid ones: the last valid block
        # of partition 0 lies after them, partition 1 ends in block 9
        mask[0] = False
        mask[0, :block // 2] = mask[0, 3 * block:3 * block + 5] = True
        mask[1, :] = np.arange(n) % 7 == 0
    pts = np.where(mask[..., None], pts, np.float32(SENTINEL))
    cap, wtile = {"tail5": (256, 0), "tail5_tiled": (256, 64),
                  "empty_partition": (128, 32), "masked_mid": (64, 0),
                  "overflow": (32, 16), "chip_geometry": (1024, 512)}[case]
    return jnp.asarray(pts), jnp.asarray(mask), cap, block, wtile


TAIL_CASES = ["tail5", "tail5_tiled", "empty_partition", "masked_mid",
              "overflow", "chip_geometry"]


@pytest.mark.parametrize("impl,case", [
    *((impl, None) for impl in SWEEP_IMPLS),
    *(pytest.param("interpret", c, id=f"interpret-{c}")
      for c in TAIL_CASES)])
def test_all_masked_and_empty_partitions(impl, case):
    if case is None:
        rng = np.random.default_rng(3)
        pts = jnp.asarray(rng.random((2, 64, 3)), jnp.float32)
        mask = jnp.zeros((2, 64), jnp.bool_).at[1, :5].set(True)
        want = local_skyline_batch(pts, mask, capacity=16, block=16,
                                   impl="perpair")
        got = local_skyline_batch(pts, mask, capacity=16, block=16,
                                  impl=impl)
        _assert_bitwise_equal(got, want, f"impl={impl} (masked)")
        assert int(got.count[0]) == 0
        assert not bool(got.mask[0].any())
        return
    # the Pallas sweep stops each partition at its last valid block
    pts, mask, cap, block, wtile = _tail_case(case)
    nblk = np.asarray(sweep_blocks(mask, block))
    valid_rows = np.asarray(mask)
    assert (nblk * block < mask.shape[1]).any() or case == "masked_mid"
    for i, nb in enumerate(nblk):
        assert not valid_rows[i, nb * block:].any()
        assert nb == 0 or valid_rows[i, (nb - 1) * block:nb * block].any()
    if case == "chip_geometry":  # the compiled sweep's own geometry
        assert tpu_geometry(block, mask.shape[1], cap, 0) == (block, wtile)
    kw = dict(block=block, wcap=cap, sentinel=float(SENTINEL))
    want = sfs_sweep(pts, mask, spec="perpair", **kw)
    got = sfs_sweep(pts, mask, spec=impl, wtile=wtile, **kw)
    for g, w, name in zip(got, want, ("window", "wmask", "count")):
        np.testing.assert_array_equal(
            np.asarray(g).view(np.int32) if name == "window"
            else np.asarray(g), np.asarray(w).view(np.int32)
            if name == "window" else np.asarray(w),
            err_msg=f"{name} differs impl={impl} case={case}")
    if case == "empty_partition":
        assert int(want[2][1]) == 0 and int(want[2][0]) > 0
    if case == "overflow":
        assert (np.asarray(want[2]) > cap).all()
    else:  # a -0.0 member came through the window unchanged
        win = np.asarray(want[0])[np.asarray(want[1])]
        assert ((win == 0) & np.signbit(win)).any()


def test_wide_d_on_jnp_sweep():
    """d=20 exceeds the Pallas D_PAD layout but must work on the jnp
    sweep (and the per-pair reference, whose dominance impl is jnp)."""
    rng = np.random.default_rng(20)
    pts = jnp.asarray(rng.integers(0, 3, (2, 120, 20)) / 3.0, jnp.float32)
    mask = jnp.asarray(rng.random((2, 120)) > 0.1)
    want = local_skyline_batch(pts, mask, capacity=120, block=32,
                               impl="perpair")
    got = local_skyline_batch(pts, mask, capacity=120, block=32,
                              impl="jnp")
    _assert_bitwise_equal(got, want, "impl=jnp d=20")
    for i in range(2):
        oracle = set(map(tuple, np.asarray(pts[i])[np.asarray(
            naive_skyline_mask(pts[i], mask[i]))]))
        gset = set(map(tuple,
                       np.asarray(got.points[i])[np.asarray(got.mask[i])]))
        assert gset == oracle


def test_wide_d_rejected_by_pallas_sweep():
    pts = jnp.zeros((1, 16, 20), jnp.float32)
    with pytest.raises(ValueError, match="use impl='jnp'"):
        local_skyline_batch(pts, capacity=16, block=16, impl="interpret")


def test_negative_zero_bits_preserved():
    """The window buffer must preserve coordinate bits exactly — a -0.0
    skyline member must not come back as +0.0 from any impl (the Pallas
    append copies values through an integer-bit sum for this)."""
    pts = jnp.asarray([[[-0.0, 0.5], [0.25, 0.25], [0.5, -0.0],
                        [0.75, -1.0], [1.0, 1.0], [0.125, 0.625]]],
                      jnp.float32)
    ref = local_skyline_batch(pts, capacity=6, block=2, impl="perpair")
    assert np.signbit(np.asarray(ref.points)).any()  # a -0.0 survived
    for impl in SWEEP_IMPLS:
        got = local_skyline_batch(pts, capacity=6, block=2, impl=impl)
        np.testing.assert_array_equal(
            np.asarray(got.points).view(np.int32),
            np.asarray(ref.points).view(np.int32),
            err_msg=f"impl={impl} (raw bits)")


def test_backend_resolution():
    spec = resolve_spec("jnp")
    assert (spec.sweep, spec.dominance) == ("jnp", "jnp")
    assert resolve_spec("perpair").sweep == "perpair"
    assert resolve_spec("auto").name in ("jnp", "pallas")
    assert resolve_spec(spec) is spec  # specs pass through
    with pytest.raises(ValueError, match="unknown kernel backend"):
        resolve_spec("no-such-backend")
    with pytest.raises(ValueError, match="unknown sweep impl"):
        KernelSpec("bad", sweep="nope", dominance="jnp")


def test_block_size_changes_layout_not_membership():
    rng = np.random.default_rng(5)
    pts, mask = _batch(rng, 2, 300, 4)
    base = local_skyline_batch(pts, mask, capacity=300, block=64,
                               impl="jnp")
    for blk in (16, 128, 512):
        got = local_skyline_batch(pts, mask, capacity=300, block=blk,
                                  impl="jnp")
        np.testing.assert_array_equal(np.asarray(got.count),
                                      np.asarray(base.count))
        for i in range(2):
            a = set(map(tuple, np.asarray(got.points[i])[
                np.asarray(got.mask[i])]))
            b = set(map(tuple, np.asarray(base.points[i])[
                np.asarray(base.mask[i])]))
            assert a == b, blk


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 3), st.integers(1, 120), st.integers(2, 6),
       st.integers(0, 3), st.sampled_from([16, 32, 64]),
       st.integers(0, 2 ** 31 - 1))
def test_hypothesis_sweep_parity(p, n, d, quant, blk, seed):
    """Property test: every sweep impl is bit-for-bit the per-pair
    reference over random data with heavy ties, duplicates, masked rows,
    and capacities small enough to overflow."""
    rng = np.random.default_rng(seed)
    levels = [3, 5, 17, 0][quant]
    if levels:
        pts = jnp.asarray(rng.integers(0, levels, (p, n, d)) / levels,
                          jnp.float32)
    else:
        pts = jnp.asarray(rng.random((p, n, d)), jnp.float32)
    mask = jnp.asarray(rng.random((p, n)) > 0.25)
    cap = int(rng.integers(1, n + 1))  # may force overflow
    want = local_skyline_batch(pts, mask, capacity=cap, block=blk,
                               impl="perpair")
    for impl in SWEEP_IMPLS:
        got = local_skyline_batch(pts, mask, capacity=cap, block=blk,
                                  impl=impl)
        _assert_bitwise_equal(
            got, want, f"impl={impl} p={p} n={n} d={d} cap={cap} blk={blk}")


# --- window tiling: wtile is pure schedule ---------------------------------
# 'gpu_interpret' runs the Triton-structured GPU kernel body (one grid
# program per partition, in-kernel candidate loop) in interpret mode —
# the CPU validation path for the GPU backend, always tiled internally.

TILED_IMPLS = ["jnp", "interpret", "gpu_interpret"]


@pytest.mark.parametrize("impl", TILED_IMPLS)
def test_tiled_sweep_matches_perpair_many_tiles(impl):
    """wcap many multiples of the tile: ties, duplicates, masked rows,
    and an overflowing capacity — every tiling bit-identical to the
    tile-free per-pair reference."""
    rng = np.random.default_rng(31)
    pts, mask = _batch(rng, 2, 500, 4)
    for cap, blk in ((512, 32), (96, 32)):  # 16 tiles; overflow at 96
        want = local_skyline_batch(pts, mask, capacity=cap, block=blk,
                                   impl="perpair")
        for wtile in (32, 64, 128):
            got = local_skyline_batch(pts, mask, capacity=cap, block=blk,
                                      impl=impl, wtile=wtile)
            _assert_bitwise_equal(
                got, want, f"impl={impl} cap={cap} wtile={wtile}")


@pytest.mark.parametrize("impl", TILED_IMPLS)
def test_window_exactly_one_tile(impl):
    """wtile == wcap degenerates to the untiled sweep — same bits."""
    rng = np.random.default_rng(33)
    pts, mask = _batch(rng, 2, 200, 3)
    want = local_skyline_batch(pts, mask, capacity=128, block=64,
                               impl="perpair")
    got = local_skyline_batch(pts, mask, capacity=128, block=64,
                              impl=impl, wtile=128)
    _assert_bitwise_equal(got, want, f"impl={impl} wtile==wcap")


@pytest.mark.parametrize("impl", TILED_IMPLS)
def test_append_straddles_tile_boundary(impl):
    """An antichain with ragged masked-row counts: every block's append
    lands mid-tile and spills into the next tile (kept counts never
    align with the tile width), exercising the two-store straddle path."""
    n, d = 100, 2
    # x + y = const: pairwise incomparable, so every unmasked row appends
    xs = np.linspace(0.0, 1.0, n, dtype=np.float32)
    pts = jnp.asarray(np.stack([xs, 1.0 - xs], axis=1))[None]
    rng = np.random.default_rng(37)
    mask = jnp.asarray(rng.random((1, n)) > 0.3)  # ragged kept counts
    want = local_skyline_batch(pts, mask, capacity=96, block=16,
                               impl="perpair")
    assert int(want.count[0]) == int(np.asarray(mask).sum())  # all kept
    for wtile in (16, 32):
        got = local_skyline_batch(pts, mask, capacity=96, block=16,
                                  impl=impl, wtile=wtile)
        _assert_bitwise_equal(got, want,
                              f"impl={impl} wtile={wtile} (straddle)")


def test_arbitrary_wtile_values_normalize():
    """wtile is a *request*: non-divisors of the window fall back to a
    valid tiling, 0 and >= wcap mean untiled — any integer must yield
    the reference bits (normalization is part of the schedule, never
    the result)."""
    rng = np.random.default_rng(41)
    pts, mask = _batch(rng, 1, 300, 4)
    want = local_skyline_batch(pts, mask, capacity=256, block=64,
                               impl="perpair")
    for wtile in (-1, 0, 7, 33, 64, 100, 128, 256, 10_000):
        got = local_skyline_batch(pts, mask, capacity=256, block=64,
                                  impl="jnp", wtile=wtile)
        _assert_bitwise_equal(got, want, f"wtile={wtile} (normalize)")


def test_tiled_negative_zero_bits_preserved():
    pts = jnp.asarray([[[-0.0, 0.5], [0.25, 0.25], [0.5, -0.0],
                        [0.75, -1.0], [1.0, 1.0], [0.125, 0.625]]],
                      jnp.float32)
    ref = local_skyline_batch(pts, capacity=6, block=2, impl="perpair")
    assert np.signbit(np.asarray(ref.points)).any()
    for impl in TILED_IMPLS:
        got = local_skyline_batch(pts, capacity=6, block=2, impl=impl,
                                  wtile=2)
        np.testing.assert_array_equal(
            np.asarray(got.points).view(np.int32),
            np.asarray(ref.points).view(np.int32),
            err_msg=f"impl={impl} wtile=2 (raw bits)")


def test_wide_d_on_gpu_sweep():
    """The GPU backend pads attribute rows instead of capping d — d=12
    must pass where the TPU Pallas layout rejects it."""
    rng = np.random.default_rng(43)
    pts = jnp.asarray(rng.integers(0, 3, (2, 120, 12)) / 3.0, jnp.float32)
    mask = jnp.asarray(rng.random((2, 120)) > 0.1)
    want = local_skyline_batch(pts, mask, capacity=120, block=32,
                               impl="perpair")
    got = local_skyline_batch(pts, mask, capacity=120, block=32,
                              impl="gpu_interpret")
    _assert_bitwise_equal(got, want, "impl=gpu_interpret d=12")


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 2), st.integers(1, 120), st.integers(2, 5),
       st.sampled_from([16, 32]), st.integers(0, 96),
       st.integers(0, 2 ** 31 - 1))
# minimal failures hypothesis once found (a one-row batch, a tile wider
# than the window, and the untiled request)
@example(p=1, n=1, d=2, blk=32, wtile=56, seed=56)
@example(p=1, n=1, d=2, blk=16, wtile=0, seed=0)
def test_hypothesis_tiled_parity(p, n, d, blk, wtile, seed):
    """Property: for ANY requested wtile (divisor or not, 0, oversized)
    every tiled impl is bit-for-bit the per-pair reference, including
    overflowing capacities."""
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.integers(0, 5, (p, n, d)) / 5, jnp.float32)
    mask = jnp.asarray(rng.random((p, n)) > 0.25)
    cap = int(rng.integers(1, n + 1))
    want = local_skyline_batch(pts, mask, capacity=cap, block=blk,
                               impl="perpair")
    for impl in TILED_IMPLS:
        got = local_skyline_batch(pts, mask, capacity=cap, block=blk,
                                  impl=impl, wtile=wtile)
        _assert_bitwise_equal(got, want, f"impl={impl} p={p} n={n} d={d} "
                                         f"cap={cap} blk={blk} wtile={wtile}")


def test_sweep_under_vmap_and_jit():
    """The engine vmaps the pipeline over queries: the fused sweep must
    compose with vmap+jit and stay bit-identical to the reference."""
    rng = np.random.default_rng(17)
    pts = jnp.asarray(rng.random((4, 2, 96, 3)), jnp.float32)  # (Q, P, n, d)
    mask = jnp.ones((4, 2, 96), jnp.bool_)

    def run(impl):
        f = jax.jit(jax.vmap(lambda x, m: local_skyline_batch(
            x, m, capacity=64, block=32, impl=impl)))
        return f(pts, mask)

    _assert_bitwise_equal(run("jnp"), run("perpair"), "vmap+jit")


def _eqns(jaxpr, in_loop=False):
    """Every equation of a jaxpr and its sub-jaxprs, with whether it runs
    inside a ``while`` loop."""
    for e in jaxpr.eqns:
        yield e, in_loop
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, in_loop or e.primitive.name
                                     == "while")


def test_vmapped_pallas_sweep_is_one_launch():
    """Batched callers (the engine, the batched chunk inserts, the
    windowed merge) vmap the sweep over queries: the batch folds into the
    partition axis, so ONE kernel sweeps every query and partition — not
    a loop of per-query launches — bit-identical to the reference, also
    nested and with an unbatched operand."""
    rng = np.random.default_rng(23)
    pts = jnp.asarray(rng.random((4, 2, 96, 3)), jnp.float32)  # (Q, P, n, d)
    mask = jnp.asarray(rng.random((4, 2, 96)) < 0.3)

    def sweep(impl):
        return lambda x, m: local_skyline_batch(x, m, capacity=64, block=32,
                                                impl=impl)

    f = jax.vmap(sweep("interpret"))
    eqns = list(_eqns(jax.make_jaxpr(f)(pts, mask).jaxpr))
    launches = [(e, loop) for e, loop in eqns
                if e.primitive.name == "pallas_call"]
    assert len(launches) == 1 and not launches[0][1]
    assert launches[0][0].params["grid_mapping"].grid == (4 * 2, 96 // 32)
    assert not any(e.primitive.name == "while" for e, _ in eqns)

    want = jax.jit(jax.vmap(sweep("perpair")))(pts, mask)
    _assert_bitwise_equal(jax.jit(f)(pts, mask), want, "vmap (pallas)")
    nested = jax.jit(jax.vmap(f, in_axes=(0, None)))(
        jnp.stack([pts, pts]), mask)
    _assert_bitwise_equal(jax.tree.map(lambda a: a[1], nested), want,
                          "nested vmap (pallas)")
