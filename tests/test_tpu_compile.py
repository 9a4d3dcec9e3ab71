"""Both Pallas kernel families compile for a described TPU v5e.

Interpret mode (the CPU validation path of every other kernel test)
accepts layouts the TPU compiler refuses: unaligned blocks, scalar
stores to vector memory, more scoped VMEM than the kernel may use.
These tests run Mosaic itself on a v5e that is described, not attached,
at the geometries the chip runs, and check that the compiled HLO holds
the kernel (``tpu_custom_call``) — no chip time, about a second or two
per kernel.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dominance.kernel import dominated_mask_pallas
from repro.kernels.sfs import kernel as sweep_kernel
from repro.kernels.sfs import ops as sweep_ops
from repro.kernels.sfs.kernel import (D_PAD, sfs_sweep_pallas,
                                      sweep_vmem_bytes, vmem_limit_bytes)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _sweep_hlo(one_chip, *, p, n, wcap, block_c, wtile):
    return _compile(
        lambda c, m, nb: sfs_sweep_pallas(c, m, nb, block_c=block_c,
                                          wcap=wcap, wtile=wtile,
                                          sentinel=1e30),
        one_chip, ((p * D_PAD, n), jnp.float32), ((p, n), jnp.int32),
        ((p,), jnp.int32))


def test_sweep_untiled_compiles(one_chip):
    """P > 1, untiled: the (W, 1) window columns need more scoped VMEM
    than the compiler's default, so this also holds Mosaic to the
    kernel's own bound (`sweep_vmem_bytes` becomes the limit here)."""
    est = sweep_vmem_bytes(block_c=256, wcap=4096, wtile=0)
    assert vmem_limit_bytes(est) == est
    text = _sweep_hlo(one_chip, p=8, n=4096, wcap=4096, block_c=256,
                      wtile=0)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,wcap", [
    (4096, 4096),
    # the widest window chip_smoke.py runs: the one-shot merge sweep at
    # capacity 131072 (compacted union of the HOU-shape local skylines)
    (131072, 131072),
])
def test_sweep_tiled_compiles(one_chip, n, wcap):
    block_c, wtile = sweep_ops.tpu_geometry(256, n, wcap, 0)
    assert (block_c, wtile) == (256, sweep_ops.TPU_WTILE)
    text = _sweep_hlo(one_chip, p=8 if n == 4096 else 1, n=n, wcap=wcap,
                      block_c=block_c, wtile=wtile)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("p,n,wcap", [
    pytest.param(8, 256256, 16384, id="hou7-local"),    # 8 buckets of 256,161
    pytest.param(1, 131072, 131072, id="hou7-merge"),   # the union's buffer
])
def test_bounded_sweep_compiles_within_its_vmem_bound(one_chip,
                                                      monkeypatch, p, n,
                                                      wcap):
    """The scalar-prefetched grid (each partition's block bound read by
    the candidate index maps) at `hou7`'s local and merge geometries,
    with Mosaic's scoped VMEM held to the kernel's own estimate rather
    than the larger compiler default."""
    block_c, wtile = sweep_ops.tpu_geometry(256, n, wcap, 0)
    assert (block_c, wtile) == (256, sweep_ops.TPU_WTILE)
    est = sweep_vmem_bytes(block_c=block_c, wcap=wcap, wtile=wtile)
    monkeypatch.setattr(sweep_kernel, "vmem_limit_bytes", lambda e: e)
    text = _compile(
        lambda c, m, nb: sfs_sweep_pallas.__wrapped__(
            c, m, nb, block_c=block_c, wcap=wcap, wtile=wtile,
            sentinel=1e30),
        one_chip, ((p * D_PAD, n), jnp.float32), ((p, n), jnp.int32),
        ((p,), jnp.int32))
    assert "tpu_custom_call" in text


def test_vmapped_sweep_compiles_to_one_kernel(one_chip):
    """The engine's small-query path vmaps the sweep over queries: with
    the block bounds prefetched per (query, partition), the compiled
    program still holds one kernel launch and no loop over the batch."""
    text = _compile(
        jax.vmap(lambda x, m: sweep_ops.sfs_sweep(
            x, m, block=256, wcap=2048, sentinel=1e30, spec="pallas")),
        one_chip, ((4, 8, 8192, 7), jnp.float32), ((4, 8, 8192), jnp.bool_))
    assert text.count("tpu_custom_call") == 1
    assert " while(" not in text


@pytest.mark.parametrize("lower_tri", [False, True])
def test_dominance_compiles(one_chip, lower_tri):
    text = _compile(
        lambda c, r, m: dominated_mask_pallas(c, r, m, lower_tri=lower_tri),
        one_chip, ((D_PAD, 4096), jnp.float32), ((D_PAD, 4096), jnp.float32),
        ((1, 4096), jnp.int32))
    assert "tpu_custom_call" in text


def test_noseq_test_compiles_under_its_name(one_chip):
    """NoSeq's phase 2 at the `ant5` cell's geometry: each of 8 local
    skylines (8,192 rows) against the 65,536-row union, vmapped, under
    the kernel name a device trace shows (`noseq_mask`)."""
    from repro.kernels.dominance.ops import dominated_mask
    text = _compile(
        jax.vmap(lambda c, r, m: dominated_mask(c, r, m, impl="pallas",
                                                name="noseq_mask"),
                 in_axes=(0, None, 0)),
        one_chip, ((8, 8192, 5), jnp.float32), ((65536, 5), jnp.float32),
        ((8, 65536), jnp.bool_))
    assert re.search(r"%noseq_mask[.\d]* = .*tpu_custom_call", text)
    assert not re.search(r"%dominated_mask[.\d]* = ", text)


@pytest.mark.parametrize("block,npad,wcap,wtile,want", [
    (256, 4096, 4096, 0, (256, 512)),      # wide window: always tiled
    (256, 4096, 512, 0, (256, 0)),         # one tile wide: untiled
    (256, 4096, 4096, 1024, (256, 1024)),  # aligned request kept
    (256, 4096, 4096, 96, (256, 512)),     # unaligned request replaced
    (256, 768, 768, 0, (256, 384)),        # tile divides the window
    (64, 640, 4096, 0, (128, 512)),        # block rounded to the lane
    (72, 72, 4104, 0, (72, 0)),            # whole-array block, odd window
])
def test_tpu_geometry_is_lane_aligned(block, npad, wcap, wtile, want):
    assert sweep_ops.tpu_geometry(block, npad, wcap, wtile) == want
