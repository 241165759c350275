"""Compile both sweep kernels for a TPU v5e at the widths the dispatcher
produces, without a chip: the TPU compiler is installed, and it compiles
for a described (not attached) device. This catches what interpret mode
cannot — block shapes off the (8, 128) tiling, dynamic indexes Mosaic
cannot prove aligned, VMEM overuse — at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports this file."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bitmap_join.kernel import bitmap_join_many_kernel
from repro.kernels.gather_intersect.kernel import (
    gather_intersect_many_kernel)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # no skip: a missing TPU compiler is a failure, not an absent check
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("b,e,w", [(1, 64, 1024), (8, 128, 4096),
                                   (32, 256, 16384), (32, 128, 2)])
def test_bitmap_join_many_compiles_for_v5e(one_chip, b, e, w):
    text = _compiled_text(bitmap_join_many_kernel, one_chip,
                          ((b, w), jnp.uint32), ((b, e, w), jnp.uint32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b,e,w,s", [(1, 128, 4096, 256),
                                     (32, 128, 4096, 128),
                                     (32, 256, 16384, 4096)])
def test_gather_intersect_many_compiles_for_v5e(one_chip, b, e, w, s):
    text = _compiled_text(gather_intersect_many_kernel, one_chip,
                          ((b, s), jnp.int32), ((b, e, w), jnp.uint32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind,b,lead,e,w,cap", [
    ("dense", 8, 1, 1024, 3125, 4096),      # a level-2 bucket batch
    ("dense", 4, 2, 128, 70, 512),          # tuple prefixes, delta width
    ("sparse", 8, 1024, 128, 3125, 4096)])  # tid columns lead
def test_packed_flush_programs_compile_for_v5e(one_chip, kind, b, lead, e,
                                               w, cap):
    """One flush part is one program: the mirror and one packed int32
    descriptor in, the row gather, W pad and kernel fused behind it."""
    from repro.core.join_backend import _flush_fns
    from repro.core.tidlist import pow2

    dense, sparse = _flush_fns("pallas-jit")
    mirror = jax.ShapeDtypeStruct((cap, w), jnp.uint32, sharding=one_chip)
    desc = jax.ShapeDtypeStruct((b, lead + e), jnp.int32, sharding=one_chip)
    if kind == "dense":
        lowered = dense.lower(mirror, desc, lp=lead, wp=pow2(w))
    else:
        lowered = sparse.lower(mirror, desc, sp=lead, wp=pow2(w))
    assert "tpu_custom_call" in lowered.compile().as_text()
