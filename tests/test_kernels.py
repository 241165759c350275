"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels.bitmap_join.kernel import (bitmap_join_kernel,
                                              bitmap_join_many_kernel)
from repro.kernels.bitmap_join.ops import bitmap_join
from repro.kernels.bitmap_join.ref import (bitmap_join_many_ref,
                                           bitmap_join_ref)

RNG = np.random.default_rng(0)


# ------------------------------------------------------------ bitmap_join
@pytest.mark.parametrize("e,w", [(1, 1), (7, 33), (256, 512), (300, 700),
                                 (513, 1025)])
def test_bitmap_join_shapes(e, w):
    prefix = jnp.asarray(RNG.integers(0, 2 ** 32, size=w, dtype=np.uint32))
    exts = jnp.asarray(RNG.integers(0, 2 ** 32, size=(e, w),
                                    dtype=np.uint32))
    out = bitmap_join_kernel(prefix, exts, interpret=True)
    np.testing.assert_array_equal(out, bitmap_join_ref(prefix, exts))


def test_bitmap_join_ops_dispatches_to_ref_on_cpu():
    prefix = jnp.asarray(RNG.integers(0, 2 ** 32, size=64, dtype=np.uint32))
    exts = jnp.asarray(RNG.integers(0, 2 ** 32, size=(8, 64),
                                    dtype=np.uint32))
    np.testing.assert_array_equal(bitmap_join(prefix, exts),
                                  bitmap_join_ref(prefix, exts))


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 64), st.integers(1, 96))
def test_property_bitmap_join_random(e, w):
    prefix = jnp.asarray(RNG.integers(0, 2 ** 32, size=w, dtype=np.uint32))
    exts = jnp.asarray(RNG.integers(0, 2 ** 32, size=(e, w),
                                    dtype=np.uint32))
    out = bitmap_join_kernel(prefix, exts, interpret=True)
    np.testing.assert_array_equal(out, bitmap_join_ref(prefix, exts))


# ------------------------------------------------- bitmap_join_many (batched)
@pytest.mark.parametrize("b,e,w", [(1, 1, 1), (3, 7, 33), (2, 64, 512),
                                   (5, 70, 600), (3, 130, 700),
                                   (33, 129, 1500)])
def test_bitmap_join_many_shapes(b, e, w):
    prefixes = jnp.asarray(RNG.integers(0, 2 ** 32, size=(b, w),
                                        dtype=np.uint32))
    exts = jnp.asarray(RNG.integers(0, 2 ** 32, size=(b, e, w),
                                    dtype=np.uint32))
    out = bitmap_join_many_kernel(prefixes, exts, interpret=True)
    np.testing.assert_array_equal(out, bitmap_join_many_ref(prefixes, exts))


def test_bitmap_join_many_each_row_matches_single_prefix_kernel():
    """Batch semantics: row b of the batched launch is exactly the
    single-prefix kernel run on (prefixes[b], exts[b])."""
    b, e, w = 4, 10, 40
    prefixes = jnp.asarray(RNG.integers(0, 2 ** 32, size=(b, w),
                                        dtype=np.uint32))
    exts = jnp.asarray(RNG.integers(0, 2 ** 32, size=(b, e, w),
                                    dtype=np.uint32))
    batched = bitmap_join_many_kernel(prefixes, exts, interpret=True)
    for i in range(b):
        np.testing.assert_array_equal(
            batched[i], bitmap_join_kernel(prefixes[i], exts[i],
                                           interpret=True))


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 6), st.integers(1, 32), st.integers(1, 70))
def test_property_bitmap_join_many_random(b, e, w):
    prefixes = jnp.asarray(RNG.integers(0, 2 ** 32, size=(b, w),
                                        dtype=np.uint32))
    exts = jnp.asarray(RNG.integers(0, 2 ** 32, size=(b, e, w),
                                    dtype=np.uint32))
    out = bitmap_join_many_kernel(prefixes, exts, interpret=True)
    np.testing.assert_array_equal(out, bitmap_join_many_ref(prefixes, exts))
