"""jit'd public wrapper: the compiled Pallas kernel, the interpreter,
or the jnp oracle, by explicit mode.

Compiled-function caching: the Pallas kernels are jitted once at module
level (``kernel.py``), and the jnp reference paths go through
:func:`_jitted`, an lru-cached factory — so a wrapper is built once per
function and jax's own shape-keyed cache handles the rest. The old
pattern of calling ``jax.jit(fn)`` inline created a FRESH wrapper per
call, which re-traced every level of a mining run (the per-level
recompilation bug the distributed driver used to have with its
``functools.partial``-wrapped ``shard_map`` bodies)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitmap_join.kernel import (bitmap_join_kernel,
                                              bitmap_join_many_kernel)
from repro.kernels.bitmap_join.ref import (bitmap_join_many_ref,
                                           bitmap_join_ref)

MODES = ("auto", "ref", "pallas-interpret", "pallas-jit")


@functools.lru_cache(maxsize=None)
def _jitted(fn):
    """One persistent jit wrapper per reference function. jax keys its
    compile cache on the wrapper object, so re-wrapping per call would
    re-trace on every invocation."""
    return jax.jit(fn)


def resolve_mode(mode: str) -> str:
    """Validate ``mode`` and resolve "auto": the compiled kernel when
    jax's default backend is a TPU, the jnp oracle otherwise. Every
    other mode runs as named — "pallas-jit" off a TPU raises in the
    Pallas lowering rather than falling back."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if mode == "auto":
        return "pallas-jit" if jax.default_backend() == "tpu" else "ref"
    return mode


def bitmap_join(prefix: jnp.ndarray, exts: jnp.ndarray,
                *, mode: str = "auto") -> jnp.ndarray:
    """Support counts of prefix∧ext for a cluster of extension bitmaps.

    ``mode`` names an execution strategy (see :func:`resolve_mode`):
    "ref" runs the jnp oracle, "pallas-jit" compiles the Pallas kernel
    for the current backend, and "pallas-interpret" runs the same
    kernel under the Pallas interpreter (bit-exact with "pallas-jit",
    available on CPU).
    """
    mode = resolve_mode(mode)
    if mode == "ref":
        return _jitted(bitmap_join_ref)(prefix, exts)
    return bitmap_join_kernel(prefix, exts,
                              interpret=mode == "pallas-interpret")


def bitmap_join_many(prefixes: jnp.ndarray, exts: jnp.ndarray,
                     mask: jnp.ndarray | None = None,
                     *, mode: str = "auto") -> jnp.ndarray:
    """Batched multi-prefix join: counts[b, e] = |prefixes[b] ∧ exts[b, e]|.

    prefixes: [B, W] uint32; exts: [B, E_max, W] uint32; optional mask
    [B, E_max] bool zeroes padded lanes of ragged batches (the sweep
    dispatcher pads every request to E_max). One kernel launch covers
    all B requests — the dispatcher's coalescing unit.
    """
    mode = resolve_mode(mode)
    if mode == "ref":
        counts = _jitted(bitmap_join_many_ref)(prefixes, exts)
    else:
        counts = bitmap_join_many_kernel(
            prefixes, exts, interpret=mode == "pallas-interpret")
    if mask is not None:
        counts = jnp.where(mask, counts, 0)
    return counts
