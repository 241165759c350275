"""jit'd public wrapper for the sparse gather-intersect sweep.

Mirrors ``bitmap_join.ops``: one lru-cached jit wrapper per reference
function (fresh per-call ``jax.jit`` would re-trace every shape), and
the same four execution modes so ``SweepDispatcher`` backends can put
dense and sparse batches of one flush through matching strategies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitmap_join.ops import resolve_mode
from repro.kernels.gather_intersect.kernel import (
    gather_intersect_many_kernel)
from repro.kernels.gather_intersect.ref import gather_intersect_many_ref


@functools.lru_cache(maxsize=None)
def _jitted(fn):
    return jax.jit(fn)


def gather_intersect_many(tids: jnp.ndarray, exts: jnp.ndarray,
                          mask: jnp.ndarray | None = None,
                          *, mode: str = "auto") -> jnp.ndarray:
    """Batched sparse sweep: counts[b, e] = |tids[b] ∩ exts[b, e]|.

    tids: [B, S] int32 sorted per row, padded with -1 (ragged batches);
    exts: [B, E, W] uint32 word-columns; optional mask [B, E] bool
    zeroes padded extension lanes. An empty tid axis (S == 0) is the
    all-empty-intersection fast path — no launch at all.
    """
    mode = resolve_mode(mode)
    b, e, _ = exts.shape
    if tids.shape[1] == 0:
        return jnp.zeros((b, e), jnp.int32)
    if mode == "ref":
        counts = _jitted(gather_intersect_many_ref)(tids, exts)
    else:
        counts = gather_intersect_many_kernel(
            tids, exts, interpret=mode == "pallas-interpret")
    if mask is not None:
        counts = jnp.where(mask, counts, 0)
    return counts
