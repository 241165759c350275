"""Pallas TPU kernel: TID-bitmap join (AND + popcount) support counting.

counts[e] = Σ_w popcount(prefix[w] & exts[e, w])

This is the paper's per-task join restructured for the TPU memory
hierarchy: the shared (k-1)-prefix bitmap tile is held in VMEM across the
whole extension-tile sweep (the clustered policy's cache reuse, made
structural), while extension bitmaps stream HBM→VMEM. Popcount is
`lax.population_count` on the VPU; the W-tile accumulation runs in the
innermost grid dimension with an @pl.when(first)-guarded init.

Tiling: E×W = 256×512 words per step → exts tile 512 KiB (uint32),
prefix tile 2 KiB, counts tile 1 KiB — comfortably VMEM-resident, lanes
aligned (512 words = 4×128 lanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

E_TILE = 256
W_TILE = 512


def _kernel(prefix_ref, exts_ref, out_ref):
    w_idx = pl.program_id(1)

    @pl.when(w_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    p = prefix_ref[...]                       # [1, Wt] uint32 (VMEM)
    e = exts_ref[...]                         # [Et, Wt] uint32
    joined = jnp.bitwise_and(e, p)            # broadcast over E
    counts = jax.lax.population_count(joined).astype(jnp.int32)
    out_ref[...] += jnp.sum(counts, axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_join_kernel(prefix: jnp.ndarray, exts: jnp.ndarray,
                       *, interpret: bool = False) -> jnp.ndarray:
    """prefix: [W] uint32; exts: [E, W] uint32 -> counts [E] int32.

    E and W are padded to tile multiples (zero words count nothing).
    """
    e, w = exts.shape
    ep = (e + E_TILE - 1) // E_TILE * E_TILE
    wp = (w + W_TILE - 1) // W_TILE * W_TILE
    if (ep, wp) != (e, w):
        exts = jnp.pad(exts, ((0, ep - e), (0, wp - w)))
        prefix = jnp.pad(prefix, (0, wp - w))
    grid = (ep // E_TILE, wp // W_TILE)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, W_TILE), lambda i, j: (0, j)),
            pl.BlockSpec((E_TILE, W_TILE), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((E_TILE,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((ep,), jnp.int32),
        interpret=interpret,
    )(prefix[None, :], exts)
    return out[:e]


# ---------------------------------------------------------------------------
# Multi-prefix (batched) variant: one grid launch for B coalesced sweeps
# ---------------------------------------------------------------------------

# The batched kernel's blocks obey the TPU tiling rule: the last two
# dims of every block are multiples of (8, 128) or span the whole axis.
# Prefixes and counts therefore carry a unit middle axis ([B, 1, W],
# [B, 1, Ep]), so a block's batch row is a leading (untiled) dim and
# its lane dim is a 128-multiple. [128, 512] words = 256 KiB uint32
# per exts block; narrow segments (delta sweeps) shrink the W tile to
# their width rounded up to one 128-lane vreg instead of padding to 512.
EB_TILE = 128
WB_TILE = 512
LANES = 128


def _many_kernel(prefixes_ref, exts_ref, out_ref):
    # prefixes_ref: [1, 1, Wt]; exts_ref: [1, Et, Wt]; out_ref: [1, 1, Et]
    w_idx = pl.program_id(2)

    @pl.when(w_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    p = prefixes_ref[0]                       # [1, Wt] uint32 (VMEM,
                                              # resident across the
                                              # request's E sweep)
    e = exts_ref[0]                           # [Et, Wt] uint32
    joined = jnp.bitwise_and(e, p)            # broadcast over E
    counts = jax.lax.population_count(joined).astype(jnp.int32)
    out_ref[0] += jnp.sum(counts, axis=1)[None, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_join_many_kernel(prefixes: jnp.ndarray, exts: jnp.ndarray,
                            *, interpret: bool = False) -> jnp.ndarray:
    """prefixes: [B, W] uint32; exts: [B, E, W] uint32 -> [B, E] int32.

    B coalesced sweep requests share one grid launch; within each
    batch row the request's prefix tile stays VMEM-resident across its
    extension sweep (same reuse as the single-prefix kernel). E and W
    are padded to tile multiples — zero words count nothing, and the
    dispatcher slices each request's real extension count out.
    """
    b, e, w = exts.shape
    ep = (e + EB_TILE - 1) // EB_TILE * EB_TILE
    wt = min(WB_TILE, (w + LANES - 1) // LANES * LANES)
    wp = (w + wt - 1) // wt * wt
    if (ep, wp) != (e, w):
        exts = jnp.pad(exts, ((0, 0), (0, ep - e), (0, wp - w)))
        prefixes = jnp.pad(prefixes, ((0, 0), (0, wp - w)))
    grid = (b, ep // EB_TILE, wp // wt)
    out = pl.pallas_call(
        _many_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, wt), lambda bi, i, j: (bi, 0, j)),
            pl.BlockSpec((1, EB_TILE, wt), lambda bi, i, j: (bi, i, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, EB_TILE), lambda bi, i, j: (bi, 0, i)),
        out_shape=jax.ShapeDtypeStruct((b, 1, ep), jnp.int32),
        interpret=interpret,
        name="bitmap_join_many",
    )(prefixes[:, None, :], exts)
    return out[:, 0, :e]
