"""Pallas TPU kernel: sparse gather-intersect support counting.

counts[b, e] = Σ_s bit(exts[b, e], tids[b, s])

This is the hybrid representation's sparse sweep: each request's
prefix row arrives as a sorted tid-list (or dEclat diffset — the
kernel doesn't care which), and instead of AND+popcount over all W
words, the kernel walks the S tids and for each one gathers a single
ext word and tests a single bit — O(S) work per extension regardless
of row width.

Layout: the tid list lives in SMEM (scalar memory), one S tile of one
request per grid step, so each tid is a scalar read. The extension
block is held WORD-MAJOR ([Wt, E_TILE]) in VMEM so the per-tid word
index lands on the sublane axis. A dynamic sublane read must start at
a multiple of 8, so each tid loads the aligned [8, E_TILE] tile that
holds its word and keeps only that word's sublane: an [8, E_TILE]
accumulator collects bits per sublane and is summed once per grid
step. One VPU pass per tid covers the whole extension tile. The tid
walk is a fori_loop with padded lanes carrying the sentinel -1
(masked, not skipped: the loop trip count must be static); an S tile
whose first tid is padding, or lies past the W tile, is skipped whole
(tids are sorted per row).

Grid: (B, E tiles, W tiles, S tiles). The W axis is tiled at
``GW_TILE`` words, so the VMEM footprint is bounded at any row width:
one [8192, 128] uint32 block is 4 MiB, 8 MiB double-buffered, inside
v5e's 16 MiB default scoped VMEM (a whole [16384, 128] block, 16 MiB
double-buffered, is refused by the compiler for running out of VMEM).
Rows wider than one W tile (more than 256K transactions per segment)
re-walk their tids once per W tile, counting only the tids that fall
inside it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

E_TILE = 128     # lane width of one extension tile
W_SUB = 8        # sublane tile of the word-major axis
GW_TILE = 8192   # words per W tile (4 MiB uint32 block at E_TILE lanes)
S_TILE = 1024    # tids per SMEM block
LANES = 128


def _many_kernel(tids_ref, exts_ref, out_ref):
    # tids_ref: [1, 1, St] int32 (SMEM); exts_ref: [1, Wt, E_TILE] uint32
    # (word-major, VMEM); out_ref: [1, 1, E_TILE] int32
    w_idx = pl.program_id(2)
    s_idx = pl.program_id(3)
    s_len = tids_ref.shape[2]
    wt = exts_ref.shape[1]
    lo = w_idx * (wt * 32)          # first tid this W tile holds
    span = wt * 32

    @pl.when((w_idx == 0) & (s_idx == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    first = tids_ref[0, 0, 0]

    @pl.when((first >= 0) & (first < lo + span))
    def _sweep():
        sub = jax.lax.broadcasted_iota(jnp.int32, (W_SUB, E_TILE), 0)

        def body(s, acc):
            t = tids_ref[0, 0, s]
            rel = t - lo
            ok = (t >= 0) & (rel >= 0) & (rel < span)
            rel = jnp.where(ok, rel, 0)
            w = rel >> 5
            base = pl.multiple_of((w >> 3) << 3, W_SUB)
            tile = exts_ref[0, pl.ds(base, W_SUB), :]      # [8, E_TILE]
            bits = (tile >> (rel & 31).astype(jnp.uint32)) & jnp.uint32(1)
            row = jnp.where(ok, w & (W_SUB - 1), -1)       # -1: no row
            return acc + jnp.where(sub == row, bits.astype(jnp.int32), 0)

        acc = jax.lax.fori_loop(0, s_len, body,
                                jnp.zeros((W_SUB, E_TILE), jnp.int32))
        out_ref[0] += jnp.sum(acc, axis=0, keepdims=True)


def _round_up(n: int, m: int) -> int:
    return max((n + m - 1) // m * m, m)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_intersect_many_kernel(tids: jnp.ndarray, exts: jnp.ndarray,
                                 *, interpret: bool = False
                                 ) -> jnp.ndarray:
    """tids: [B, S] int32 (-1 = padded lane); exts: [B, E, W] uint32
    -> counts [B, E] int32.

    E is padded to E_TILE, W to a sublane multiple (or to GW_TILE
    multiples past one tile; padded words are never gathered: every
    valid tid is < 32·W), S to a lane multiple (or S_TILE multiples)
    with the -1 sentinel. The extension block is transposed word-major
    on device before the launch.
    """
    b, e, w = exts.shape
    s = tids.shape[1]
    ep = _round_up(e, E_TILE)
    wt = min(_round_up(w, W_SUB), GW_TILE)
    wp = _round_up(w, wt)
    st = min(_round_up(s, LANES), S_TILE)
    sp = _round_up(s, st)
    if (ep, wp) != (e, w):
        exts = jnp.pad(exts, ((0, 0), (0, ep - e), (0, wp - w)))
    if sp != s:
        tids = jnp.pad(tids, ((0, 0), (0, sp - s)), constant_values=-1)
    exts_t = jnp.transpose(exts, (0, 2, 1))            # [B, Wp, Ep]
    ns = sp // st
    grid = (b, ep // E_TILE, wp // wt, ns)
    out = pl.pallas_call(
        _many_kernel,
        grid=grid,
        in_specs=[
            # tids as [B·ns, 1, St]: the (8, 128) block rule binds SMEM
            # blocks too, and a 1-D block would not match XLA's
            # 1024-element tiling of 1-D int32 arrays
            pl.BlockSpec((1, 1, st),
                         lambda bi, i, wj, sj: (bi * ns + sj, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, wt, E_TILE),
                         lambda bi, i, wj, sj: (bi, wj, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, E_TILE),
                               lambda bi, i, wj, sj: (bi, 0, i)),
        out_shape=jax.ShapeDtypeStruct((b, 1, ep), jnp.int32),
        interpret=interpret,
        name="gather_intersect_many",
    )(tids.reshape(b * ns, 1, st), exts_t)
    return out[:, 0, :e]
