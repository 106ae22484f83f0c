"""Density (heatmap) kernel: weighted 2-D grid histograms on device.

The aggregation the reference pushes to tablet servers as DensityScan /
DensityIterator (geomesa-index-api/.../iterators/DensityScan.scala:31-109:
snap each feature to a W×H grid over the query envelope via GridSnap,
accumulate weights into a sparse (row, col) → weight map, merge partial
grids client-side).  Here the grid is a dense device array built with one
masked scatter-add — and the cross-shard merge is a ``psum`` over the mesh
instead of a client reduce (SURVEY.md §2.7 "scatter-gather + client
reduce").
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["density_grid", "density_grid_auto", "density_grid_sorted",
           "grid_snap", "pyramid_reduce", "pyramid_reduce_np"]


def grid_snap(x, y, env, width: int, height: int):
    """GridSnap semantics (geomesa-utils GridSnap): index of the cell
    containing each point; points outside the envelope are clamped."""
    xmin, ymin, xmax, ymax = env
    dx = (xmax - xmin) / width
    dy = (ymax - ymin) / height
    ix = jnp.clip(jnp.floor((x - xmin) / dx).astype(jnp.int32), 0, width - 1)
    iy = jnp.clip(jnp.floor((y - ymin) / dy).astype(jnp.int32), 0, height - 1)
    return ix, iy


@partial(jax.jit, static_argnames=("width", "height"))
def density_grid(x, y, weights, mask, env, width: int, height: int):
    """Masked weighted histogram: (N,) coords → (height, width) float64 grid.

    ``mask`` selects the features that passed the query filter; ``weights``
    is the DENSITY_WEIGHT expression column (ones for plain counts).
    """
    ix, iy = grid_snap(x, y, env, width, height)
    flat = iy.astype(jnp.int32) * width + ix
    w = jnp.where(mask, weights, 0.0)
    grid = jnp.zeros(width * height, dtype=jnp.float64).at[flat].add(w)
    return grid.reshape(height, width)


@partial(jax.jit, static_argnames=("width", "height"))
def density_grid_sorted(x, y, weights, mask, env, width: int, height: int):
    """Sort-by-cell histogram: sort (cell, weight) pairs, then per-cell
    segment sums via cumsum differences at searchsorted cell boundaries.

    O(n log n) independent of the grid size, vs the one-hot MXU kernel's
    O(n·G) — the faster path for large batches or fine grids (the device
    sort runs ~230M keys/s, so 16M points cost ~70ms of sort).  The
    cumsum accumulates in float64 (exact far past 2^24), with the final
    per-cell sums rounded to the float32 output grid like the Pallas
    path; masked rows sort to a sentinel cell past the grid."""
    ix, iy = grid_snap(x, y, env, width, height)
    flat = jnp.where(mask, iy * width + ix, jnp.int32(width * height))
    w = jnp.where(mask, weights, 0.0).astype(jnp.float32)
    flat_s, w_s = jax.lax.sort((flat, w), dimension=0, num_keys=1)
    cw = jnp.concatenate([jnp.zeros(1, jnp.float64),
                          jnp.cumsum(w_s.astype(jnp.float64))])
    bounds = jnp.searchsorted(
        flat_s, jnp.arange(width * height + 1, dtype=jnp.int32), side="left")
    grid = (cw[bounds[1:]] - cw[bounds[:-1]]).astype(jnp.float32)
    return grid.reshape(height, width)


#: above ~2M points (or per-point one-hot work ~6e10 compares) the sorted
#: path beats the MXU one-hot kernel; measured crossover on v5e
_SORTED_MIN_N = 2_000_000


@partial(jax.jit, static_argnames=("levels",))
def pyramid_reduce(grid, levels: int):
    """2×2 reduction ladder for density pyramids (ISSUE 18): fold a
    square power-of-two (w, w) float64 cell-count grid into ``levels``
    successively-halved sum grids, returning the tuple
    ``(w/2, w/4, ..., w/2^levels)``.

    Each level is an EXACT 2×2 block sum of its parent — counts are
    integers carried in float64 (exact below 2^53), so any level equals
    what binning the raw points at that resolution would produce,
    bit-for-bit (the pyramid-serving exactness contract in
    docs/density.md)."""
    out = []
    g = grid
    for _ in range(levels):
        h, w = g.shape
        g = g.reshape(h // 2, 2, w // 2, 2).sum(axis=(1, 3))
        out.append(g)
    return tuple(out)


def pyramid_reduce_np(grid, levels: int):
    """Numpy twin of :func:`pyramid_reduce` for host-tier (spilled) run
    grids — same exact 2×2 integer-in-f64 block sums, no device
    round-trip."""
    out = []
    g = grid
    for _ in range(levels):
        h, w = g.shape
        g = g.reshape(h // 2, 2, w // 2, 2).sum(axis=(1, 3))
        out.append(g)
    return tuple(out)


def density_grid_auto(x, y, weights, mask, env, width: int, height: int):
    """Dispatch: Pallas MXU one-hot histogram for small batches on TPU,
    sort-based segment sums for large batches or fine grids (one-hot work
    grows with n·G), XLA scatter elsewhere."""
    from .pallas_kernels import GATES, density_grid_pallas

    if GATES["density"].choose():
        n = x.shape[0]
        if n >= _SORTED_MIN_N or n * width * height >= 6e10:
            return density_grid_sorted(x, y, weights, mask, env,
                                       width, height)
        return density_grid_pallas(x, y, weights, mask, env,
                                   width, height)
    return density_grid(x, y, weights, mask, env, width, height)
