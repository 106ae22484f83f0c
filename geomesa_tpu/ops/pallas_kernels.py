"""Pallas TPU kernels for the scan-side hot ops.

The reference runs its aggregation hot loops row-at-a-time inside tablet
servers (AggregatingScan.aggregate, geomesa-index-api/.../iterators/
AggregatingScan.scala:80-102; DensityScan.writeGeom, DensityScan.scala:55-58).
The XLA ports in :mod:`geomesa_tpu.ops.density` express the same math as
scatter-adds, which TPU lowers to a serialized per-element update loop.
These Pallas kernels re-shape the work for the hardware instead:

* **density**: the weighted 2-D histogram becomes a one-hot contraction on
  the MXU — each (chunk × grid-tile) program compares its chunk's flat cell
  ids against the tile's cell ids (broadcasted iota), multiplies by the
  weight column, and accumulates ``w @ onehot`` partials in a VMEM scratch
  accumulator across chunk steps.  O(N·G) lane-parallel flops replace O(N)
  serialized scatter updates; for GDELT-scale N and a 128-256² grid the MXU
  does this in ~1ms.
* **z3 candidate mask**: the push-down filter semantics of
  Z3Filter.inBounds (index/filters/Z3Filter.scala:19-55) — de-interleave
  each candidate z and compare the int-space coordinates against R query
  boxes — fused into one VMEM-resident pass producing a packed bool mask.

Both kernels are shape-polymorphic over padded inputs (pad with mask=0
rows) and run in interpreter mode off-TPU, so the same tests cover CPU CI
and real chips; ``tests/test_tpu_compile.py`` compiles them for a
described v5e with Mosaic lowering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["density_grid_pallas", "z3_mask_pallas", "z2_mask_pallas",
           "hist1d_pallas", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


class PallasGate:
    """Per-kernel Pallas routing: ``ok`` is None until the kernel first
    runs and True once it has.  A Mosaic failure is never caught here —
    it raises to the caller, so a run on the chip cannot silently serve
    the XLA twin."""

    def __init__(self, kind: str):
        self.kind = kind
        self.ok: bool | None = None

    def choose(self, enabled: bool = True) -> bool:
        """Routing decision for call sites that return lazy arrays:
        True = take the pallas path."""
        return enabled and on_tpu()

    def run(self, pallas_thunk, xla_thunk, enabled: bool = True):
        """``enabled`` must be computed from process-invariant inputs
        (global shapes, mesh size): multihost, the two variants are
        different compiled programs entering the same mesh collectives,
        so every process must take the same one."""
        if not (enabled and on_tpu()):
            return xla_thunk()
        out = pallas_thunk()
        self.ok = True
        return out


#: one gate per integrated kernel; pallas_health reports them all
GATES = {k: PallasGate(k)
         for k in ("z3_scan", "z2_scan", "hist1d", "density")}


def _interpret() -> bool:
    return not on_tpu()


# ---------------------------------------------------------------------------
# density: one-hot MXU histogram
# ---------------------------------------------------------------------------

_CHUNK = 512          # features per program along N
_GTILE = 2048         # grid cells per program along G


_ROWS = 8             # sublane-aligned rows per block (Mosaic requires 8)


def _density_kernel(cells_ref, w_ref, out_ref, acc_ref):
    """One (grid-tile j, chunk i) step: acc += w_i @ onehot(cells_i, tile_j).

    The chunk axis i is the fastest grid dimension, so for each grid tile j
    the accumulator is initialized at i == 0, summed over all chunks, and
    flushed at the last chunk before the next tile reuses the scratch.
    Each block carries _ROWS sublane rows of _CHUNK candidates; the rows
    accumulate via _ROWS sequential MXU contractions (onehot stays within
    VMEM budget that way).
    """
    j = pl.program_id(0)
    i = pl.program_id(1)
    n_i = pl.num_programs(1)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    cells = cells_ref[:]                   # (_ROWS, CHUNK) int32 flat cell ids
    w = w_ref[:]                           # (_ROWS, CHUNK) f32 (0 where masked)
    base = j * _GTILE
    tile_ids = base + jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, _GTILE), 1)
    for r in range(_ROWS):                 # static unroll
        onehot = (cells[r].reshape(_CHUNK, 1) == tile_ids).astype(jnp.float32)
        acc_ref[:] += jnp.dot(w[r].reshape(1, _CHUNK), onehot,
                              preferred_element_type=jnp.float32)

    @pl.when(i == n_i - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.partial(jax.jit, static_argnames=("width", "height"))
def density_grid_pallas(x, y, weights, mask, env, width: int, height: int):
    """Weighted masked 2-D histogram via MXU one-hot contraction.

    Same contract as :func:`geomesa_tpu.ops.density.density_grid`
    (DensityScan.writeGeom + client-side grid merge, DensityScan.scala:55-58,
    115-139): snap (x, y) to a ``height × width`` grid over ``env``,
    accumulate ``weights`` where ``mask``; returns float32 grid.
    """
    xmin, ymin, xmax, ymax = env
    dx = (xmax - xmin) / width
    dy = (ymax - ymin) / height
    ix = jnp.clip(jnp.floor((x - xmin) / dx).astype(jnp.int32), 0, width - 1)
    iy = jnp.clip(jnp.floor((y - ymin) / dy).astype(jnp.int32), 0, height - 1)
    cells = iy * width + ix
    # masked-out rows point at an id past every grid tile → contribute nowhere
    cells = jnp.where(mask, cells, jnp.int32(width * height))
    w = jnp.where(mask, weights, 0.0).astype(jnp.float32)

    n = cells.shape[0]
    block = _ROWS * _CHUNK
    n_pad = max(block, ((n + block - 1) // block) * block)
    cells = jnp.pad(cells, (0, n_pad - n), constant_values=width * height)
    w = jnp.pad(w, (0, n_pad - n))

    g = width * height
    g_pad = max(_GTILE, ((g + _GTILE - 1) // _GTILE) * _GTILE)

    n_rows = n_pad // _CHUNK
    grid = (g_pad // _GTILE, n_rows // _ROWS)
    # Mosaic rejects i64 program constants; trace the kernel in 32-bit mode
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _density_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((_ROWS, _CHUNK), lambda j, i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((_ROWS, _CHUNK), lambda j, i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((8, _GTILE), lambda j, i: (0, j),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((8, g_pad), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, _GTILE), jnp.float32)],
            interpret=_interpret(),
        )(cells.reshape(n_rows, _CHUNK), w.reshape(n_rows, _CHUNK))
    return out[0, :g].reshape(height, width)


# ---------------------------------------------------------------------------
# z3 candidate mask: fused de-interleave + R-box bounds test
# ---------------------------------------------------------------------------

_ZCHUNK = 1024


def _combine3_32(v):
    """Every-3rd-bit extract from a 32-bit lane (11 output bits)."""
    v = v & jnp.uint32(0x49249249)
    v = (v ^ (v >> jnp.uint32(2))) & jnp.uint32(0xC30C30C3)
    v = (v ^ (v >> jnp.uint32(4))) & jnp.uint32(0x0F00F00F)
    v = (v ^ (v >> jnp.uint32(8))) & jnp.uint32(0xFF0000FF)
    v = (v ^ (v >> jnp.uint32(16))) & jnp.uint32(0x0000FFFF)
    return v


def _z3_mask_kernel(boxes_ref, zlo_ref, zhi_ref, tlo_ref, thi_ref, out_ref):
    """Per-chunk Z3Filter.inBounds: decode z, OR the R box tests, AND the
    per-candidate time-offset bounds.

    Mosaic has no 64-bit lanes, so the z column arrives as two uint32
    halves; each 21-bit dimension recombines from an every-3rd-bit
    extract of both halves (offsets differ because 32 % 3 == 2)."""
    z_lo = zlo_ref[:]                                  # (_ROWS, ZCHUNK) u32
    z_hi = zhi_ref[:]

    def decode(shift):
        # dim bits sit at z positions p = 3k + shift; the hi half's local
        # offset is (shift + 1) % 3 and the lo half contributes
        # ceil((32 - shift) / 3) low bits
        nlo = (32 - shift + 2) // 3
        lo = _combine3_32(z_lo >> jnp.uint32(shift))
        hi = _combine3_32(z_hi >> jnp.uint32((shift + 1) % 3))
        return (lo | (hi << jnp.uint32(nlo))).astype(jnp.int32)

    xs = decode(0)
    ys = decode(1)
    ts = decode(2)

    r = boxes_ref.shape[0]
    hit = jnp.zeros(z_lo.shape, jnp.bool_)
    for k in range(r):                                 # R is static & small
        ok = (xs >= boxes_ref[k, 0]) & (ys >= boxes_ref[k, 1])
        ok &= (xs <= boxes_ref[k, 2]) & (ys <= boxes_ref[k, 3])
        hit |= ok
    out_ref[:] = hit & (ts >= tlo_ref[:]) & (ts <= thi_ref[:])


@jax.jit
def z3_mask_pallas(z, ixy, tlo, thi):
    """Vectorized Z3Filter.inBounds over R int-space boxes.

    ``z``: (N,) candidate z values; ``ixy``: (R, 4) int32 normalized
    [xlo, ylo, xhi, yhi]; ``tlo``/``thi``: (N,) int32 per-candidate time
    offset bounds (already gathered per owning range).  Returns bool (N,).
    Mirrors index/filters/Z3Filter.scala:19-55 (pointInBounds +
    timeInBounds per row) as one fused VMEM pass.
    """
    n = z.shape[0]
    block = _ROWS * _ZCHUNK
    n_pad = max(block, ((n + block - 1) // block) * block)
    zp = jnp.pad(z.astype(jnp.int64), (0, n_pad - n))
    # Mosaic has no 64-bit lanes: ship z as two uint32 halves
    z_u = zp.astype(jnp.uint64)
    z_lo = (z_u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    z_hi = (z_u >> jnp.uint64(32)).astype(jnp.uint32)
    tlop = jnp.pad(jnp.asarray(tlo, jnp.int32), (0, n_pad - n),
                   constant_values=1)
    thip = jnp.pad(jnp.asarray(thi, jnp.int32), (0, n_pad - n))
    n_rows = n_pad // _ZCHUNK
    ixy = jnp.asarray(ixy, jnp.int32).reshape(-1, 4)
    r = ixy.shape[0]

    vspec = pl.BlockSpec((_ROWS, _ZCHUNK), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    # Mosaic rejects i64 program constants; trace the kernel in 32-bit mode
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _z3_mask_kernel,
            grid=(n_rows // _ROWS,),
            in_specs=[
                pl.BlockSpec((r, 4), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                vspec, vspec, vspec, vspec,
            ],
            out_specs=vspec,
            out_shape=jax.ShapeDtypeStruct((n_rows, _ZCHUNK), jnp.bool_),
            interpret=_interpret(),
        )(ixy, z_lo.reshape(n_rows, _ZCHUNK), z_hi.reshape(n_rows, _ZCHUNK),
          tlop.reshape(n_rows, _ZCHUNK), thip.reshape(n_rows, _ZCHUNK))
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# z2 candidate mask: fused de-interleave + R-box bounds test
# ---------------------------------------------------------------------------


def _combine2_32(v):
    """Every-2nd-bit extract from a 32-bit lane (16 output bits)."""
    v = v & jnp.uint32(0x55555555)
    v = (v | (v >> jnp.uint32(1))) & jnp.uint32(0x33333333)
    v = (v | (v >> jnp.uint32(2))) & jnp.uint32(0x0F0F0F0F)
    v = (v | (v >> jnp.uint32(4))) & jnp.uint32(0x00FF00FF)
    v = (v | (v >> jnp.uint32(8))) & jnp.uint32(0x0000FFFF)
    return v


def _z2_mask_kernel(boxes_ref, zlo_ref, zhi_ref, out_ref):
    """Per-chunk Z2Filter.inBounds (index/filters/Z2Filter.scala role):
    decode the 31-bit x/y dims from the two uint32 z halves and OR the R
    int-space box tests.  Bit 32 is even, so both halves decode with the
    same every-2nd-bit extract (x from offset 0, y from offset 1)."""
    z_lo = zlo_ref[:]
    z_hi = zhi_ref[:]
    xs = (_combine2_32(z_lo)
          | (_combine2_32(z_hi) << jnp.uint32(16))).astype(jnp.int32)
    ys = (_combine2_32(z_lo >> jnp.uint32(1))
          | (_combine2_32(z_hi >> jnp.uint32(1))
             << jnp.uint32(16))).astype(jnp.int32)
    r = boxes_ref.shape[0]
    hit = jnp.zeros(z_lo.shape, jnp.bool_)
    for k in range(r):                                 # R is static & small
        ok = (xs >= boxes_ref[k, 0]) & (ys >= boxes_ref[k, 1])
        ok &= (xs <= boxes_ref[k, 2]) & (ys <= boxes_ref[k, 3])
        hit |= ok
    out_ref[:] = hit


@jax.jit
def z2_mask_pallas(z, ixy):
    """Vectorized Z2 int-space box mask over R boxes: the z2 scan's
    decode + (N × R) bounds broadcast as one fused VMEM pass (the exact
    float re-check stays in XLA — it fuses into the surrounding mask)."""
    n = z.shape[0]
    block = _ROWS * _ZCHUNK
    n_pad = max(block, ((n + block - 1) // block) * block)
    # pad with the max z — decodes to max coords, outside every box
    zp = jnp.pad(z.astype(jnp.int64), (0, n_pad - n),
                 constant_values=(1 << 62) - 1)
    z_u = zp.astype(jnp.uint64)
    z_lo = (z_u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    z_hi = (z_u >> jnp.uint64(32)).astype(jnp.uint32)
    n_rows = n_pad // _ZCHUNK
    ixy = jnp.asarray(ixy, jnp.int32).reshape(-1, 4)
    r = ixy.shape[0]
    vspec = pl.BlockSpec((_ROWS, _ZCHUNK), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _z2_mask_kernel,
            grid=(n_rows // _ROWS,),
            in_specs=[
                pl.BlockSpec((r, 4), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                vspec, vspec,
            ],
            out_specs=vspec,
            out_shape=jax.ShapeDtypeStruct((n_rows, _ZCHUNK), jnp.bool_),
            interpret=_interpret(),
        )(ixy, z_lo.reshape(n_rows, _ZCHUNK), z_hi.reshape(n_rows, _ZCHUNK))
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# 1-D histogram: one-hot MXU contraction (StatsScan's Histogram sketch)
# ---------------------------------------------------------------------------

_HTILE = 512


def _hist1d_kernel(bins_ref, w_ref, out_ref, acc_ref):
    """acc += w_i @ onehot(bins_i, tile_j): the 1-D sibling of the
    density kernel — replaces XLA's serialized scatter-add (TPU lowers
    ``.at[b].add`` to a per-element update loop)."""
    j = pl.program_id(0)
    i = pl.program_id(1)
    n_i = pl.num_programs(1)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    bins = bins_ref[:]
    w = w_ref[:]
    base = j * _HTILE
    tile_ids = base + jax.lax.broadcasted_iota(jnp.int32,
                                               (_CHUNK, _HTILE), 1)
    for r in range(_ROWS):
        onehot = (bins[r].reshape(_CHUNK, 1) == tile_ids).astype(jnp.float32)
        acc_ref[:] += jnp.dot(w[r].reshape(1, _CHUNK), onehot,
                              preferred_element_type=jnp.float32)

    @pl.when(i == n_i - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.partial(jax.jit, static_argnames=("n_bins",))
def hist1d_pallas(bins, weights, mask, n_bins: int):
    """Masked weighted 1-D histogram via the MXU one-hot trick.

    ``bins``: (N,) int32 bin ids in [0, n_bins); rows with ``mask`` False
    contribute nothing.  Returns float32 (n_bins,).  Serves the Histogram
    sketch of the stats scan (iterators/StatsScan.scala:125 +
    utils/stats/Histogram) where XLA's scatter-add serializes."""
    cells = jnp.where(mask, jnp.asarray(bins, jnp.int32), jnp.int32(n_bins))
    w = jnp.where(mask, weights, 0.0).astype(jnp.float32)
    n = cells.shape[0]
    block = _ROWS * _CHUNK
    n_pad = max(block, ((n + block - 1) // block) * block)
    cells = jnp.pad(cells, (0, n_pad - n), constant_values=n_bins)
    w = jnp.pad(w, (0, n_pad - n))
    g_pad = max(_HTILE, ((n_bins + _HTILE - 1) // _HTILE) * _HTILE)
    n_rows = n_pad // _CHUNK
    grid = (g_pad // _HTILE, n_rows // _ROWS)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _hist1d_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((_ROWS, _CHUNK), lambda j, i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((_ROWS, _CHUNK), lambda j, i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((8, _HTILE), lambda j, i: (0, j),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((8, g_pad), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, _HTILE), jnp.float32)],
            interpret=_interpret(),
        )(cells.reshape(n_rows, _CHUNK), w.reshape(n_rows, _CHUNK))
    return out[0, :n_bins]


def pallas_health() -> dict:
    """Health snapshot: whether the Pallas paths are live on this
    backend, and which kernels have run."""
    out = {"on_tpu": on_tpu()}
    for kind, gate in GATES.items():
        out[f"{kind}_ok"] = gate.ok
    return out
