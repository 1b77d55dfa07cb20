"""Fused Mosaic kernels for the RLC MSM fast path (ops/msm.py).

Two arithmetic-dense stages run as Pallas kernels so their intermediates
live in VMEM/vregs instead of round-tripping HBM (the same motivation as
ops/pallas_ed25519.py, which measured the XLA-composed ladder at ~3.5x
the fused kernel):

  build_table_pallas    point decompression (sqrt chain, ~300 muls/point)
                        of -R_i / -A_i straight into niels rows
  bucket_scan_pallas    the layered bucket fill: grid (K/tile, T) with
                        the bucket accumulators RESIDENT in the output
                        blocks across the T sweep (the t axis is the
                        minor grid dimension, so each (tile)-slab of
                        buckets is revisited T times while staying in
                        VMEM); each step is one niels mixed add over the
                        tile lanes

Everything else in the MSM (digit windows, the sort, layer gather,
aggregation scans) is gather/sort-shaped — exactly what XLA:TPU already
does well — and stays in ops/msm.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import field as F
from .pallas_ed25519 import (_CONSTS_PACKED, _COL_D, _COL_D2, _COL_ONE,
                             _COL_SQRT_M1, _COL_TWO_P, _COL_ZERO,
                             _bytes_to_limbs, _carry_lazy, _eq, _freeze,
                             _madd_niels, _mul, _mul_const, _pow_p58,
                             _select, _sqr)

NLIMB = F.NLIMB
_i32 = jnp.int32

DEFAULT_TILE = 256


def _kernel_decompress_niels(const_ref, b_ref, ypx_ref, ymx_ref, t2d_ref,
                             ok_ref, one_scr, zero_scr):
    """Decompress one (32, T) block of compressed points into NEGATED
    niels rows: ypx(-P) = y - x, ymx(-P) = y + x, t2d(-P) = -2dxy.
    Mirrors the decompression block of pallas_ed25519._verify_tile
    (reference RFC 8032 §5.1.3 / Go fe.SetBytes semantics: non-canonical
    y accepted and reduced, negative zero rejected, non-square
    rejected)."""
    T = b_ref.shape[1]
    consts = const_ref[...]

    def cst(col):
        return consts[:, col : col + 1]

    # launder the one/zero limb constants through VMEM scratch (same
    # Mosaic replicated-layout workaround as pallas_ed25519._kernel)
    one_scr[...] = jnp.broadcast_to(cst(_COL_ONE), (NLIMB, T))
    zero_scr[...] = jnp.broadcast_to(cst(_COL_ZERO), (NLIMB, T))
    one = one_scr[...]
    two_p = cst(_COL_TWO_P)

    y_l, sign = _bytes_to_limbs(b_ref[...].astype(_i32) & 0xFF)
    y = _carry_lazy(y_l)
    yy = _sqr(y)
    u = yy - one
    v = _carry_lazy(_mul_const(yy, cst(_COL_D)) + one)
    v3 = _mul(_sqr(v), v)
    v7 = _mul(_sqr(v3), v)
    uv7 = _mul(u, v7)
    x = _mul(_mul(u, v3), _pow_p58(uv7))
    vxx = _mul(v, _sqr(x))
    ok_plus = _eq(vxx, _carry_lazy(u), two_p)
    ok_minus = _eq(vxx, _carry_lazy(-u), two_p)
    x = _select(ok_minus, _mul_const(x, cst(_COL_SQRT_M1)), x)
    ok = ok_plus | ok_minus
    x_frozen = _freeze(x, two_p)
    x_is_zero = jnp.all(x_frozen == 0, axis=0, keepdims=True)
    x_neg = x_frozen[0:1] & 1
    ok = ok & ~(x_is_zero & (sign == 1))
    x = _select(x_neg != sign, _carry_lazy(-x), x)
    t = _mul(x, y)
    # niels of -P: swap (y+x, y-x), negate 2dt
    ypx_ref[...] = _carry_lazy(y - x)
    ymx_ref[...] = _carry_lazy(y + x)
    t2d_ref[...] = _mul_const(_carry_lazy(-t), cst(_COL_D2))
    ok_ref[...] = jnp.broadcast_to(ok.astype(_i32), (8, T))


@partial(jax.jit, static_argnames=("tile",))
def decompress_niels_pallas(b_rows, tile: int = DEFAULT_TILE):
    """(32, B) int8 compressed points -> negated niels rows (3 arrays
    (NLIMB, B) int32) + ok (B,) bool.  B must be a multiple of tile."""
    B = b_rows.shape[1]
    assert b_rows.shape[0] == 32 and B % tile == 0, (b_rows.shape, tile)
    grid = (B // tile,)
    outs = pl.pallas_call(
        _kernel_decompress_niels,
        out_shape=[
            jax.ShapeDtypeStruct((NLIMB, B), _i32),
            jax.ShapeDtypeStruct((NLIMB, B), _i32),
            jax.ShapeDtypeStruct((NLIMB, B), _i32),
            jax.ShapeDtypeStruct((8, B), _i32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((NLIMB, 128), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((32, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((NLIMB, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((NLIMB, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((NLIMB, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[pltpu.VMEM((NLIMB, tile), _i32),
                        pltpu.VMEM((NLIMB, tile), _i32)],
    )(jnp.asarray(_CONSTS_PACKED), b_rows.astype(jnp.int8))
    ypx, ymx, t2d, ok = outs
    return (ypx, ymx, t2d), ok[0].astype(jnp.bool_)


def build_table_pallas(r_bytes, pub_bytes):
    """The pallas twin of msm._build_table: decompress -R_i / -A_i with
    the fused kernel, then msm.assemble_table for the shared layout."""
    from . import msm

    n = r_bytes.shape[0]
    both = jnp.concatenate([r_bytes, pub_bytes], axis=0)  # (2n, 32)
    # bucketed batches make n a power of two >= 64, so 2n is always a
    # multiple of 128; Mosaic wants full lane tiles
    assert (2 * n) % 128 == 0, n
    tile = DEFAULT_TILE if (2 * n) % DEFAULT_TILE == 0 else 128
    coords, ok = decompress_niels_pallas(both.T.astype(jnp.int8), tile=tile)
    return msm.assemble_table(coords), jnp.all(ok)


def _kernel_bucket_scan(ypx_ref, ymx_ref, t2d_ref, ox, oy, oz, ot):
    """One grid step: fold layer t's niels points into the resident
    bucket accumulators for this tile of buckets.  Grid is (K/tile, T)
    with t minor, so (ox, oy, oz, ot) stay in VMEM for the whole T
    sweep of a bucket tile."""
    t = pl.program_id(1)
    T = ox.shape[1]

    @pl.when(t == 0)
    def _init():
        ident_hi = jnp.zeros((NLIMB - 1, T), _i32)
        one_row = jnp.ones((1, T), _i32)
        ox[...] = jnp.zeros((NLIMB, T), _i32)
        oy[...] = jnp.concatenate([one_row, ident_hi], axis=0)
        oz[...] = jnp.concatenate([one_row, ident_hi], axis=0)
        ot[...] = jnp.zeros((NLIMB, T), _i32)

    px, py, pz, pt = ox[...], oy[...], oz[...], ot[...]
    nypx = ypx_ref[0]
    nymx = ymx_ref[0]
    nt2d = t2d_ref[0]
    rx, ry, rz, rt = _madd_niels(px, py, pz, pt, nypx, nymx, nt2d)
    ox[...] = rx
    oy[...] = ry
    oz[...] = rz
    ot[...] = rt


@partial(jax.jit, static_argnames=("tile",))
def _bucket_scan_call(ypx, ymx, t2d, tile: int):
    T, _, K = ypx.shape
    grid = (K // tile, T)
    spec_in = pl.BlockSpec((1, NLIMB, tile), lambda k, t: (t, 0, k),
                           memory_space=pltpu.VMEM)
    spec_out = pl.BlockSpec((NLIMB, tile), lambda k, t: (0, k),
                            memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _kernel_bucket_scan,
        out_shape=[jax.ShapeDtypeStruct((NLIMB, K), _i32)] * 4,
        grid=grid,
        in_specs=[spec_in] * 3,
        out_specs=[spec_out] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(ypx, ymx, t2d)


def bucket_scan_pallas(layers, K: int):
    """layers: 3 niels arrays (T, NLIMB, K).  K must be a multiple of
    256 (msm.Plan.K_pad guarantees it).  Returns bucket sums as
    curve.Ext (NLIMB, K)."""
    from . import curve as C

    assert K % 256 == 0, K
    x, y, z, t = _bucket_scan_call(*layers, tile=DEFAULT_TILE)
    return C.Ext(x, y, z, t)
