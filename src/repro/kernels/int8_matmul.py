"""W8A8 int8 matmul Pallas kernel with fused dequant epilogue.

TPU mapping of the paper's int8 inference path: the MXU consumes s8xs8
tiles accumulating in s32 VREGs; the epilogue applies the zero-point
correction, the combined per-output-channel scale (s_x * s_w), and the
bias — so the dequantized tile is written to HBM exactly once (no
separate dequant kernel as in the CUDA reference flow).

Tiling: grid (M/bm, N/bn, K/bk), k innermost. x tile (bm,bk) and w tile
(bk,bn) stream through VMEM; the (bm,bn) s32 accumulator lives in VMEM
scratch. Block dims default to MXU-aligned multiples of 128 (bm 128,
bn 128, bk 256 -> ~160KB VMEM working set, well under the ~16MB/core
budget, leaving room for double buffering).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 256


def _kernel(x_ref, w_ref, scale_ref, corr_ref, bias_ref, o_ref, acc_ref, *,
            nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...] - corr_ref[...]            # zero-point correction
        y = acc.astype(jnp.float32) * scale_ref[...]
        y = y + bias_ref[...]
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def int8_matmul(xq, wq, scale, corr, bias=None, *, bm=DEFAULT_BM,
                bn=DEFAULT_BN, bk=DEFAULT_BK, out_dtype=jnp.float32,
                interpret=False):
    """y[M,N] = (xq @ wq - corr) * scale (+ bias).

    xq: (M,K) int8, wq: (K,N) int8, scale: (N,) f32 (s_x*s_w per channel),
    corr: (N,) int32 (z_eff * colsum(wq)), bias: (N,) f32 or None.
    Shapes need not be block-aligned; inputs are zero-padded (int8 zero
    pads contribute zx*0 handled inside corr of the REAL columns only —
    padding columns are sliced away).
    """
    M, K = xq.shape
    K2, N = wq.shape
    assert K == K2, (xq.shape, wq.shape)
    bm_, bn_, bk_ = min(bm, _ceil(M)), min(bn, _ceil(N)), min(bk, _ceil(K))
    Mp, Np, Kp = _pad_to(M, bm_), _pad_to(N, bn_), _pad_to(K, bk_)

    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    xq = jnp.pad(xq, ((0, Mp - M), (0, Kp - K)))
    wq = jnp.pad(wq, ((0, Kp - K), (0, Np - N)))
    scale = jnp.pad(scale.astype(jnp.float32), (0, Np - N)).reshape(1, Np)
    corr = jnp.pad(corr.astype(jnp.int32), (0, Np - N)).reshape(1, Np)
    bias = jnp.pad(bias.astype(jnp.float32), (0, Np - N)).reshape(1, Np)

    nk = Kp // bk_
    grid = (Mp // bm_, Np // bn_, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk_, bn_), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn_), lambda m, n, k: (0, n)),
            pl.BlockSpec((1, bn_), lambda m, n, k: (0, n)),
            pl.BlockSpec((1, bn_), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        interpret=interpret,
        name="int8_matmul",
    )(xq, wq, scale, corr, bias)
    return out[:M, :N]


def _ceil(x, to=8):
    return max(to, -to * (-x // to))


def _pad_to(x, b):
    return -b * (-x // b)


def _group_param(shape, index_map):
    """BlockSpec for one group's row of a (G, 1, n) param stack.

    The stack carries a unit middle axis so that the last two block dims
    equal the array's own (1, n): the TPU tiling then admits a one-row
    block at any G. The squeezed group axis is picked by ``index_map``,
    and the kernel sees a plain (1, n) tile."""
    return pl.BlockSpec((pl.squeezed, 1) + tuple(shape), index_map)


def _stack3(p):
    """(G, n) param stack -> (G, 1, n) for ``_group_param`` blocks."""
    p = jnp.asarray(p)
    return p.reshape(p.shape[0], 1, p.shape[1])
