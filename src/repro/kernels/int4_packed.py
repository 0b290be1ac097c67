"""Packed-int4 fused serving kernels (the deployed W4A4 hot path).

Weights are stored TWO signed 4-bit codes per byte — byte ``i`` of a
column holds row ``2i`` in its low nibble and row ``2i + 1`` in its high
nibble — so the weight operand streams from HBM at half the int8 byte
count (a 2x weight-traffic cut on top of the int8 win, the dominant term
for the weight-bound ada/qkv/fc linears). The nibbles are widened to s8
codes in the VMEM prologue with two arithmetic shifts per byte
(sign-extension via ``((p & 0xF) ^ 8) - 8``) and fed to the MXU as s8xs8
dots, exactly like the int8 family; the MXU never sees a 4-bit operand.

Accuracy at 4 bits needs finer weight granularity than the int8 path's
per-output-channel scale (Q-DiT's observation): weights here carry
**per-(K-group, output-channel)** scales. The contraction axis is split
into groups of ``group_k`` rows — chosen at pack time to equal the
kernel's K tile, so one grid step is exactly one scale group — and the
s32 partial product of each K step is dequantized into a persistent
**f32** accumulator with that group's scale row before the next step:

    acc_f32 += (dot_s32(xq, unpack(wp)) - corr[g, k]) * scale[g, k]

``int4_matmul_fq``
    Affine 4-bit activations (uniform zero-point quantizer, the W4A4
    recipe's activation side): the fp tile is quantized in VMEM with the
    TGQ group-``g`` step ``clip(round(x/sx) + zx - 8, -8, 7)``, and the
    per-K-group zero-point correction ``corr[g, k] = z_eff[g] *
    colsum(codes[k-group])`` is subtracted before dequantization.

``int4_matmul_mrq_fq``
    Single-pass MRQ twin-region deployment at 4 bits (post-GELU fc2):
    the sign mask splits the activation tile into the two disjoint code
    tiles, ONE unpacked weight tile feeds two s32 dots, and both partial
    products are dequantized into one f32 accumulator with the region's
    per-K-group scale.

TGQ rides the same scalar-prefetch contract as ``int8_fused``: all
activation-side params are (G, ·)-stacked, ``g`` is a traced scalar
gathered by the BlockSpec index maps (scale/corr are (G, nk, N), viewed
as (G, nk, 1, N) with ``(g[0], k, 0, n)`` maps), so the DDPM scan still
compiles ONCE.

``int4_matmul_fq_vec`` / ``int4_matmul_mrq_fq_vec`` are the
vector-tgroup variants (see ``int8_fused``): a per-ROW (M,) group vector
replaces the scalar prefetch, the (G, bn) param slices of EVERY group
stream per K step, and each row gathers its own group's params in VMEM
with the exact select of ``int8_fused._gather_rows`` — one nibble-packed
weight stream covers a
batch mixing timestep groups.

Prologue/epilogue fusions: the whole family shares ``int8_fused``'s
optional norm-modulate prologue (``nm``), channel-balance prescale
(``ps``) and gate+residual epilogue (``gr``) — see that module's
docstring. The prologue runs before the quantize (and, for MRQ, before
the sign split); the epilogue gates + adds the residual tile onto the
f32 accumulator after the bias, ahead of the single HBM write.

Padding: K is padded to a multiple of ``group_k`` at pack time; padded
weight rows pack to code 0 and their column sums are not counted in
``corr``, so padded x columns (which quantize to the zero point) meet
zero codes and contribute nothing — the int8 padding argument, per group.

Tolerance contract: unlike the int8 family (integer accumulation, one
f32 epilogue — bit-exact vs the oracle), the per-K-group dequantization
accumulates in f32 once per K step. The oracle (`ref.int4_matmul_fq_ref`)
replays the same group-ordered accumulation; kernel-vs-oracle agreement
is a few f32 ulp (see the conformance suite's tolerance registry), not
bit-exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int8_fused import (
    _fusion_epilogue, _fusion_prologue, _fusions, _gather_rows,
    _unpack_fusion_refs,
)
from repro.kernels.int8_matmul import (
    DEFAULT_BK, DEFAULT_BM, DEFAULT_BN, _ceil, _group_param, _pad_to, _stack3,
)


def pack_int4(codes, axis=0):
    """Pack signed 4-bit codes two-per-byte along ``axis``.

    codes: int tensor of values in [-8, 7]. Rows ``2i``/``2i + 1`` along
    ``axis`` land in byte ``i``'s low/high nibble. An odd length is
    zero-padded by one row (code 0 dequantizes to 0 — inert).
    Returns int8 of the same shape with ``axis`` halved (rounded up).
    """
    c = jnp.moveaxis(jnp.asarray(codes), axis, 0)
    if c.shape[0] % 2:
        c = jnp.concatenate([c, jnp.zeros((1,) + c.shape[1:], c.dtype)], 0)
    u = c.astype(jnp.int32) & 0xF
    byte = u[0::2] | (u[1::2] << 4)
    byte = jnp.where(byte > 127, byte - 256, byte).astype(jnp.int8)
    return jnp.moveaxis(byte, 0, axis)


def nibble_split(packed):
    """One packed int8 tensor -> (low, high) sign-extended s4-in-s32 codes.

    The sign extension is branch-free: ``(u ^ 8) - 8`` maps the 4-bit
    two's-complement pattern u in [0, 15] onto [-8, 7].
    """
    p = jnp.asarray(packed).astype(jnp.int32)
    lo = ((p & 0xF) ^ 8) - 8
    hi = (((p >> 4) & 0xF) ^ 8) - 8
    return lo, hi


def unpack_int4(packed, k=None, axis=0):
    """Inverse of ``pack_int4``: interleave nibbles back to s8 codes.

    ``k`` trims the unpacked ``axis`` back to the pre-padding length.
    """
    p = jnp.moveaxis(jnp.asarray(packed), axis, 0)
    lo, hi = nibble_split(p)
    out = jnp.stack([lo, hi], axis=1).reshape((2 * p.shape[0],) + p.shape[1:])
    if k is not None:
        out = out[:k]
    return jnp.moveaxis(out.astype(jnp.int8), 0, axis)


def _unpack_w(w_ref, bk):
    """VMEM prologue: (bk/2, bn) packed bytes -> (bk, bn) s8 MXU codes."""
    lo, hi = nibble_split(w_ref[...])
    return jnp.stack([lo, hi], axis=1).reshape(
        bk, w_ref.shape[-1]).astype(jnp.int8)


def _kgroup_param(bn_, index_map):
    """BlockSpec for one (group, K step) row of a (G, nk, 1, N) scale or
    correction stack: both leading axes squeezed, so the last two block
    dims equal the array's (1, ·) at any G and nk."""
    return pl.BlockSpec((pl.squeezed, pl.squeezed, 1, bn_), index_map)


def _kstep_stack(G, bn_, nbn):
    """BlockSpec for K step k's (G, bn) slice of every group's scales or
    corrections, over a (G, nk, Np) stack viewed as (G, nk * Np) — a free
    reshape, where a block over the (nk, ·) axes would not tile: K step
    k, column tile n is block column ``k * nbn + n``."""
    return pl.BlockSpec((G, bn_), lambda m, n, k: (0, k * nbn + n))


def _fq4_kernel(g_ref, *refs, nk: int, bk: int, half: int, **fusions):
    """Grid body for ``int4_matmul_fq`` at grid point (m, n, k).

    One K step == one weight-scale group: the (bk/2, bn) packed tile is
    widened to (bk, bn) s8-range codes, dotted against the in-VMEM
    quantized x tile, and the s32 partial is corrected + dequantized into
    the persistent f32 ``acc_ref`` with THIS group's (1, 1, bn) scale row
    before the next step overwrites the tiles. Optional fusion refs
    follow ``bias`` (``_unpack_fusion_refs`` order).
    """
    del g_ref  # consumed by the index maps (per-group row gather)
    x_ref, w_ref, sx_ref, zx_ref, scale_ref, corr_ref, bias_ref = refs[:7]
    o_ref, acc_ref = refs[-2], refs[-1]
    ps_ref, bv_ref, mu_ref, rsig_ref, sh_ref, sc_ref, gate_ref, res_ref = \
        _unpack_fusion_refs(refs[7:-2], **fusions)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sx = sx_ref[0, 0]
    zx = zx_ref[0, 0]
    xf = _fusion_prologue(x_ref[...].astype(jnp.float32), ps_ref, bv_ref,
                          mu_ref, rsig_ref, sh_ref, sc_ref)
    xq = jnp.clip(jnp.round(xf / sx) + zx - half,
                  -half, half - 1).astype(jnp.int8)
    w = _unpack_w(w_ref, bk)
    partial = jax.lax.dot_general(
        xq, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    acc_ref[...] += ((partial - corr_ref[...]).astype(jnp.float32)
                     * scale_ref[...])

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[...] + bias_ref[...]
        y = _fusion_epilogue(y, bv_ref, gate_ref, res_ref)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group_k", "bm", "bn",
                                             "rows_per_batch", "out_dtype",
                                             "interpret"))
def int4_matmul_fq(x, wp, sx, zx, scale, corr, bias=None, g=None, *,
                   ps=None, nm=None, gr=None, rows_per_batch=None,
                   group_k=DEFAULT_BK, bm=DEFAULT_BM, bn=DEFAULT_BN,
                   out_dtype=jnp.float32, interpret=False):
    """y[M,N] = sum_k (q4(x_k; sx[g], zx[g]) @ s4(wp_k) - corr[g,k]) * scale[g,k].

    x: (M, K) float. wp: (Kp/2, N) int8 nibble-packed weight codes with
    Kp = nk * group_k >= K (pack-time padding; padded rows are code 0).
    sx/zx: (G, 1) f32 4-bit affine activation params. scale: (G, nk, N)
    f32 combined sx[g] * sw[kgroup, channel]; corr: (G, nk, N) i32
    per-K-group zero-point corrections. ``group_k`` is the pack-time
    K-group size and MUST equal the kernel's K tile (it is the K tile).
    g as in ``int8_matmul_fq``: python int or traced scalar.
    Optional ``ps``/``nm``/``gr``/``rows_per_batch`` fusions as
    ``int8_matmul_fq``.
    """
    M, K = x.shape
    Kp = 2 * wp.shape[0]
    N = wp.shape[1]
    assert Kp % group_k == 0 and Kp >= K, (Kp, group_k, K)
    nk = Kp // group_k
    G = scale.shape[0]
    assert sx.shape == (G, 1) and zx.shape == (G, 1), (sx.shape, zx.shape)
    assert scale.shape == (G, nk, N) and corr.shape == (G, nk, N), \
        (scale.shape, corr.shape, (G, nk, N))
    bm_, bn_ = min(bm, _ceil(M)), min(bn, _ceil(N))
    Mp, Np = _pad_to(M, bm_), _pad_to(N, bn_)

    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    if g is None:
        g = 0
    fspecs, fargs, fusions = _fusions(
        x, ps, nm, gr, rows_per_batch, has_g=True, M=M, K=K, N=N, Mp=Mp,
        Kp=Kp, Np=Np, bm_=bm_, bk_=group_k, bn_=bn_)
    x = jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(wp, ((0, 0), (0, Np - N)))
    scale = jnp.pad(scale.astype(jnp.float32), ((0, 0), (0, 0), (0, Np - N)))
    corr = jnp.pad(corr.astype(jnp.int32), ((0, 0), (0, 0), (0, Np - N)))
    bias = jnp.pad(bias.astype(jnp.float32), (0, Np - N)).reshape(1, Np)

    grid = (Mp // bm_, Np // bn_, nk)
    # Same scalar-prefetch TGQ gather as int8_matmul_fq, with one more
    # gathered axis: scale/corr are (G, nk, N) and each K step pulls its
    # own (g, k) row — the per-group weight scales ride the grid, not the
    # executable, so one compile still covers all timestep groups.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, group_k), lambda m, n, k, g: (m, k)),   # x
            pl.BlockSpec((group_k // 2, bn_),
                         lambda m, n, k, g: (k, n)),         # packed W
            _group_param((1,), lambda m, n, k, g: (g[0], 0, 0)),   # sx[g]
            _group_param((1,), lambda m, n, k, g: (g[0], 0, 0)),   # zx[g]
            _kgroup_param(bn_, lambda m, n, k, g: (g[0], k, 0, n)),  # scale
            _kgroup_param(bn_, lambda m, n, k, g: (g[0], k, 0, n)),  # corr
            pl.BlockSpec((1, bn_), lambda m, n, k, g: (0, n)),         # bias
        ] + fspecs,
        out_specs=pl.BlockSpec((bm_, bn_), lambda m, n, k, g: (m, n)),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_fq4_kernel, nk=nk, bk=group_k, half=8,
                          **fusions),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        interpret=interpret,
        name="int4_matmul_fq",
    )(jnp.asarray(g, jnp.int32).reshape(1), x, wp,
      _stack3(sx.astype(jnp.float32)), _stack3(zx.astype(jnp.float32)),
      scale.reshape(G, nk, 1, Np), corr.reshape(G, nk, 1, Np), bias, *fargs)
    return out[:M, :N]


def _mrq4_kernel(g_ref, *refs, nk: int, bk: int, half: int, **fusions):
    """Grid body for ``int4_matmul_mrq_fq`` at grid point (m, n, k).

    MRQ twin-region split as in ``int8_fused._mrq_kernel`` — ONE unpacked
    weight tile, two s32 dots — but both partials are dequantized into a
    single f32 accumulator with this K-group's per-region scale rows
    (there is no zero point, so no correction term). The fusion prologue
    runs before the sign split.
    """
    del g_ref
    x_ref, w_ref, sn_ref, sp_ref, scale_n_ref, scale_p_ref, bias_ref = \
        refs[:7]
    o_ref, acc_ref = refs[-2], refs[-1]
    ps_ref, bv_ref, mu_ref, rsig_ref, sh_ref, sc_ref, gate_ref, res_ref = \
        _unpack_fusion_refs(refs[7:-2], **fusions)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xf = _fusion_prologue(x_ref[...].astype(jnp.float32), ps_ref, bv_ref,
                          mu_ref, rsig_ref, sh_ref, sc_ref)
    neg = xf < 0
    qn = jnp.where(neg, jnp.clip(jnp.round(xf / sn_ref[0, 0]), -half, 0),
                   0).astype(jnp.int8)
    qp = jnp.where(neg, 0, jnp.clip(jnp.round(xf / sp_ref[0, 0]), 0, half - 1)
                   ).astype(jnp.int8)
    w = _unpack_w(w_ref, bk)                  # ONE weight-tile read, two dots
    dims = (((1,), (0,)), ((), ()))
    pn = jax.lax.dot_general(qn, w, dims, preferred_element_type=jnp.int32)
    pp = jax.lax.dot_general(qp, w, dims, preferred_element_type=jnp.int32)
    acc_ref[...] += (pn.astype(jnp.float32) * scale_n_ref[...]
                     + pp.astype(jnp.float32) * scale_p_ref[...])

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[...] + bias_ref[...]
        y = _fusion_epilogue(y, bv_ref, gate_ref, res_ref)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group_k", "bm", "bn",
                                             "rows_per_batch", "out_dtype",
                                             "interpret"))
def int4_matmul_mrq_fq(x, wp, s_neg, s_pos, scale_neg, scale_pos, bias=None,
                       g=None, *, ps=None, nm=None, gr=None,
                       rows_per_batch=None,
                       group_k=DEFAULT_BK, bm=DEFAULT_BM,
                       bn=DEFAULT_BN, out_dtype=jnp.float32, interpret=False):
    """Single-pass MRQ matmul on nibble-packed weights, per-K-group scales.

    y = sum_k s_neg[g]*sw[k]*(qn_k @ w_k) + s_pos[g]*sw[k]*(qp_k @ w_k)
    (+ bias). Operand layout as ``int4_matmul_fq`` but with the twin
    region steps s_neg/s_pos (G, 1) and scales scale_neg/scale_pos
    (G, nk, N). Optional ``ps``/``nm``/``gr``/``rows_per_batch`` fusions as
    ``int8_matmul_fq``.
    """
    M, K = x.shape
    Kp = 2 * wp.shape[0]
    N = wp.shape[1]
    assert Kp % group_k == 0 and Kp >= K, (Kp, group_k, K)
    nk = Kp // group_k
    G = scale_neg.shape[0]
    assert s_neg.shape == (G, 1) and s_pos.shape == (G, 1)
    assert scale_neg.shape == (G, nk, N) and scale_pos.shape == (G, nk, N)
    bm_, bn_ = min(bm, _ceil(M)), min(bn, _ceil(N))
    Mp, Np = _pad_to(M, bm_), _pad_to(N, bn_)

    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    if g is None:
        g = 0
    fspecs, fargs, fusions = _fusions(
        x, ps, nm, gr, rows_per_batch, has_g=True, M=M, K=K, N=N, Mp=Mp,
        Kp=Kp, Np=Np, bm_=bm_, bk_=group_k, bn_=bn_)
    x = jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(wp, ((0, 0), (0, Np - N)))
    scale_neg = jnp.pad(scale_neg.astype(jnp.float32),
                        ((0, 0), (0, 0), (0, Np - N)))
    scale_pos = jnp.pad(scale_pos.astype(jnp.float32),
                        ((0, 0), (0, 0), (0, Np - N)))
    bias = jnp.pad(bias.astype(jnp.float32), (0, Np - N)).reshape(1, Np)

    grid = (Mp // bm_, Np // bn_, nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, group_k), lambda m, n, k, g: (m, k)),   # x
            pl.BlockSpec((group_k // 2, bn_),
                         lambda m, n, k, g: (k, n)),         # packed W
            _group_param((1,), lambda m, n, k, g: (g[0], 0, 0)),   # s_neg[g]
            _group_param((1,), lambda m, n, k, g: (g[0], 0, 0)),   # s_pos[g]
            _kgroup_param(bn_, lambda m, n, k, g: (g[0], k, 0, n)),  # scale_n
            _kgroup_param(bn_, lambda m, n, k, g: (g[0], k, 0, n)),  # scale_p
            pl.BlockSpec((1, bn_), lambda m, n, k, g: (0, n)),         # bias
        ] + fspecs,
        out_specs=pl.BlockSpec((bm_, bn_), lambda m, n, k, g: (m, n)),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_mrq4_kernel, nk=nk, bk=group_k, half=8,
                          **fusions),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        interpret=interpret,
        name="int4_matmul_mrq_fq",
    )(jnp.asarray(g, jnp.int32).reshape(1), x, wp,
      _stack3(s_neg.astype(jnp.float32)), _stack3(s_pos.astype(jnp.float32)),
      scale_neg.reshape(G, nk, 1, Np), scale_pos.reshape(G, nk, 1, Np),
      bias, *fargs)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# vector-tgroup variants: per-ROW group indices, one packed weight stream
# ---------------------------------------------------------------------------
def _fq4_vec_kernel(gv_ref, *refs, nk: int, bk: int, half: int, **fusions):
    """Vector-tgroup body for ``int4_matmul_fq``: the (G, bn) stacks of
    THIS K step's scales/corrections stream for every group; each row
    gathers its own group's values with the exact select before
    the per-step dequantized accumulation."""
    x_ref, w_ref, sx_ref, zx_ref, scale_ref, corr_ref, bias_ref = refs[:7]
    o_ref, acc_ref = refs[-2], refs[-1]
    ps_ref, bv_ref, mu_ref, rsig_ref, sh_ref, sc_ref, gate_ref, res_ref = \
        _unpack_fusion_refs(refs[7:-2], **fusions)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gv = gv_ref[...]
    sx_row = _gather_rows(gv, sx_ref[...])               # (bm, 1)
    zx_row = _gather_rows(gv, zx_ref[...])               # (bm, 1)
    xf = _fusion_prologue(x_ref[...].astype(jnp.float32), ps_ref, bv_ref,
                          mu_ref, rsig_ref, sh_ref, sc_ref)
    xq = jnp.clip(
        jnp.round(xf / sx_row) + zx_row - half,
        -half, half - 1).astype(jnp.int8)
    w = _unpack_w(w_ref, bk)
    partial = jax.lax.dot_general(
        xq, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    scale_row = _gather_rows(gv, scale_ref[...])         # (bm, bn)
    corr_row = _gather_rows(gv, corr_ref[...])           # (bm, bn)
    acc_ref[...] += (partial - corr_row).astype(jnp.float32) * scale_row

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[...] + bias_ref[...]
        y = _fusion_epilogue(y, bv_ref, gate_ref, res_ref)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group_k", "bm", "bn",
                                             "rows_per_batch", "out_dtype",
                                             "interpret"))
def int4_matmul_fq_vec(x, wp, sx, zx, scale, corr, bias=None, gv=None, *,
                       ps=None, nm=None, gr=None, rows_per_batch=None,
                       group_k=DEFAULT_BK, bm=DEFAULT_BM, bn=DEFAULT_BN,
                       out_dtype=jnp.float32, interpret=False):
    """``int4_matmul_fq`` with a per-ROW group vector gv (M,) int32.

    The nibble-packed weight streams ONCE for the whole mixed-group
    batch; per K step the (G, bn) scale/corr slices of every group
    ride along. A constant gv is bit-identical to the scalar path (same
    elementwise ops, same f32 accumulation order). Optional ``ps``/
    ``nm``/``gr``/``rows_per_batch`` fusions as ``int8_matmul_fq``.
    """
    M, K = x.shape
    Kp = 2 * wp.shape[0]
    N = wp.shape[1]
    assert Kp % group_k == 0 and Kp >= K, (Kp, group_k, K)
    nk = Kp // group_k
    G = scale.shape[0]
    assert sx.shape == (G, 1) and zx.shape == (G, 1), (sx.shape, zx.shape)
    assert scale.shape == (G, nk, N) and corr.shape == (G, nk, N), \
        (scale.shape, corr.shape, (G, nk, N))
    bm_, bn_ = min(bm, _ceil(M)), min(bn, _ceil(N))
    Mp, Np = _pad_to(M, bm_), _pad_to(N, bn_)

    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    if gv is None:
        gv = jnp.zeros((M,), jnp.int32)
    gv = jnp.pad(jnp.asarray(gv, jnp.int32), (0, Mp - M)).reshape(Mp, 1)
    fspecs, fargs, fusions = _fusions(
        x, ps, nm, gr, rows_per_batch, has_g=False, M=M, K=K, N=N, Mp=Mp,
        Kp=Kp, Np=Np, bm_=bm_, bk_=group_k, bn_=bn_)
    x = jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(wp, ((0, 0), (0, Np - N)))
    scale = jnp.pad(scale.astype(jnp.float32), ((0, 0), (0, 0), (0, Np - N)))
    corr = jnp.pad(corr.astype(jnp.int32), ((0, 0), (0, 0), (0, Np - N)))
    bias = jnp.pad(bias.astype(jnp.float32), (0, Np - N)).reshape(1, Np)

    nbn = Np // bn_
    grid = (Mp // bm_, nbn, nk)
    out = pl.pallas_call(
        functools.partial(_fq4_vec_kernel, nk=nk, bk=group_k, half=8,
                          **fusions),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, 1), lambda m, n, k: (m, 0)),          # gv rows
            pl.BlockSpec((bm_, group_k), lambda m, n, k: (m, k)),    # x
            pl.BlockSpec((group_k // 2, bn_),
                         lambda m, n, k: (k, n)),          # packed W
            pl.BlockSpec((G, 1), lambda m, n, k: (0, 0)),            # sx stack
            pl.BlockSpec((G, 1), lambda m, n, k: (0, 0)),            # zx stack
            _kstep_stack(G, bn_, nbn),                     # scale[:, k]
            _kstep_stack(G, bn_, nbn),                     # corr[:, k]
            pl.BlockSpec((1, bn_), lambda m, n, k: (0, n)),          # bias
        ] + fspecs,
        out_specs=pl.BlockSpec((bm_, bn_), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        interpret=interpret,
        name="int4_matmul_fq_vec",
    )(gv, x, wp, sx.astype(jnp.float32), zx.astype(jnp.float32),
      scale.reshape(G, nk * Np), corr.reshape(G, nk * Np), bias, *fargs)
    return out[:M, :N]


def _mrq4_vec_kernel(gv_ref, *refs, nk: int, bk: int, half: int, **fusions):
    """Vector-tgroup body for ``int4_matmul_mrq_fq``: per-row twin-region
    steps, ONE unpacked weight tile, per-row per-K-group region scales."""
    x_ref, w_ref, sn_ref, sp_ref, scale_n_ref, scale_p_ref, bias_ref = \
        refs[:7]
    o_ref, acc_ref = refs[-2], refs[-1]
    ps_ref, bv_ref, mu_ref, rsig_ref, sh_ref, sc_ref, gate_ref, res_ref = \
        _unpack_fusion_refs(refs[7:-2], **fusions)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gv = gv_ref[...]
    sn_row = _gather_rows(gv, sn_ref[...])               # (bm, 1)
    sp_row = _gather_rows(gv, sp_ref[...])               # (bm, 1)
    xf = _fusion_prologue(x_ref[...].astype(jnp.float32), ps_ref, bv_ref,
                          mu_ref, rsig_ref, sh_ref, sc_ref)
    neg = xf < 0
    qn = jnp.where(neg, jnp.clip(jnp.round(xf / sn_row), -half, 0),
                   0).astype(jnp.int8)
    qp = jnp.where(neg, 0, jnp.clip(jnp.round(xf / sp_row), 0, half - 1)
                   ).astype(jnp.int8)
    w = _unpack_w(w_ref, bk)                  # ONE weight-tile read, two dots
    dims = (((1,), (0,)), ((), ()))
    pn = jax.lax.dot_general(qn, w, dims, preferred_element_type=jnp.int32)
    pp = jax.lax.dot_general(qp, w, dims, preferred_element_type=jnp.int32)
    scale_n_row = _gather_rows(gv, scale_n_ref[...])     # (bm, bn)
    scale_p_row = _gather_rows(gv, scale_p_ref[...])     # (bm, bn)
    acc_ref[...] += (pn.astype(jnp.float32) * scale_n_row
                     + pp.astype(jnp.float32) * scale_p_row)

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[...] + bias_ref[...]
        y = _fusion_epilogue(y, bv_ref, gate_ref, res_ref)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group_k", "bm", "bn",
                                             "rows_per_batch", "out_dtype",
                                             "interpret"))
def int4_matmul_mrq_fq_vec(x, wp, s_neg, s_pos, scale_neg, scale_pos,
                           bias=None, gv=None, *, ps=None, nm=None, gr=None,
                           rows_per_batch=None, group_k=DEFAULT_BK,
                           bm=DEFAULT_BM, bn=DEFAULT_BN,
                           out_dtype=jnp.float32, interpret=False):
    """``int4_matmul_mrq_fq`` with a per-ROW group vector gv (M,) int32
    (one-weight-read contract as ``int4_matmul_fq_vec``)."""
    M, K = x.shape
    Kp = 2 * wp.shape[0]
    N = wp.shape[1]
    assert Kp % group_k == 0 and Kp >= K, (Kp, group_k, K)
    nk = Kp // group_k
    G = scale_neg.shape[0]
    assert s_neg.shape == (G, 1) and s_pos.shape == (G, 1)
    assert scale_neg.shape == (G, nk, N) and scale_pos.shape == (G, nk, N)
    bm_, bn_ = min(bm, _ceil(M)), min(bn, _ceil(N))
    Mp, Np = _pad_to(M, bm_), _pad_to(N, bn_)

    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    if gv is None:
        gv = jnp.zeros((M,), jnp.int32)
    gv = jnp.pad(jnp.asarray(gv, jnp.int32), (0, Mp - M)).reshape(Mp, 1)
    fspecs, fargs, fusions = _fusions(
        x, ps, nm, gr, rows_per_batch, has_g=False, M=M, K=K, N=N, Mp=Mp,
        Kp=Kp, Np=Np, bm_=bm_, bk_=group_k, bn_=bn_)
    x = jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(wp, ((0, 0), (0, Np - N)))
    scale_neg = jnp.pad(scale_neg.astype(jnp.float32),
                        ((0, 0), (0, 0), (0, Np - N)))
    scale_pos = jnp.pad(scale_pos.astype(jnp.float32),
                        ((0, 0), (0, 0), (0, Np - N)))
    bias = jnp.pad(bias.astype(jnp.float32), (0, Np - N)).reshape(1, Np)

    nbn = Np // bn_
    grid = (Mp // bm_, nbn, nk)
    out = pl.pallas_call(
        functools.partial(_mrq4_vec_kernel, nk=nk, bk=group_k, half=8,
                          **fusions),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, 1), lambda m, n, k: (m, 0)),          # gv rows
            pl.BlockSpec((bm_, group_k), lambda m, n, k: (m, k)),    # x
            pl.BlockSpec((group_k // 2, bn_),
                         lambda m, n, k: (k, n)),          # packed W
            pl.BlockSpec((G, 1), lambda m, n, k: (0, 0)),         # s_neg stack
            pl.BlockSpec((G, 1), lambda m, n, k: (0, 0)),         # s_pos stack
            _kstep_stack(G, bn_, nbn),                     # scale_neg[:, k]
            _kstep_stack(G, bn_, nbn),                     # scale_pos[:, k]
            pl.BlockSpec((1, bn_), lambda m, n, k: (0, n)),          # bias
        ] + fspecs,
        out_specs=pl.BlockSpec((bm_, bn_), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        interpret=interpret,
        name="int4_matmul_mrq_fq_vec",
    )(gv, x, wp, s_neg.astype(jnp.float32), s_pos.astype(jnp.float32),
      scale_neg.reshape(G, nk * Np), scale_pos.reshape(G, nk * Np), bias,
      *fargs)
    return out[:M, :N]
