"""Batched int8 matmul Pallas kernels for the attention hot path.

Attention is the memory-bound quadratic half of a DiT block and — before
this module — the last full-precision island in the W8A8 serving path:
QK^T and P·V ran as fp einsums and the post-softmax MRQ quantizer
dequantized the probabilities back to fp before P·V. Two kernels close
the gap:

``int8_bmm_qk``
    scores[b] = (q8[b] @ k8[b]^T) * (s_q[g] * s_k[g] * alpha). Both
    operands are ACTIVATIONS quantized with per-tensor SYMMETRIC steps
    in the fused prologue (fp tile -> s8 codes in VMEM, no zero point,
    so no correction term in the batched epilogue). ``alpha`` — the
    softmax 1/sqrt(hd) — is folded into the stacked scale row, so the
    dequantized scores are written to HBM exactly once.

``int8_bmm_pv``
    out[b] = (P[b] @ v8[b]) with P consumed DIRECTLY as the
    region-signed int8 codes emitted by ``softmax_mrq_codes`` (see
    ``kernels/softmax_mrq.py``): code c >= 0 is a region-1 (fine step
    s1) prob code, c < 0 stores the NEGATED region-2 (coarse step
    s2 = 1/2^{k-1}) code. The kernel splits the code tile into the two
    non-negative region magnitudes in VMEM and feeds TWO s32
    accumulators against ONE read of the v tile (quantized in the same
    prologue style), mirroring ``int8_matmul_mrq_fq``'s dual-region
    structure; the epilogue recombines with the per-region scales
    s1[g]*s_v[g] and s2*s_v[g]. The probabilities therefore never exist
    in HBM as floats — codes out of the softmax kernel, codes into P·V.

TGQ exactly as in ``int8_fused``: every activation-side parameter is
stacked along a leading (G,) group axis and the timestep group ``g`` —
a traced scalar inside the ``ddpm_sample`` lax.scan — is
scalar-prefetched; the per-group row is gathered by the BlockSpec index
maps, so the whole sampling loop stays ONE compiled executable.

Tiling: grid (B, M/bm, N/bn, K/bk) with the contraction innermost and a
leading batch axis (one (b, h) attention matrix per batch step);
(bm, bn) s32 accumulator(s) in VMEM scratch. Non-aligned shapes are
zero-padded; padded contraction columns quantize to code 0 and
contribute nothing.

GQA: the q-side batch may be a multiple of the k/v-side batch (G query
groups per kv head). The kernels gather the SHARED kv tile with a
``b // rep`` batch index map instead of asking the caller to materialize
G HBM copies of k/v — each kv head streams from HBM once per group
schedule, and q-side batches that share a kv head reuse the same tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int8_matmul import DEFAULT_BK, DEFAULT_BM, DEFAULT_BN, \
    _ceil, _group_param, _pad_to, _stack3


def _sym_levels(x, scale, half):
    """fp tile -> symmetric integer levels as f32 (code range, no -half)."""
    return jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                    -(half - 1), half - 1)


def _sym_codes(x, scale, half):
    """fp tile -> symmetric s8 codes in VMEM (weight code range, no -128)."""
    return _sym_levels(x, scale, half).astype(jnp.int8)


def _qk_kernel(g_ref, q_ref, k_ref, sq_ref, sk_ref, scale_ref, o_ref,
               acc_ref, *, nk: int, half: int):
    """Grid body for ``int8_bmm_qk`` at grid point (b, m, n, d).

    Refs arrive as VMEM tiles gathered by the index maps: q (1, bm, bd)
    fp, k (1, bn, bd) fp, and the group-``g`` rows of the stacked (G, 1)
    params. ``acc_ref`` is a persistent (bm, bn) s32 scratch zeroed at
    d == 0 and epilogued at d == nk - 1 (d innermost). ``g_ref`` feeds
    the index maps only.
    """
    del g_ref
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q8 = _sym_codes(q_ref[0], sq_ref[0, 0], half)
    k8 = _sym_codes(k_ref[0], sk_ref[0, 0], half)
    acc_ref[...] += jax.lax.dot_general(
        q8, k8, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(d == nk - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...].astype(jnp.float32)
                    * scale_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "out_dtype", "interpret"))
def int8_bmm_qk(q, k, s_q, s_k, scale, g=None, *, bits=8, bm=DEFAULT_BM,
                bn=DEFAULT_BN, bk=DEFAULT_BK, out_dtype=jnp.float32,
                interpret=False):
    """scores[B,M,N] = (q8 @ k8^T) * scale[g], q8/k8 symmetric s8 codes.

    q: (B, M, D) float, k: (Bk, N, D) float (contraction over D = head
    dim) with B = rep * Bk — the GQA layout where ``rep`` query-group
    batches share each kv head; the kernel gathers the shared k tile via
    a ``b // rep`` index map (no materialized copies). s_q/s_k: (G, 1)
    f32 per-tensor symmetric steps; scale: (G, 1) f32 combined
    s_q[g]*s_k[g]*alpha (alpha = the softmax scale, folded by the
    caller). g is the TGQ group — python int or traced scalar
    (scalar-prefetched, gathered by the index maps; no retrace across
    groups).
    """
    B, M, D = q.shape
    B2, N, D2 = k.shape
    assert D == D2 and B % B2 == 0, (q.shape, k.shape)
    rep = B // B2
    G = s_q.shape[0]
    assert s_k.shape == (G, 1) and scale.shape == (G, 1), \
        (s_q.shape, s_k.shape, scale.shape)
    half = 2 ** (bits - 1)
    bm_, bn_, bk_ = min(bm, _ceil(M)), min(bn, _ceil(N)), min(bk, _ceil(D))
    Mp, Np, Dp = _pad_to(M, bm_), _pad_to(N, bn_), _pad_to(D, bk_)

    if g is None:
        g = 0
    q = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, Mp - M), (0, Dp - D)))
    k = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, Np - N), (0, Dp - D)))

    nk = Dp // bk_
    grid = (B, Mp // bm_, Np // bn_, nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm_, bk_), lambda b, m, n, d, g: (b, m, d)),
            pl.BlockSpec((1, bn_, bk_),
                         lambda b, m, n, d, g: (b // rep, n, d)),  # shared kv
            _group_param((1,), lambda b, m, n, d, g: (g[0], 0, 0)),   # s_q[g]
            _group_param((1,), lambda b, m, n, d, g: (g[0], 0, 0)),   # s_k[g]
            _group_param((1,), lambda b, m, n, d, g: (g[0], 0, 0)),   # scale
        ],
        out_specs=pl.BlockSpec((1, bm_, bn_), lambda b, m, n, d, g: (b, m, n)),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_qk_kernel, nk=nk, half=half),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Mp, Np), out_dtype),
        interpret=interpret,
        name="int8_bmm_qk",
    )(jnp.asarray(g, jnp.int32).reshape(1), q, k,
      _stack3(s_q.astype(jnp.float32)), _stack3(s_k.astype(jnp.float32)),
      _stack3(scale.astype(jnp.float32)))
    return out[:, :M, :N]


def _pv_kernel(g_ref, c_ref, v_ref, sv_ref, scale1_ref, scale2_ref, o_ref,
               acc1_ref, acc2_ref, *, nk: int, half: int):
    """Grid body for ``int8_bmm_pv`` at grid point (b, m, d, n).

    The prob-code tile (1, bm, bn) is split by SIGN into the two region
    tiles (region 1: max(c, 0), region 2: min(c, 0) — disjoint support
    by construction of the encoding) feeding dual s32 accumulators
    against a single read of the v tile, which is quantized in the
    prologue with the group-``g`` symmetric step. Both stay s8 MXU
    operands: region 2 keeps its negated codes (magnitude up to 2^{k-1},
    one past the s8 maximum) and meets the negated v codes, which the
    symmetric code range keeps inside s8.
    Epilogue recombines with the per-region combined scales. n (the Skv
    contraction) is innermost.
    """
    del g_ref
    n = pl.program_id(3)

    @pl.when(n == 0)
    def _init():
        acc1_ref[...] = jnp.zeros_like(acc1_ref)
        acc2_ref[...] = jnp.zeros_like(acc2_ref)

    c = c_ref[0].astype(jnp.int32)            # the VPU has no s8 min/max
    c1 = jnp.maximum(c, 0).astype(jnp.int8)   # region-1 codes [0, half-1]
    c2n = jnp.minimum(c, 0).astype(jnp.int8)  # -(region-2 codes) [-half, 0]
    vq = _sym_levels(v_ref[0], sv_ref[0, 0], half)
    dims = (((1,), (0,)), ((), ()))           # ONE v-tile read, two dots
    acc1_ref[...] += jax.lax.dot_general(c1, vq.astype(jnp.int8), dims,
                                         preferred_element_type=jnp.int32)
    acc2_ref[...] += jax.lax.dot_general(c2n, (-vq).astype(jnp.int8), dims,
                                         preferred_element_type=jnp.int32)

    @pl.when(n == nk - 1)
    def _epilogue():
        y = (acc1_ref[...].astype(jnp.float32) * scale1_ref[0, 0]
             + acc2_ref[...].astype(jnp.float32) * scale2_ref[0, 0])
        o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "out_dtype", "interpret"))
def int8_bmm_pv(codes, v, s_v, scale1, scale2, g=None, *, bits=8,
                bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK,
                out_dtype=jnp.float32, interpret=False):
    """out[B,M,D] = scale1[g]*(c1 @ v8) + scale2[g]*(c2 @ v8).

    codes: (B, M, N) int8 region-signed MRQ prob codes (c >= 0: region-1
    code, c < 0: negated region-2 code — the ``softmax_mrq_codes``
    output); v: (Bv, N, D) float with B = rep * Bv (GQA: ``rep``
    query-group batches share each v head, gathered via a ``b // rep``
    index map), quantized in-kernel with s_v[g].
    s_v: (G, 1) f32; scale1/scale2: (G, 1) f32 combined region*value
    scales (s1[g]*s_v[g] and s2*s_v[g], s2 = 1/2^{k-1}).
    """
    B, M, N = codes.shape
    B2, N2, D = v.shape
    assert N == N2 and B % B2 == 0, (codes.shape, v.shape)
    rep = B // B2
    G = s_v.shape[0]
    assert scale1.shape == (G, 1) and scale2.shape == (G, 1), \
        (s_v.shape, scale1.shape, scale2.shape)
    half = 2 ** (bits - 1)
    bm_, bd_, bn_ = min(bm, _ceil(M)), min(bn, _ceil(D)), min(bk, _ceil(N))
    Mp, Dp, Np = _pad_to(M, bm_), _pad_to(D, bd_), _pad_to(N, bn_)

    if g is None:
        g = 0
    codes = jnp.pad(codes, ((0, 0), (0, Mp - M), (0, Np - N)))
    v = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, Np - N), (0, Dp - D)))

    nk = Np // bn_
    grid = (B, Mp // bm_, Dp // bd_, nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm_, bn_), lambda b, m, d, n, g: (b, m, n)),
            pl.BlockSpec((1, bn_, bd_),
                         lambda b, m, d, n, g: (b // rep, n, d)),  # shared kv
            _group_param((1,), lambda b, m, d, n, g: (g[0], 0, 0)),  # s_v[g]
            _group_param((1,), lambda b, m, d, n, g: (g[0], 0, 0)),  # scale1
            _group_param((1,), lambda b, m, d, n, g: (g[0], 0, 0)),  # scale2
        ],
        out_specs=pl.BlockSpec((1, bm_, bd_), lambda b, m, d, n, g: (b, m, d)),
        scratch_shapes=[pltpu.VMEM((bm_, bd_), jnp.int32),
                        pltpu.VMEM((bm_, bd_), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_pv_kernel, nk=nk, half=half),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Mp, Dp), out_dtype),
        interpret=interpret,
        name="int8_bmm_pv",
    )(jnp.asarray(g, jnp.int32).reshape(1), codes, v,
      _stack3(s_v.astype(jnp.float32)), _stack3(scale1.astype(jnp.float32)),
      _stack3(scale2.astype(jnp.float32)))
    return out[:, :M, :D]


# ---------------------------------------------------------------------------
# vector-tgroup variants: per-BATCH-ROW groups via a (B,) prefetch vector
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "out_dtype", "interpret"))
def int8_bmm_qk_vec(q, k, s_q, s_k, scale, gv=None, *, bits=8, bm=DEFAULT_BM,
                    bn=DEFAULT_BN, bk=DEFAULT_BK, out_dtype=jnp.float32,
                    interpret=False):
    """``int8_bmm_qk`` with a per-batch-row group vector gv (B,) int32.

    The kernel BODY (``_qk_kernel``) is unchanged; the batch axis leads
    the grid, so the whole (B,) vector rides as the prefetched array and
    each param index map picks ``(g[b], 0)`` — batch row b's params
    stream per grid row, k/v sharing (GQA ``b // rep``) untouched. A
    constant gv is bit-identical to the scalar path.
    """
    B, M, D = q.shape
    B2, N, D2 = k.shape
    assert D == D2 and B % B2 == 0, (q.shape, k.shape)
    rep = B // B2
    G = s_q.shape[0]
    assert s_k.shape == (G, 1) and scale.shape == (G, 1), \
        (s_q.shape, s_k.shape, scale.shape)
    half = 2 ** (bits - 1)
    bm_, bn_, bk_ = min(bm, _ceil(M)), min(bn, _ceil(N)), min(bk, _ceil(D))
    Mp, Np, Dp = _pad_to(M, bm_), _pad_to(N, bn_), _pad_to(D, bk_)

    gv = (jnp.zeros((B,), jnp.int32) if gv is None
          else jnp.asarray(gv, jnp.int32).reshape(B))
    q = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, Mp - M), (0, Dp - D)))
    k = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, Np - N), (0, Dp - D)))

    nk = Dp // bk_
    grid = (B, Mp // bm_, Np // bn_, nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm_, bk_), lambda b, m, n, d, g: (b, m, d)),
            pl.BlockSpec((1, bn_, bk_),
                         lambda b, m, n, d, g: (b // rep, n, d)),  # shared kv
            _group_param((1,), lambda b, m, n, d, g: (g[b], 0, 0)),  # s_q[g_b]
            _group_param((1,), lambda b, m, n, d, g: (g[b], 0, 0)),  # s_k[g_b]
            _group_param((1,), lambda b, m, n, d, g: (g[b], 0, 0)),  # scale
        ],
        out_specs=pl.BlockSpec((1, bm_, bn_), lambda b, m, n, d, g: (b, m, n)),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_qk_kernel, nk=nk, half=half),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Mp, Np), out_dtype),
        interpret=interpret,
        name="int8_bmm_qk_vec",
    )(gv, q, k, _stack3(s_q.astype(jnp.float32)),
      _stack3(s_k.astype(jnp.float32)), _stack3(scale.astype(jnp.float32)))
    return out[:, :M, :N]


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "out_dtype", "interpret"))
def int8_bmm_pv_vec(codes, v, s_v, scale1, scale2, gv=None, *, bits=8,
                    bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK,
                    out_dtype=jnp.float32, interpret=False):
    """``int8_bmm_pv`` with a per-batch-row group vector gv (B,) int32
    (same contract as ``int8_bmm_qk_vec``)."""
    B, M, N = codes.shape
    B2, N2, D = v.shape
    assert N == N2 and B % B2 == 0, (codes.shape, v.shape)
    rep = B // B2
    G = s_v.shape[0]
    assert scale1.shape == (G, 1) and scale2.shape == (G, 1), \
        (s_v.shape, scale1.shape, scale2.shape)
    half = 2 ** (bits - 1)
    bm_, bd_, bn_ = min(bm, _ceil(M)), min(bn, _ceil(D)), min(bk, _ceil(N))
    Mp, Dp, Np = _pad_to(M, bm_), _pad_to(D, bd_), _pad_to(N, bn_)

    gv = (jnp.zeros((B,), jnp.int32) if gv is None
          else jnp.asarray(gv, jnp.int32).reshape(B))
    codes = jnp.pad(codes, ((0, 0), (0, Mp - M), (0, Np - N)))
    v = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, Np - N), (0, Dp - D)))

    nk = Np // bn_
    grid = (B, Mp // bm_, Dp // bd_, nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm_, bn_), lambda b, m, d, n, g: (b, m, n)),
            pl.BlockSpec((1, bn_, bd_),
                         lambda b, m, d, n, g: (b // rep, n, d)),  # shared kv
            _group_param((1,), lambda b, m, d, n, g: (g[b], 0, 0)),  # s_v[g_b]
            _group_param((1,), lambda b, m, d, n, g: (g[b], 0, 0)),  # scale1
            _group_param((1,), lambda b, m, d, n, g: (g[b], 0, 0)),  # scale2
        ],
        out_specs=pl.BlockSpec((1, bm_, bd_), lambda b, m, d, n, g: (b, m, d)),
        scratch_shapes=[pltpu.VMEM((bm_, bd_), jnp.int32),
                        pltpu.VMEM((bm_, bd_), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_pv_kernel, nk=nk, half=half),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Mp, Dp), out_dtype),
        interpret=interpret,
        name="int8_bmm_pv_vec",
    )(gv, codes, v, _stack3(s_v.astype(jnp.float32)),
      _stack3(scale1.astype(jnp.float32)), _stack3(scale2.astype(jnp.float32)))
    return out[:, :M, :D]
