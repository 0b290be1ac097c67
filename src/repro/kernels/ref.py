"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` computes exactly what the corresponding kernel must produce;
tests sweep shapes/dtypes and assert_allclose kernel-vs-ref.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantizers import mrq_signed_qdq, mrq_softmax_qdq


def quantize_int8_ref(x, scale, zero, bits: int = 8):
    """Uniform affine codes: q = clip(round(x/s)+z-h, -h, h-1), h=2^{b-1}.

    Codes are stored SIGNED (two's complement, offset by half the code
    range from the unsigned convention) so the MXU s8 path applies; the
    effective zero point becomes (z - 2^{b-1}). Sub-byte widths keep the
    same convention inside int8 storage (6-bit: [-32, 31]; 4-bit:
    [-8, 7], nibble-packed downstream)."""
    half = 2 ** (bits - 1)
    q = jnp.clip(jnp.round(x / scale) + zero - half, -half, half - 1)
    return q.astype(jnp.int8)


def int8_matmul_ref(xq, wq, scale, corr, bias=None, out_dtype=jnp.float32):
    """y = (xq @ wq - corr) * scale (+ bias).

    xq: (M,K) int8; wq: (K,N) int8; scale: (N,) f32 combined s_x*s_w;
    corr: (N,) int32 zero-point correction z_x_eff * colsum(wq).
    """
    acc = jax.lax.dot_general(
        xq.astype(jnp.int32), wq.astype(jnp.int32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    y = (acc - corr[None, :]).astype(jnp.float32) * scale[None, :]
    if bias is not None:
        y = y + bias[None, :].astype(jnp.float32)
    return y.astype(out_dtype)


def int8_matmul_fq_ref(x, wq, sx, zx, scale, corr, bias=None, g=0,
                       bits: int = 8, out_dtype=jnp.float32):
    """Fused-quantize matmul oracle: quantize x with group-g params, then
    the int8 matmul + dequant epilogue.

    x: (M,K) float; wq: (K,N) int8; sx/zx: (G,1) f32; scale: (G,N) f32;
    corr: (G,N) i32; g: group index (int or traced scalar).
    """
    sx_g = jnp.take(sx, g, axis=0)[0]
    zx_g = jnp.take(zx, g, axis=0)[0]
    xq = quantize_int8_ref(x.astype(jnp.float32), sx_g, zx_g, bits)
    return int8_matmul_ref(xq, wq, jnp.take(scale, g, axis=0),
                           jnp.take(corr, g, axis=0), bias=bias,
                           out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# packed-int4 linears (per-K-group weight scales, f32 group accumulation)
# ---------------------------------------------------------------------------
def int4_matmul_fq_ref(x, wp, sx, zx, scale, corr, bias=None, g=0,
                       group_k: int = 256, out_dtype=jnp.float32):
    """Oracle for ``int4_matmul_fq``: unpack nibbles, quantize x at 4
    bits with the group-g affine params, then replay the kernel's
    GROUP-ORDERED f32 accumulation — each K group's s32 partial is
    corrected and dequantized with its own (nk, N) scale row before the
    next group is added, matching the kernel's per-K-step dequant.

    wp: (Kp/2, N) int8 packed; scale: (G, nk, N) f32; corr: (G, nk, N)
    i32 with nk = Kp / group_k.
    """
    from repro.kernels.int4_packed import unpack_int4
    M, K = x.shape
    Kp, N = 2 * wp.shape[0], wp.shape[1]
    nk = Kp // group_k
    sx_g = jnp.take(sx, g, axis=0)[0]
    zx_g = jnp.take(zx, g, axis=0)[0]
    xq = quantize_int8_ref(x.astype(jnp.float32), sx_g, zx_g, bits=4)
    xq = jnp.pad(xq, ((0, 0), (0, Kp - K))).astype(jnp.int32)
    w = unpack_int4(wp).astype(jnp.int32)
    scale_g = jnp.take(scale, g, axis=0)
    corr_g = jnp.take(corr, g, axis=0)
    acc = jnp.zeros((M, N), jnp.float32)
    for kg in range(nk):
        sl = slice(kg * group_k, (kg + 1) * group_k)
        partial = jax.lax.dot_general(
            xq[:, sl], w[sl], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = acc + ((partial - corr_g[kg][None, :]).astype(jnp.float32)
                     * scale_g[kg][None, :])
    if bias is not None:
        acc = acc + bias[None, :].astype(jnp.float32)
    return acc.astype(out_dtype)


def int4_matmul_mrq_fq_ref(x, wp, s_neg, s_pos, scale_neg, scale_pos,
                           bias=None, g=0, group_k: int = 256,
                           out_dtype=jnp.float32):
    """Oracle for ``int4_matmul_mrq_fq``: 4-bit twin-region codes
    (disjoint support by sign), nibble-unpacked weights, and the kernel's
    group-ordered f32 accumulation with per-region per-K-group scales.
    """
    from repro.kernels.int4_packed import unpack_int4
    half = 8
    M, K = x.shape
    Kp, N = 2 * wp.shape[0], wp.shape[1]
    nk = Kp // group_k
    xf = x.astype(jnp.float32)
    sn = jnp.take(s_neg, g, axis=0)[0]
    sp = jnp.take(s_pos, g, axis=0)[0]
    neg = xf < 0
    qn = jnp.where(neg, jnp.clip(jnp.round(xf / sn), -half, 0), 0
                   ).astype(jnp.int32)
    qp = jnp.where(neg, 0, jnp.clip(jnp.round(xf / sp), 0, half - 1)
                   ).astype(jnp.int32)
    qn = jnp.pad(qn, ((0, 0), (0, Kp - K)))
    qp = jnp.pad(qp, ((0, 0), (0, Kp - K)))
    w = unpack_int4(wp).astype(jnp.int32)
    sn_g = jnp.take(scale_neg, g, axis=0)
    sp_g = jnp.take(scale_pos, g, axis=0)
    dims = (((1,), (0,)), ((), ()))
    acc = jnp.zeros((M, N), jnp.float32)
    for kg in range(nk):
        sl = slice(kg * group_k, (kg + 1) * group_k)
        pn = jax.lax.dot_general(qn[:, sl], w[sl], dims,
                                 preferred_element_type=jnp.int32)
        pp = jax.lax.dot_general(qp[:, sl], w[sl], dims,
                                 preferred_element_type=jnp.int32)
        acc = acc + (pn.astype(jnp.float32) * sn_g[kg][None, :]
                     + pp.astype(jnp.float32) * sp_g[kg][None, :])
    if bias is not None:
        acc = acc + bias[None, :].astype(jnp.float32)
    return acc.astype(out_dtype)


def int8_matmul_mrq_fq_ref(x, wq, s_neg, s_pos, scale_neg, scale_pos,
                           bias=None, g=0, bits: int = 8,
                           out_dtype=jnp.float32):
    """Single-pass MRQ matmul oracle: two-region codes (disjoint support,
    selected by sign), one logical W traversal, per-region dequant.

    x: (M,K) float; wq: (K,N) int8; s_neg/s_pos: (G,1) f32 region steps;
    scale_neg/scale_pos: (G,N) f32 combined region*weight scales.
    """
    half = 2 ** (bits - 1)
    xf = x.astype(jnp.float32)
    sn = jnp.take(s_neg, g, axis=0)[0]
    sp = jnp.take(s_pos, g, axis=0)[0]
    neg = xf < 0
    qn = jnp.where(neg, jnp.clip(jnp.round(xf / sn), -half, 0), 0
                   ).astype(jnp.int8)
    qp = jnp.where(neg, 0, jnp.clip(jnp.round(xf / sp), 0, half - 1)
                   ).astype(jnp.int8)
    dims = (((1,), (0,)), ((), ()))
    acc_n = jax.lax.dot_general(qn.astype(jnp.int32), wq.astype(jnp.int32),
                                dims, preferred_element_type=jnp.int32)
    acc_p = jax.lax.dot_general(qp.astype(jnp.int32), wq.astype(jnp.int32),
                                dims, preferred_element_type=jnp.int32)
    y = (acc_n.astype(jnp.float32) * jnp.take(scale_neg, g, axis=0)[None]
         + acc_p.astype(jnp.float32) * jnp.take(scale_pos, g, axis=0)[None])
    if bias is not None:
        y = y + bias[None, :].astype(jnp.float32)
    return y.astype(out_dtype)


# ---------------------------------------------------------------------------
# prologue/epilogue fusion oracles (adaLN norm-modulate, channel-balance
# prescale, gate+residual) — see ``int8_fused``'s fusion contract
# ---------------------------------------------------------------------------
def fused_prologue_ref(x, nm=None, ps=None, bv=None, eps: float = 1e-6):
    """What the kernels' VMEM prologue computes before quantizing.

    ``nm = (shift, scale)`` per-batch (B, K) adaLN rows with ``bv`` the
    (M,) row->batch map: non-affine layernorm (mean, var, ``rsqrt(var +
    eps)``) then ``y * (1 + scale[bv]) + shift[bv]``. ``ps`` is the (K,)
    channel-balance vector, applied as a DIVIDE after the modulate (the
    fake-quant ``_q_in`` order). x: (M, K) rows."""
    x = x.astype(jnp.float32)
    if nm is not None:
        sh, sc = nm
        bv = jnp.asarray(bv, jnp.int32)
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        x = (x - mu) * jax.lax.rsqrt(var + eps)
        x = (x * (1.0 + jnp.take(jnp.asarray(sc, jnp.float32), bv, axis=0))
             + jnp.take(jnp.asarray(sh, jnp.float32), bv, axis=0))
    if ps is not None:
        x = x / jnp.asarray(ps, jnp.float32)[None, :]
    return x


def fused_epilogue_ref(y, gr=None, bv=None):
    """What the kernels' dequant epilogue computes after the bias add:
    ``gr = (gate, residual)`` with gate (B, N) rows, residual (M, N), and
    ``bv`` the (M,) row->batch map — ``residual + gate[bv] * y``."""
    if gr is not None:
        gate, res = gr
        bv = jnp.asarray(bv, jnp.int32)
        y = (jnp.asarray(res, jnp.float32)
             + jnp.take(jnp.asarray(gate, jnp.float32), bv, axis=0) * y)
    return y


def int8_matmul_fq_fused_ref(x, wq, sx, zx, scale, corr, bias=None, g=0,
                             ps=None, nm=None, gr=None, bv=None,
                             bits: int = 8, out_dtype=jnp.float32):
    """``int8_matmul_fq`` with fusions: prologue -> fq oracle -> epilogue."""
    xf = fused_prologue_ref(x, nm=nm, ps=ps, bv=bv)
    y = int8_matmul_fq_ref(xf, wq, sx, zx, scale, corr, bias=bias, g=g,
                           bits=bits)
    return fused_epilogue_ref(y, gr=gr, bv=bv).astype(out_dtype)


def int8_matmul_mrq_fq_fused_ref(x, wq, s_neg, s_pos, scale_neg, scale_pos,
                                 bias=None, g=0, ps=None, nm=None, gr=None,
                                 bv=None, bits: int = 8,
                                 out_dtype=jnp.float32):
    """``int8_matmul_mrq_fq`` with fusions (prologue before the sign
    split — the balance vector is positive, so regions are unchanged)."""
    xf = fused_prologue_ref(x, nm=nm, ps=ps, bv=bv)
    y = int8_matmul_mrq_fq_ref(xf, wq, s_neg, s_pos, scale_neg, scale_pos,
                               bias=bias, g=g, bits=bits)
    return fused_epilogue_ref(y, gr=gr, bv=bv).astype(out_dtype)


def int4_matmul_fq_fused_ref(x, wp, sx, zx, scale, corr, bias=None, g=0,
                             ps=None, nm=None, gr=None, bv=None,
                             group_k: int = 256, out_dtype=jnp.float32):
    """``int4_matmul_fq`` with fusions."""
    xf = fused_prologue_ref(x, nm=nm, ps=ps, bv=bv)
    y = int4_matmul_fq_ref(xf, wp, sx, zx, scale, corr, bias=bias, g=g,
                           group_k=group_k)
    return fused_epilogue_ref(y, gr=gr, bv=bv).astype(out_dtype)


def int4_matmul_mrq_fq_fused_ref(x, wp, s_neg, s_pos, scale_neg, scale_pos,
                                 bias=None, g=0, ps=None, nm=None, gr=None,
                                 bv=None, group_k: int = 256,
                                 out_dtype=jnp.float32):
    """``int4_matmul_mrq_fq`` with fusions."""
    xf = fused_prologue_ref(x, nm=nm, ps=ps, bv=bv)
    y = int4_matmul_mrq_fq_ref(xf, wp, s_neg, s_pos, scale_neg, scale_pos,
                               bias=bias, g=g, group_k=group_k)
    return fused_epilogue_ref(y, gr=gr, bv=bv).astype(out_dtype)


def int8_matmul_fq_vec_fused_ref(x, wq, sx, zx, scale, corr, bias=None,
                                 gv=None, ps=None, nm=None, gr=None, bv=None,
                                 bits: int = 8, out_dtype=jnp.float32):
    """Vector-tgroup sibling of ``int8_matmul_fq_fused_ref``."""
    xf = fused_prologue_ref(x, nm=nm, ps=ps, bv=bv)
    y = int8_matmul_fq_vec_ref(xf, wq, sx, zx, scale, corr, bias=bias,
                               gv=gv, bits=bits)
    return fused_epilogue_ref(y, gr=gr, bv=bv).astype(out_dtype)


def int8_matmul_mrq_fq_vec_fused_ref(x, wq, s_neg, s_pos, scale_neg,
                                     scale_pos, bias=None, gv=None, ps=None,
                                     nm=None, gr=None, bv=None,
                                     bits: int = 8, out_dtype=jnp.float32):
    """Vector-tgroup sibling of ``int8_matmul_mrq_fq_fused_ref``."""
    xf = fused_prologue_ref(x, nm=nm, ps=ps, bv=bv)
    y = int8_matmul_mrq_fq_vec_ref(xf, wq, s_neg, s_pos, scale_neg,
                                   scale_pos, bias=bias, gv=gv, bits=bits)
    return fused_epilogue_ref(y, gr=gr, bv=bv).astype(out_dtype)


def int4_matmul_fq_vec_fused_ref(x, wp, sx, zx, scale, corr, bias=None,
                                 gv=None, ps=None, nm=None, gr=None, bv=None,
                                 group_k: int = 256, out_dtype=jnp.float32):
    """Vector-tgroup sibling of ``int4_matmul_fq_fused_ref``."""
    xf = fused_prologue_ref(x, nm=nm, ps=ps, bv=bv)
    y = int4_matmul_fq_vec_ref(xf, wp, sx, zx, scale, corr, bias=bias,
                               gv=gv, group_k=group_k)
    return fused_epilogue_ref(y, gr=gr, bv=bv).astype(out_dtype)


def int4_matmul_mrq_fq_vec_fused_ref(x, wp, s_neg, s_pos, scale_neg,
                                     scale_pos, bias=None, gv=None, ps=None,
                                     nm=None, gr=None, bv=None,
                                     group_k: int = 256,
                                     out_dtype=jnp.float32):
    """Vector-tgroup sibling of ``int4_matmul_mrq_fq_fused_ref``."""
    xf = fused_prologue_ref(x, nm=nm, ps=ps, bv=bv)
    y = int4_matmul_mrq_fq_vec_ref(xf, wp, s_neg, s_pos, scale_neg,
                                   scale_pos, bias=bias, gv=gv,
                                   group_k=group_k)
    return fused_epilogue_ref(y, gr=gr, bv=bv).astype(out_dtype)


def softmax_mrq_ref(scores, s1, bits: int, out_dtype=jnp.float32):
    """Row softmax (last axis, f32 accumulation) then MRQ two-region
    quant-dequant (§III-C)."""
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return mrq_softmax_qdq(p, s1, bits).astype(out_dtype)


# ---------------------------------------------------------------------------
# int8 attention (batched kernels)
# ---------------------------------------------------------------------------
def sym_quantize_int8_ref(x, scale, bits: int = 8):
    """Symmetric s8 codes over the weight code range [-(h-1), h-1]."""
    hi = 2 ** (bits - 1) - 1
    return jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -hi, hi
                    ).astype(jnp.int8)


def int8_bmm_qk_ref(q, k, s_q, s_k, scale, g=0, bits: int = 8,
                    out_dtype=jnp.float32):
    """Batched symmetric QK^T oracle: quantize both activation operands
    with group-g per-tensor steps, s32 batched matmul, scalar dequant.

    q: (B,M,D), k: (B,N,D) float; s_q/s_k/scale: (G,1) f32 (scale is the
    combined s_q[g]*s_k[g]*alpha the kernel applies in its epilogue).
    """
    q8 = sym_quantize_int8_ref(q, jnp.take(s_q, g, axis=0)[0], bits)
    k8 = sym_quantize_int8_ref(k, jnp.take(s_k, g, axis=0)[0], bits)
    acc = jax.lax.dot_general(
        q8.astype(jnp.int32), k8.astype(jnp.int32),
        (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32)
            * jnp.take(scale, g, axis=0)[0]).astype(out_dtype)


def softmax_mrq_codes_ref(scores, s1, g=0, bits: int = 8):
    """Row softmax then region-signed int8 MRQ codes: c >= 0 is a
    region-1 code (step s1[g]), c < 0 the NEGATED region-2 code (step
    s2 = 1/2^{k-1}; negation fits region-2's [0, 2^{k-1}] range in a
    signed byte). c == 0 is shared but dequantizes to 0 either way."""
    half = 2 ** (bits - 1)
    s1_g = jnp.take(jnp.asarray(s1, jnp.float32), g, axis=0)[0]
    s2 = 1.0 / half
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    q1 = jnp.clip(jnp.round(p / s1_g), 0, half - 1)
    q2 = jnp.clip(jnp.round(p / s2), 0, half)
    return jnp.where(p < half * s1_g, q1, -q2).astype(jnp.int8)


def mrq_codes_decode_ref(codes, s1, g=0, bits: int = 8):
    """Dequantize region-signed prob codes back to fp probabilities.
    Equals ``mrq_softmax_qdq`` applied to the same softmax rows."""
    half = 2 ** (bits - 1)
    s1_g = jnp.take(jnp.asarray(s1, jnp.float32), g, axis=0)[0]
    c = codes.astype(jnp.float32)
    return jnp.where(c >= 0, c * s1_g, -c * (1.0 / half))


def int8_bmm_pv_ref(codes, v, s_v, scale1, scale2, g=0, bits: int = 8,
                    out_dtype=jnp.float32):
    """Batched dual-region P·V oracle consuming region-signed prob codes.

    codes: (B,M,N) int8; v: (B,N,D) float; s_v/scale1/scale2: (G,1) f32
    (scale1 = s1[g]*s_v[g], scale2 = s2*s_v[g]).
    """
    c = codes.astype(jnp.int32)
    c1 = jnp.maximum(c, 0)
    c2 = jnp.maximum(-c, 0)
    v8 = sym_quantize_int8_ref(v, jnp.take(s_v, g, axis=0)[0], bits
                               ).astype(jnp.int32)
    dims = (((2,), (1,)), ((0,), (0,)))
    acc1 = jax.lax.dot_general(c1, v8, dims,
                               preferred_element_type=jnp.int32)
    acc2 = jax.lax.dot_general(c2, v8, dims,
                               preferred_element_type=jnp.int32)
    y = (acc1.astype(jnp.float32) * jnp.take(scale1, g, axis=0)[0]
         + acc2.astype(jnp.float32) * jnp.take(scale2, g, axis=0)[0])
    return y.astype(out_dtype)


def int8_attention_ref(q, k, v, qk_pack, pv_pack, mask=None, scale=1.0,
                       g=0, bits: int = 8, out_dtype=jnp.float32):
    """Full int8 attention oracle over FLATTENED (BHG, S, hd) operands:
    symmetric QK^T -> mask -> softmax-to-codes -> dual-region P·V.
    Exactly the composition ``kernels.ops.int8_attention`` runs."""
    from repro.nn.ctx import NEG_INF
    scores = int8_bmm_qk_ref(q, k, qk_pack["s_q"], qk_pack["s_k"],
                             qk_pack["scale"] * scale, g=g, bits=bits)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    codes = softmax_mrq_codes_ref(scores, pv_pack["s1"], g=g, bits=bits)
    return int8_bmm_pv_ref(codes, v, pv_pack["s_v"], pv_pack["scale1"],
                           pv_pack["scale2"], g=g, bits=bits,
                           out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# flash-style fused attention (single kernel, no (S,S) HBM round-trip)
# ---------------------------------------------------------------------------
def flash_attn_mrq_ref(q, k, v, qk_pack, pv_pack, mask=None, scale=1.0,
                       g_qk=0, g_pv=0, bits: int = 8, bn=None,
                       out_dtype=jnp.float32):
    """Tile-faithful oracle for ``flash_attn_mrq`` over FLATTENED
    (B, S, hd) operands (kv materialized per q batch — the kernel's
    ``b // rep`` GQA gather is equivalence-tested separately).

    Replays the kernel's exact per-kv-tile recurrence — int8 QK^T,
    NEG_INF lane masking BEFORE the online max, running max/denominator,
    MRQ two-region codes against the running normalization, dual-region
    integer P·V with the fp rescale — so kernel vs oracle comparisons are
    (jitted) bit-exact, the same contract as the composed kernels.
    """
    from repro.nn.ctx import NEG_INF
    from repro.kernels.flash_attn_mrq import kv_tile
    from repro.kernels.int8_matmul import _ceil
    B, M, D = q.shape
    N = k.shape[1]
    half = 2 ** (bits - 1)
    # the kernel's kv tile
    bn_ = kv_tile(M, N) if bn is None else min(bn, _ceil(N))
    Np = -bn_ * (-N // bn_)

    sq_g = jnp.take(qk_pack["s_q"], g_qk, axis=0)[0]
    sk_g = jnp.take(qk_pack["s_k"], g_qk, axis=0)[0]
    qs_g = jnp.take(qk_pack["scale"], g_qk, axis=0)[0] * scale
    s1_g = jnp.take(pv_pack["s1"], g_pv, axis=0)[0]
    sv_g = jnp.take(pv_pack["s_v"], g_pv, axis=0)[0]
    sc1_g = jnp.take(pv_pack["scale1"], g_pv, axis=0)[0]
    sc2_g = jnp.take(pv_pack["scale2"], g_pv, axis=0)[0]
    s2 = 1.0 / half

    q8 = sym_quantize_int8_ref(q, sq_g, bits).astype(jnp.int32)
    k8 = sym_quantize_int8_ref(
        jnp.pad(k.astype(jnp.float32), ((0, 0), (0, Np - N), (0, 0))),
        sk_g, bits).astype(jnp.int32)
    v8 = sym_quantize_int8_ref(
        jnp.pad(v.astype(jnp.float32), ((0, 0), (0, Np - N), (0, 0))),
        sv_g, bits).astype(jnp.int32)
    if mask is not None:
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, Np - N)))

    m_run = jnp.full((B, M, 1), -1e30, jnp.float32)
    l_run = jnp.zeros((B, M, 1), jnp.float32)
    acc1 = jnp.zeros((B, M, D), jnp.float32)
    acc2 = jnp.zeros((B, M, D), jnp.float32)
    col = jnp.arange(Np)
    for n0 in range(0, Np, bn_):
        kt = k8[:, n0:n0 + bn_]
        vt = v8[:, n0:n0 + bn_]
        s = jax.lax.dot_general(
            q8, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.int32).astype(jnp.float32) * qs_g
        s = jnp.where(col[n0:n0 + bn_][None, None, :] < N, s, NEG_INF)
        if mask is not None:
            s = jnp.where(mask[:, :, n0:n0 + bn_], s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        e = jnp.exp(s - m_new)
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(e, axis=-1, keepdims=True)
        p = e / l_new
        region1 = p < half * s1_g
        c1 = jnp.where(region1, jnp.clip(jnp.round(p / s1_g), 0, half - 1),
                       0.0).astype(jnp.int32)
        c2 = jnp.where(region1, 0.0, jnp.clip(jnp.round(p / s2), 0, half)
                       ).astype(jnp.int32)
        dims = (((2,), (1,)), ((0,), (0,)))
        d1 = jax.lax.dot_general(c1, vt, dims,
                                 preferred_element_type=jnp.int32)
        d2 = jax.lax.dot_general(c2, vt, dims,
                                 preferred_element_type=jnp.int32)
        rho = corr * l_run / l_new
        acc1 = acc1 * rho + d1.astype(jnp.float32)
        acc2 = acc2 * rho + d2.astype(jnp.float32)
        m_run, l_run = m_new, l_new
    return (acc1 * sc1_g + acc2 * sc2_g).astype(out_dtype)


def flash_vs_composed_atol(pv_pack, g, n_kv: int, bits: int = 8) -> float:
    """The documented flash ≡ composed tolerance contract (worst case).

    Both paths dequantize each probability to within half a step of the
    true softmax value; the flash path's codes round against the RUNNING
    normalization, but the running estimate times the subsequent rescale
    factors equals the final normalized probability exactly in real
    arithmetic, and every rescale factor is <= 1 — so the per-element
    dequantized-probability divergence between the two paths is bounded
    by one coarse step ``s2 = 1/2^{k-1}`` (fine-region elements are
    tighter). Each output element sums ``n_kv`` such probabilities
    against dequantized values of magnitude <= (2^{k-1}-1)·s_v[g]:

        |flash - composed| <= n_kv · s2 · (2^{k-1}-1) · s_v[g]

    This is deliberately loose (worst case, every code off by a full
    region-2 step in the same direction); the sweeps in
    ``tests/test_flash_attn.py`` additionally assert the observed error
    sits far inside it.
    """
    import numpy as np
    half = 2 ** (bits - 1)
    s_v = float(np.asarray(jnp.take(pv_pack["s_v"], g, axis=0))[0])
    return n_kv * (1.0 / half) * (half - 1) * s_v


def act_mrq_ref(x, s_neg, s_pos, bits: int, kind: str = "gelu",
                out_dtype=jnp.float32):
    """GELU/SiLU (f32) then MRQ signed two-region quant-dequant."""
    xf = x.astype(jnp.float32)
    h = jax.nn.gelu(xf, approximate=True) if kind == "gelu" else jax.nn.silu(xf)
    return mrq_signed_qdq(h, s_neg, s_pos, bits).astype(out_dtype)


# ---------------------------------------------------------------------------
# vector-tgroup oracles: per-row / per-batch-row group indices
# ---------------------------------------------------------------------------
def int8_matmul_fq_vec_ref(x, wq, sx, zx, scale, corr, bias=None, gv=None,
                           bits: int = 8, out_dtype=jnp.float32):
    """Per-row oracle for ``int8_matmul_fq_vec``: row i quantizes with
    sx[gv[i]]/zx[gv[i]] and dequantizes with scale[gv[i]]/corr[gv[i]]."""
    M = x.shape[0]
    gv = jnp.zeros((M,), jnp.int32) if gv is None else jnp.asarray(gv)
    sx_r = jnp.take(sx, gv, axis=0)                       # (M, 1)
    zx_r = jnp.take(zx, gv, axis=0)                       # (M, 1)
    xq = quantize_int8_ref(x.astype(jnp.float32), sx_r, zx_r, bits)
    acc = jax.lax.dot_general(
        xq.astype(jnp.int32), wq.astype(jnp.int32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    y = ((acc - jnp.take(corr, gv, axis=0)).astype(jnp.float32)
         * jnp.take(scale, gv, axis=0))
    if bias is not None:
        y = y + bias[None, :].astype(jnp.float32)
    return y.astype(out_dtype)


def int8_matmul_mrq_fq_vec_ref(x, wq, s_neg, s_pos, scale_neg, scale_pos,
                               bias=None, gv=None, bits: int = 8,
                               out_dtype=jnp.float32):
    """Per-row oracle for ``int8_matmul_mrq_fq_vec``."""
    half = 2 ** (bits - 1)
    M = x.shape[0]
    gv = jnp.zeros((M,), jnp.int32) if gv is None else jnp.asarray(gv)
    xf = x.astype(jnp.float32)
    sn_r = jnp.take(s_neg, gv, axis=0)                    # (M, 1)
    sp_r = jnp.take(s_pos, gv, axis=0)                    # (M, 1)
    neg = xf < 0
    qn = jnp.where(neg, jnp.clip(jnp.round(xf / sn_r), -half, 0), 0
                   ).astype(jnp.int8)
    qp = jnp.where(neg, 0, jnp.clip(jnp.round(xf / sp_r), 0, half - 1)
                   ).astype(jnp.int8)
    dims = (((1,), (0,)), ((), ()))
    acc_n = jax.lax.dot_general(qn.astype(jnp.int32), wq.astype(jnp.int32),
                                dims, preferred_element_type=jnp.int32)
    acc_p = jax.lax.dot_general(qp.astype(jnp.int32), wq.astype(jnp.int32),
                                dims, preferred_element_type=jnp.int32)
    y = (acc_n.astype(jnp.float32) * jnp.take(scale_neg, gv, axis=0)
         + acc_p.astype(jnp.float32) * jnp.take(scale_pos, gv, axis=0))
    if bias is not None:
        y = y + bias[None, :].astype(jnp.float32)
    return y.astype(out_dtype)


def int4_matmul_fq_vec_ref(x, wp, sx, zx, scale, corr, bias=None, gv=None,
                           group_k: int = 256, out_dtype=jnp.float32):
    """Per-row oracle for ``int4_matmul_fq_vec`` — the kernel's
    group-ordered f32 accumulation with per-row scale/corr rows."""
    from repro.kernels.int4_packed import unpack_int4
    M, K = x.shape
    Kp, N = 2 * wp.shape[0], wp.shape[1]
    nk = Kp // group_k
    gv = jnp.zeros((M,), jnp.int32) if gv is None else jnp.asarray(gv)
    sx_r = jnp.take(sx, gv, axis=0)                       # (M, 1)
    zx_r = jnp.take(zx, gv, axis=0)
    xq = quantize_int8_ref(x.astype(jnp.float32), sx_r, zx_r, bits=4)
    xq = jnp.pad(xq, ((0, 0), (0, Kp - K))).astype(jnp.int32)
    w = unpack_int4(wp).astype(jnp.int32)
    scale_r = jnp.take(scale, gv, axis=0)                 # (M, nk, N)
    corr_r = jnp.take(corr, gv, axis=0)
    acc = jnp.zeros((M, N), jnp.float32)
    for kg in range(nk):
        sl = slice(kg * group_k, (kg + 1) * group_k)
        partial = jax.lax.dot_general(
            xq[:, sl], w[sl], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = acc + ((partial - corr_r[:, kg]).astype(jnp.float32)
                     * scale_r[:, kg])
    if bias is not None:
        acc = acc + bias[None, :].astype(jnp.float32)
    return acc.astype(out_dtype)


def int4_matmul_mrq_fq_vec_ref(x, wp, s_neg, s_pos, scale_neg, scale_pos,
                               bias=None, gv=None, group_k: int = 256,
                               out_dtype=jnp.float32):
    """Per-row oracle for ``int4_matmul_mrq_fq_vec``."""
    from repro.kernels.int4_packed import unpack_int4
    half = 8
    M, K = x.shape
    Kp, N = 2 * wp.shape[0], wp.shape[1]
    nk = Kp // group_k
    gv = jnp.zeros((M,), jnp.int32) if gv is None else jnp.asarray(gv)
    xf = x.astype(jnp.float32)
    sn_r = jnp.take(s_neg, gv, axis=0)                    # (M, 1)
    sp_r = jnp.take(s_pos, gv, axis=0)
    neg = xf < 0
    qn = jnp.where(neg, jnp.clip(jnp.round(xf / sn_r), -half, 0), 0
                   ).astype(jnp.int32)
    qp = jnp.where(neg, 0, jnp.clip(jnp.round(xf / sp_r), 0, half - 1)
                   ).astype(jnp.int32)
    qn = jnp.pad(qn, ((0, 0), (0, Kp - K)))
    qp = jnp.pad(qp, ((0, 0), (0, Kp - K)))
    w = unpack_int4(wp).astype(jnp.int32)
    sn_g = jnp.take(scale_neg, gv, axis=0)                # (M, nk, N)
    sp_g = jnp.take(scale_pos, gv, axis=0)
    dims = (((1,), (0,)), ((), ()))
    acc = jnp.zeros((M, N), jnp.float32)
    for kg in range(nk):
        sl = slice(kg * group_k, (kg + 1) * group_k)
        pn = jax.lax.dot_general(qn[:, sl], w[sl], dims,
                                 preferred_element_type=jnp.int32)
        pp = jax.lax.dot_general(qp[:, sl], w[sl], dims,
                                 preferred_element_type=jnp.int32)
        acc = acc + (pn.astype(jnp.float32) * sn_g[:, kg]
                     + pp.astype(jnp.float32) * sp_g[:, kg])
    if bias is not None:
        acc = acc + bias[None, :].astype(jnp.float32)
    return acc.astype(out_dtype)


def int8_bmm_qk_vec_ref(q, k, s_q, s_k, scale, gv=None, bits: int = 8,
                        out_dtype=jnp.float32):
    """Per-batch-row oracle for ``int8_bmm_qk_vec`` (q and k batches
    equal here — GQA sharing is equivalence-tested at the kernel level)."""
    B = q.shape[0]
    gv = jnp.zeros((B,), jnp.int32) if gv is None else jnp.asarray(gv)
    sq_b = jnp.take(s_q, gv, axis=0)[:, :, None]          # (B, 1, 1)
    sk_b = jnp.take(s_k, gv, axis=0)[:, :, None]
    q8 = sym_quantize_int8_ref(q, sq_b, bits)
    k8 = sym_quantize_int8_ref(k, sk_b, bits)
    acc = jax.lax.dot_general(
        q8.astype(jnp.int32), k8.astype(jnp.int32),
        (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32)
            * jnp.take(scale, gv, axis=0)[:, :, None]).astype(out_dtype)


def softmax_mrq_codes_vec_ref(scores, s1, gv=None, bits: int = 8):
    """Per-row oracle for ``softmax_mrq_codes_vec``: gv has shape
    ``scores.shape[:-1]`` (one group per softmax row)."""
    half = 2 ** (bits - 1)
    if gv is None:
        gv = jnp.zeros(scores.shape[:-1], jnp.int32)
    s1_r = jnp.take(jnp.asarray(s1, jnp.float32), jnp.asarray(gv), axis=0)
    s2 = 1.0 / half
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    q1 = jnp.clip(jnp.round(p / s1_r), 0, half - 1)
    q2 = jnp.clip(jnp.round(p / s2), 0, half)
    return jnp.where(p < half * s1_r, q1, -q2).astype(jnp.int8)


def int8_bmm_pv_vec_ref(codes, v, s_v, scale1, scale2, gv=None,
                        bits: int = 8, out_dtype=jnp.float32):
    """Per-batch-row oracle for ``int8_bmm_pv_vec``."""
    B = codes.shape[0]
    gv = jnp.zeros((B,), jnp.int32) if gv is None else jnp.asarray(gv)
    c = codes.astype(jnp.int32)
    c1 = jnp.maximum(c, 0)
    c2 = jnp.maximum(-c, 0)
    sv_b = jnp.take(s_v, gv, axis=0)[:, :, None]          # (B, 1, 1)
    v8 = sym_quantize_int8_ref(v, sv_b, bits).astype(jnp.int32)
    dims = (((2,), (1,)), ((0,), (0,)))
    acc1 = jax.lax.dot_general(c1, v8, dims,
                               preferred_element_type=jnp.int32)
    acc2 = jax.lax.dot_general(c2, v8, dims,
                               preferred_element_type=jnp.int32)
    y = (acc1.astype(jnp.float32) * jnp.take(scale1, gv, axis=0)[:, :, None]
         + acc2.astype(jnp.float32) * jnp.take(scale2, gv, axis=0)[:, :, None])
    return y.astype(out_dtype)


def int8_attention_vec_ref(q, k, v, qk_pack, pv_pack, mask=None, scale=1.0,
                           gv=None, bits: int = 8, out_dtype=jnp.float32):
    """Composed per-batch-row int8 attention oracle over FLATTENED
    (BHG, S, hd) operands — the vector sibling of ``int8_attention_ref``;
    exactly the composition ``kernels.ops.int8_attention`` runs when the
    tgroup is a per-slot vector."""
    from repro.nn.ctx import NEG_INF
    B, M, _ = q.shape
    gv = jnp.zeros((B,), jnp.int32) if gv is None else jnp.asarray(gv)
    scores = int8_bmm_qk_vec_ref(q, k, qk_pack["s_q"], qk_pack["s_k"],
                                 qk_pack["scale"] * scale, gv=gv, bits=bits)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    rows_gv = jnp.broadcast_to(gv[:, None], (B, M))
    codes = softmax_mrq_codes_vec_ref(scores, pv_pack["s1"], gv=rows_gv,
                                      bits=bits)
    return int8_bmm_pv_vec_ref(codes, v, pv_pack["s_v"], pv_pack["scale1"],
                               pv_pack["scale2"], gv=gv, bits=bits,
                               out_dtype=out_dtype)


def flash_attn_mrq_vec_ref(q, k, v, qk_pack, pv_pack, mask=None, scale=1.0,
                           g_qk=None, g_pv=None, bits: int = 8,
                           bn=None, out_dtype=jnp.float32):
    """Tile-faithful per-batch-row oracle for ``flash_attn_mrq_vec``:
    the recurrence of ``flash_attn_mrq_ref`` with every group-gathered
    scalar widened to a (B, 1, 1) per-batch-row column."""
    from repro.nn.ctx import NEG_INF
    from repro.kernels.flash_attn_mrq import kv_tile
    from repro.kernels.int8_matmul import _ceil
    B, M, D = q.shape
    N = k.shape[1]
    half = 2 ** (bits - 1)
    bn_ = kv_tile(M, N) if bn is None else min(bn, _ceil(N))
    Np = -bn_ * (-N // bn_)
    g_qk = jnp.zeros((B,), jnp.int32) if g_qk is None else jnp.asarray(g_qk)
    g_pv = jnp.zeros((B,), jnp.int32) if g_pv is None else jnp.asarray(g_pv)

    sq_g = jnp.take(qk_pack["s_q"], g_qk, axis=0)[:, :, None]      # (B,1,1)
    sk_g = jnp.take(qk_pack["s_k"], g_qk, axis=0)[:, :, None]
    qs_g = jnp.take(qk_pack["scale"], g_qk, axis=0)[:, :, None] * scale
    s1_g = jnp.take(pv_pack["s1"], g_pv, axis=0)[:, :, None]
    sv_g = jnp.take(pv_pack["s_v"], g_pv, axis=0)[:, :, None]
    sc1_g = jnp.take(pv_pack["scale1"], g_pv, axis=0)[:, :, None]
    sc2_g = jnp.take(pv_pack["scale2"], g_pv, axis=0)[:, :, None]
    s2 = 1.0 / half

    q8 = sym_quantize_int8_ref(q, sq_g, bits).astype(jnp.int32)
    k8 = sym_quantize_int8_ref(
        jnp.pad(k.astype(jnp.float32), ((0, 0), (0, Np - N), (0, 0))),
        sk_g, bits).astype(jnp.int32)
    v8 = sym_quantize_int8_ref(
        jnp.pad(v.astype(jnp.float32), ((0, 0), (0, Np - N), (0, 0))),
        sv_g, bits).astype(jnp.int32)
    if mask is not None:
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, Np - N)))

    m_run = jnp.full((B, M, 1), -1e30, jnp.float32)
    l_run = jnp.zeros((B, M, 1), jnp.float32)
    acc1 = jnp.zeros((B, M, D), jnp.float32)
    acc2 = jnp.zeros((B, M, D), jnp.float32)
    col = jnp.arange(Np)
    for n0 in range(0, Np, bn_):
        kt = k8[:, n0:n0 + bn_]
        vt = v8[:, n0:n0 + bn_]
        s = jax.lax.dot_general(
            q8, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.int32).astype(jnp.float32) * qs_g
        s = jnp.where(col[n0:n0 + bn_][None, None, :] < N, s, NEG_INF)
        if mask is not None:
            s = jnp.where(mask[:, :, n0:n0 + bn_], s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        e = jnp.exp(s - m_new)
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(e, axis=-1, keepdims=True)
        p = e / l_new
        region1 = p < half * s1_g
        c1 = jnp.where(region1, jnp.clip(jnp.round(p / s1_g), 0, half - 1),
                       0.0).astype(jnp.int32)
        c2 = jnp.where(region1, 0.0, jnp.clip(jnp.round(p / s2), 0, half)
                       ).astype(jnp.int32)
        dims = (((2,), (1,)), ((0,), (0,)))
        d1 = jax.lax.dot_general(c1, vt, dims,
                                 preferred_element_type=jnp.int32)
        d2 = jax.lax.dot_general(c2, vt, dims,
                                 preferred_element_type=jnp.int32)
        rho = corr * l_run / l_new
        acc1 = acc1 * rho + d1.astype(jnp.float32)
        acc2 = acc2 * rho + d2.astype(jnp.float32)
        m_run, l_run = m_new, l_new
    return (acc1 * sc1_g + acc2 * sc2_g).astype(out_dtype)
