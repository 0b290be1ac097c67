"""Flash-style fused int8 MRQ attention: QK^T -> online softmax -> MRQ
prob codes -> P·V in ONE Pallas kernel — the (S, S) scores and prob-code
tensors never touch HBM.

The composed int8 attention path (``int8_bmm_qk`` -> ``softmax_mrq_codes``
-> ``int8_bmm_pv``) serves fully int8 but still round-trips the full
(BH, S, S) f32 scores and int8 prob codes through HBM — the dominant
remaining attention traffic. This kernel streams K/V tiles per
(batch·head, q-tile) grid point and keeps the whole quadratic
intermediate in VMEM:

1. **int8 QK^T** — the q and k tiles are quantized with the group-``g``
   symmetric per-tensor steps in the VMEM prologue (same ``SymQ``
   contract as ``int8_bmm_qk``); the s32 MXU product dequantizes with the
   combined ``s_q[g]·s_k[g]·alpha`` scale into an f32 (bm, bn) score tile
   that never leaves VMEM.
2. **Ragged / user masking BEFORE the online max** — kv lanes past the
   true sequence length (S not a multiple of the k-tile) and user-masked
   lanes are set to ``NEG_INF`` *before* the running-max update.
   Unmasked, a padded lane's int8 score of exactly 0 would win the row
   max whenever the real scores are negative and poison both the max and
   the denominator (``exp(NEG_INF - m)`` underflows to exactly 0.0 in
   f32, so masked lanes contribute nothing downstream).
3. **Online softmax** — running row max ``m`` and denominator ``l`` in
   VMEM scratch, the standard flash recurrence
   ``m' = max(m, rowmax(s))``, ``l' = l·exp(m - m') + rowsum(exp(s - m'))``.
4. **MRQ two-region prob codes per tile** — the paper's §III-C
   post-softmax quantizer, applied to the tile's *running-normalized*
   probability estimate ``p̃ = exp(s - m')/l'`` against the calibrated
   per-group region-1 step ``s1[g]``: region 1 (fine step ``s1``) where
   ``p̃ < 2^{k-1}·s1``, region 2 (coarse step ``s2 = 1/2^{k-1}``) above.
   The two disjoint region-magnitude tiles are exactly the operands the
   composed path transports as region-signed bytes — here they are formed
   and consumed inside VMEM.
5. **Dual-region P·V with fp running-rescale** — each region tile
   multiplies the in-VMEM-quantized v tile on the MXU into an s32
   product, accumulated into two f32 region accumulators with the flash
   rescale ``rho = exp(m - m')·l/l'`` applied to the previously
   accumulated contributions. Because ``p̃·(Π rho) == exp(s - m_fin)/l_fin``
   exactly in real arithmetic, the only divergence from the composed
   path is that each tile's codes ROUND against the running normalization
   instead of the final one — the rescale then shrinks that (already
   ≤ step/2) rounding error by ``Π rho <= 1``. See
   ``ref.flash_vs_composed_atol`` for the documented tolerance contract.
6. **Epilogue** — ``out = scale1[g]·acc1 + scale2[g]·acc2`` with
   ``scale1 = s1[g]·s_v[g]``, ``scale2 = s2·s_v[g]`` (the ``int8_bmm_pv``
   epilogue scales), written to HBM exactly once.

TGQ exactly as in the composed kernels: every activation-side parameter
is stacked along a leading (G,) group axis and the timestep groups — a
``(2,)`` i32 vector ``[g_qk, g_pv]``, possibly traced inside the
``ddpm_sample`` lax.scan — are scalar-prefetched; the BlockSpec index
maps gather the per-group rows, so the whole sampling loop stays ONE
compiled executable (the qk-side and pv-side packs may carry different
group counts — each side clamps its own index).

GQA as in ``int8_bmm``: the q-side batch may be ``rep`` times the
k/v-side batch; the shared kv tile is gathered via a ``b // rep`` index
map — no materialized copies, and kv HBM traffic does not scale with the
number of query groups.

Traffic: q is read from HBM once in fp, the output written once, and
the K/V stream is re-fetched once per q-tile (the standard flash trade:
``ceil(M/bm)`` reads each — exactly ONE at DiT-serving sequence lengths,
since the default q-tile ``bm = 256`` covers DiT-XL/2's S = 256). The
(S, S) scores/codes round-trip — ``BH·S²·10`` bytes on the composed
path — is eliminated entirely: ≥3x whole-attention traffic cut at
DiT-XL/2 shapes (``benchmarks/kernel_micro.py::traffic_attention_flash``
charges the kv re-reads honestly).

Grid: (B, M/bm, N/bn) with the kv axis innermost; the running stats and
both accumulators live in VMEM scratch persisting across the kv axis.
The optional boolean mask streams as int8 0/1 tiles (1 byte/elt — still
no fp quadratic tensor through HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int4_packed import nibble_split, pack_int4
from repro.kernels.int8_bmm import _sym_codes, _sym_levels
from repro.kernels.int8_matmul import _ceil, _group_param, _pad_to, _stack3

# The q-tile covers DiT-XL/2's full S = 256, so K/V stream from HBM
# exactly once there.
DEFAULT_BM = 256
# The kv tile follows the sequence: one tile over all N keys while the
# (bm, N) f32 score tile fits ONE_TILE_SCORE_BYTES of VMEM (N <= 1024 at
# bm = 256), MULTI_TILE_BN-wide tiles above. Accuracy contract at that
# boundary: with one kv tile the prob codes round against the final
# softmax sum and flash equals the composed path to f32 ulp; with several,
# the codes of every tile but the last round against a partial sum
# (bounded by ``ref.flash_vs_composed_atol``), which at XL/2 W8A8 on a
# v5e doubled eps's distance from fake-quant (6.7e-2 vs 3.2e-2).
ONE_TILE_SCORE_BYTES = 1 << 20
MULTI_TILE_BN = 256
_M_INIT = -1e30         # below any masked score; exp(_M_INIT - m) == 0.0


def kv_tile(m: int, n: int, bm: int = DEFAULT_BM) -> int:
    """The kv tile width for ``m`` queries against ``n`` keys at q-tile
    ``bm`` when the caller gives none: the whole padded sequence if its
    score tile fits ``ONE_TILE_SCORE_BYTES``, else ``MULTI_TILE_BN``."""
    full = _ceil(n)
    if min(bm, _ceil(m)) * full * 4 <= ONE_TILE_SCORE_BYTES:
        return full
    return MULTI_TILE_BN


def _flash_kernel(g_ref, *refs, nkv: int, half: int, n_real: int, bn: int,
                  neg_inf: float, has_mask: bool, packed_kv: bool = False,
                  bd: int = 0):
    """Grid body at (b, m, n) — n (the kv tile) innermost.

    ``refs`` unpacks to the tile refs (q, k, v[, mask8]), the group-``g``
    rows of the stacked (G, 1) params (s_q, s_k, qk_scale, s1, s_v,
    scale1, scale2), the output ref and the four VMEM scratch refs
    (running max / denominator as (bm, 128) lane-broadcast stats, two
    (bm, D) f32 region accumulators). ``g_ref`` ([g_qk, g_pv]) feeds the
    index maps only.

    ``packed_kv``: k/v tiles arrive as (bn, bd/2) nibble-PACKED
    pre-quantized 4-bit codes (the W4A4 path's one-time pack pass) and
    are widened to s8-range codes here instead of running ``_sym_codes``
    — halving the kv bytes streamed per q-tile.
    """
    del g_ref
    if has_mask:
        (q_ref, k_ref, v_ref, mask_ref, sq_ref, sk_ref, qs_ref, s1_ref,
         sv_ref, sc1_ref, sc2_ref, o_ref, m_ref, l_ref, acc1_ref,
         acc2_ref) = refs
    else:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref, qs_ref, s1_ref, sv_ref,
         sc1_ref, sc2_ref, o_ref, m_ref, l_ref, acc1_ref, acc2_ref) = refs
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc1_ref[...] = jnp.zeros_like(acc1_ref)
        acc2_ref[...] = jnp.zeros_like(acc2_ref)

    # -- int8 QK^T for this tile (scores stay in VMEM) ----------------------
    q8 = _sym_codes(q_ref[0], sq_ref[0, 0], half)
    if packed_kv:                # widen two-nibbles-per-byte codes in VMEM
        lo, hi = nibble_split(k_ref[0])
        k8 = jnp.stack([lo, hi], axis=2).reshape(
            k_ref.shape[1], bd).astype(jnp.int8)
    else:
        k8 = _sym_codes(k_ref[0], sk_ref[0, 0], half)
    s = jax.lax.dot_general(
        q8, k8, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    ).astype(jnp.float32) * qs_ref[0, 0]

    # -- NEG_INF masking BEFORE the online max ------------------------------
    # Ragged kv: lanes past the true length get the additive mask now —
    # a padded lane's exact-0 int8 score must never enter the running max
    # or denominator.
    col = n * bn + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < n_real, s, neg_inf)
    if has_mask:
        s = jnp.where(mask_ref[0] != 0, s, neg_inf)

    # -- online softmax update ----------------------------------------------
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    e = jnp.exp(s - m_new)                           # (bm, bn)
    corr = jnp.exp(m_prev - m_new)                   # (bm, 1)
    l_new = l_prev * corr + jnp.sum(e, axis=-1, keepdims=True)

    # -- MRQ two-region codes against the running normalization -------------
    p = e / l_new
    s1 = s1_ref[0, 0]
    s2 = 1.0 / half
    region1 = p < half * s1
    c1 = jnp.where(region1, jnp.clip(jnp.round(p / s1), 0, half - 1), 0.0
                   ).astype(jnp.int8)
    # region-2 codes reach 2^{k-1}, one past the s8 maximum: they enter the
    # MXU negated, against the negated v codes (symmetric, so still s8)
    c2n = jnp.where(region1, 0.0, -jnp.clip(jnp.round(p / s2), 0, half)
                    ).astype(jnp.int8)

    # -- dual-region P·V with fp running-rescale ----------------------------
    if packed_kv:
        lo_v, hi_v = nibble_split(v_ref[0])
        vq = jnp.stack([lo_v, hi_v], axis=2).reshape(v_ref.shape[1], bd)
    else:
        vq = _sym_levels(v_ref[0], sv_ref[0, 0], half)
    dims = (((1,), (0,)), ((), ()))                  # ONE v-tile read
    d1 = jax.lax.dot_general(c1, vq.astype(jnp.int8), dims,
                             preferred_element_type=jnp.int32)
    d2 = jax.lax.dot_general(c2n, (-vq).astype(jnp.int8), dims,
                             preferred_element_type=jnp.int32)
    rho = corr * l_prev / l_new                      # <= 1; 0 at n == 0
    acc1_ref[...] = acc1_ref[...] * rho + d1.astype(jnp.float32)
    acc2_ref[...] = acc2_ref[...] * rho + d2.astype(jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(n == nkv - 1)
    def _epilogue():
        y = acc1_ref[...] * sc1_ref[0, 0] + acc2_ref[...] * sc2_ref[0, 0]
        o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "packed_kv", "bm", "bn",
                                             "out_dtype", "interpret"))
def flash_attn_mrq(q, k, v, s_q, s_k, qk_scale, s1, s_v, scale1, scale2,
                   g_qk=None, g_pv=None, mask=None, *, bits=8,
                   packed_kv=False, bm=DEFAULT_BM, bn=None,
                   out_dtype=jnp.float32, interpret=False):
    """out[B,M,D] = MRQ-quantized softmax(q8 k8^T · qk_scale[g]) @ v8 —
    one kernel, no (S, S) HBM round-trip.

    q: (B, M, D) float; k, v: (Bk, N, D) float with B = rep · Bk (GQA —
    the shared kv head is gathered via a ``b // rep`` index map).
    s_q/s_k: (Gq, 1) f32 symmetric steps; qk_scale: (Gq, 1) combined
    ``s_q[g]·s_k[g]·alpha`` (alpha = the softmax scale, folded by the
    caller). s1/s_v/scale1/scale2: (Gp, 1) f32 — the ``int8_pv`` pack
    params (``scale1 = s1·s_v``, ``scale2 = s2·s_v``). g_qk / g_pv: the
    TGQ groups for each pack side — python ints or traced scalars
    (scalar-prefetched together; no retrace across groups). mask:
    optional (B, M, N) boolean (True = attend), streamed as int8 tiles.

    ``packed_kv`` (4-bit only): k/v are quantized with the group-g steps
    and nibble-packed along D in ONE jnp pre-pass; the kernel then
    streams half the kv bytes per q-tile and widens nibbles in its
    prologue. The trade is honest: the pack pass reads kv in fp and
    writes the packed codes once, so it wins when kv is re-streamed
    (ceil(M/bm) > 1, long S) and is neutral at one q-tile — see
    ``benchmarks/kernel_micro.traffic_attention_flash_packed``.
    Numerics are IDENTICAL to the unpacked 4-bit path (same symmetric
    codes, formed once instead of per tile), so the same oracle and
    flash-vs-composed tolerance contract apply.
    """
    B, M, D = q.shape
    B2, N, D2 = k.shape
    assert D == D2 and k.shape == v.shape and B % B2 == 0, \
        (q.shape, k.shape, v.shape)
    rep = B // B2
    Gq, Gp = s_q.shape[0], s1.shape[0]
    assert s_k.shape == (Gq, 1) and qk_scale.shape == (Gq, 1), \
        (s_q.shape, s_k.shape, qk_scale.shape)
    assert s_v.shape == (Gp, 1) and scale1.shape == (Gp, 1) \
        and scale2.shape == (Gp, 1), (s1.shape, s_v.shape)
    half = 2 ** (bits - 1)
    bm_ = min(bm, _ceil(M))
    bn_ = kv_tile(M, N, bm) if bn is None else min(bn, _ceil(N))
    bd_ = _ceil(D)
    Mp, Np = _pad_to(M, bm_), _pad_to(N, bn_)

    g = jnp.stack([jnp.asarray(0 if g_qk is None else g_qk, jnp.int32),
                   jnp.asarray(0 if g_pv is None else g_pv, jnp.int32)])
    q = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, Mp - M), (0, bd_ - D)))
    k = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, Np - N), (0, bd_ - D)))
    v = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, Np - N), (0, bd_ - D)))

    kv_bd = bd_
    if packed_kv:
        assert bits == 4, "packed_kv streams nibbles: 4-bit codes only"
        # one-time quantize+pack pass (jnp): group-g symmetric codes,
        # two per byte along D. Padded lanes/dims are code 0 — inert.
        sk_g = jnp.take(s_k.astype(jnp.float32), g[0], axis=0)[0]
        sv_g = jnp.take(s_v.astype(jnp.float32), g[1], axis=0)[0]
        k = pack_int4(_sym_codes(k, sk_g, half), axis=-1)
        v = pack_int4(_sym_codes(v, sv_g, half), axis=-1)
        kv_bd = bd_ // 2

    has_mask = mask is not None
    operands = [q, k, v]
    in_specs = [
        pl.BlockSpec((1, bm_, bd_), lambda b, m, n, g: (b, m, 0)),
        pl.BlockSpec((1, bn_, kv_bd),
                     lambda b, m, n, g: (b // rep, n, 0)),   # shared kv
        pl.BlockSpec((1, bn_, kv_bd),
                     lambda b, m, n, g: (b // rep, n, 0)),   # shared kv
    ]
    if has_mask:
        assert mask.shape == (B, M, N), (mask.shape, (B, M, N))
        mask8 = jnp.pad(mask.astype(jnp.int8),
                        ((0, 0), (0, Mp - M), (0, Np - N)))
        operands.append(mask8)
        in_specs.append(
            pl.BlockSpec((1, bm_, bn_), lambda b, m, n, g: (b, m, n)))
    qk_row = lambda b, m, n, g: (g[0], 0, 0)                 # qk-side group
    pv_row = lambda b, m, n, g: (g[1], 0, 0)                 # pv-side group
    operands += [_stack3(p.astype(jnp.float32)) for p in
                 (s_q, s_k, qk_scale, s1, s_v, scale1, scale2)]
    in_specs += [_group_param((1,), qk_row)] * 3 \
        + [_group_param((1,), pv_row)] * 4

    # the one masking value, shared with the composed path and the oracle
    # (deferred import: repro.nn pulls in model layers at package init)
    from repro.nn.ctx import NEG_INF

    nkv = Np // bn_
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Mp // bm_, nkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm_, bd_), lambda b, m, n, g: (b, m, 0)),
        scratch_shapes=[pltpu.VMEM((bm_, 128), jnp.float32),   # running max
                        pltpu.VMEM((bm_, 128), jnp.float32),   # running denom
                        pltpu.VMEM((bm_, bd_), jnp.float32),   # region-1 acc
                        pltpu.VMEM((bm_, bd_), jnp.float32)],  # region-2 acc
    )
    out = pl.pallas_call(
        functools.partial(_flash_kernel, nkv=nkv, half=half, n_real=N,
                          bn=bn_, neg_inf=NEG_INF, has_mask=has_mask,
                          packed_kv=packed_kv, bd=bd_),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Mp, bd_), out_dtype),
        interpret=interpret,
        name="flash_attn_mrq",
    )(g, *operands)
    return out[:, :M, :D]


@functools.partial(jax.jit, static_argnames=("bits", "packed_kv", "bm", "bn",
                                             "out_dtype", "interpret"))
def flash_attn_mrq_vec(q, k, v, s_q, s_k, qk_scale, s1, s_v, scale1, scale2,
                       g_qk=None, g_pv=None, mask=None, *, bits=8,
                       packed_kv=False, bm=DEFAULT_BM, bn=None,
                       out_dtype=jnp.float32, interpret=False):
    """Vector-tgroup ``flash_attn_mrq``: per-BATCH-ROW group vectors.

    g_qk / g_pv: (B,) int32 — batch row ``b`` runs with its own groups'
    params. The kernel BODY is ``_flash_kernel`` unchanged; only the
    prefetch layout differs — the two vectors ride concatenated as one
    (2B,) prefetched array and the param index maps pick ``(g[b], 0)`` /
    ``(g[B + b], 0, 0)``, so each grid row DMAs exactly its group's (1, 1)
    param rows (the per-group gather stays in the index maps; weights —
    here the kv stream — are untouched by the group mix). Constant
    vectors are bit-identical to scalar ``g_qk``/``g_pv``.

    GQA: q rows sharing a kv row (``b // rep``) must share a group —
    true by construction when rows are slots (``ops.flash_attention``
    repeats each slot's group over its heads/query-groups); ``packed_kv``
    uses kv row ``j``'s group ``g[j * rep]`` for the one-time pack pass.
    """
    B, M, D = q.shape
    B2, N, D2 = k.shape
    assert D == D2 and k.shape == v.shape and B % B2 == 0, \
        (q.shape, k.shape, v.shape)
    rep = B // B2
    Gq, Gp = s_q.shape[0], s1.shape[0]
    assert s_k.shape == (Gq, 1) and qk_scale.shape == (Gq, 1), \
        (s_q.shape, s_k.shape, qk_scale.shape)
    assert s_v.shape == (Gp, 1) and scale1.shape == (Gp, 1) \
        and scale2.shape == (Gp, 1), (s1.shape, s_v.shape)
    half = 2 ** (bits - 1)
    bm_ = min(bm, _ceil(M))
    bn_ = kv_tile(M, N, bm) if bn is None else min(bn, _ceil(N))
    bd_ = _ceil(D)
    Mp, Np = _pad_to(M, bm_), _pad_to(N, bn_)

    gqk = (jnp.zeros((B,), jnp.int32) if g_qk is None
           else jnp.asarray(g_qk, jnp.int32).reshape(B))
    gpv = (jnp.zeros((B,), jnp.int32) if g_pv is None
           else jnp.asarray(g_pv, jnp.int32).reshape(B))
    g = jnp.concatenate([gqk, gpv])                          # (2B,)
    q = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, Mp - M), (0, bd_ - D)))
    k = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, Np - N), (0, bd_ - D)))
    v = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, Np - N), (0, bd_ - D)))

    kv_bd = bd_
    if packed_kv:
        assert bits == 4, "packed_kv streams nibbles: 4-bit codes only"
        # one-time quantize+pack pass with PER-KV-ROW group steps: kv row
        # j serves q rows [j*rep, (j+1)*rep) which share a group (slots),
        # so row j packs with g[j*rep]'s step.
        gk_kv = gqk.reshape(B2, rep)[:, 0]
        gp_kv = gpv.reshape(B2, rep)[:, 0]
        sk_g = jnp.take(s_k.astype(jnp.float32), gk_kv, axis=0)[:, :, None]
        sv_g = jnp.take(s_v.astype(jnp.float32), gp_kv, axis=0)[:, :, None]
        k = pack_int4(_sym_codes(k, sk_g, half), axis=-1)
        v = pack_int4(_sym_codes(v, sv_g, half), axis=-1)
        kv_bd = bd_ // 2

    has_mask = mask is not None
    operands = [q, k, v]
    in_specs = [
        pl.BlockSpec((1, bm_, bd_), lambda b, m, n, g: (b, m, 0)),
        pl.BlockSpec((1, bn_, kv_bd),
                     lambda b, m, n, g: (b // rep, n, 0)),   # shared kv
        pl.BlockSpec((1, bn_, kv_bd),
                     lambda b, m, n, g: (b // rep, n, 0)),   # shared kv
    ]
    if has_mask:
        assert mask.shape == (B, M, N), (mask.shape, (B, M, N))
        mask8 = jnp.pad(mask.astype(jnp.int8),
                        ((0, 0), (0, Mp - M), (0, Np - N)))
        operands.append(mask8)
        in_specs.append(
            pl.BlockSpec((1, bm_, bn_), lambda b, m, n, g: (b, m, n)))
    qk_row = lambda b, m, n, g: (g[b], 0, 0)             # row b's qk group
    pv_row = lambda b, m, n, g: (g[B + b], 0, 0)         # row b's pv group
    operands += [_stack3(p.astype(jnp.float32)) for p in
                 (s_q, s_k, qk_scale, s1, s_v, scale1, scale2)]
    in_specs += [_group_param((1,), qk_row)] * 3 \
        + [_group_param((1,), pv_row)] * 4

    from repro.nn.ctx import NEG_INF

    nkv = Np // bn_
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Mp // bm_, nkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm_, bd_), lambda b, m, n, g: (b, m, 0)),
        scratch_shapes=[pltpu.VMEM((bm_, 128), jnp.float32),   # running max
                        pltpu.VMEM((bm_, 128), jnp.float32),   # running denom
                        pltpu.VMEM((bm_, bd_), jnp.float32),   # region-1 acc
                        pltpu.VMEM((bm_, bd_), jnp.float32)],  # region-2 acc
    )
    out = pl.pallas_call(
        functools.partial(_flash_kernel, nkv=nkv, half=half, n_real=N,
                          bn=bn_, neg_inf=NEG_INF, has_mask=has_mask,
                          packed_kv=packed_kv, bd=bd_),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Mp, bd_), out_dtype),
        interpret=interpret,
        name="flash_attn_mrq_vec",
    )(g, *operands)
    return out[:, :M, :D]
