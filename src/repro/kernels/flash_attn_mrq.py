"""Flash-style fused int8 MRQ attention: QK^T -> online softmax -> MRQ
prob codes -> P·V in ONE Pallas kernel — the (S, S) scores and prob-code
tensors never touch HBM.

The composed int8 attention path (``int8_bmm_qk`` -> ``softmax_mrq_codes``
-> ``int8_bmm_pv``) serves fully int8 but still round-trips the full
(BH, S, S) f32 scores and int8 prob codes through HBM — the dominant
remaining attention traffic. This kernel streams K/V tiles per
(batch·head, q-tile) grid point and keeps the whole quadratic
intermediate in VMEM:

1. **int8 QK^T** — q, k and v arrive as symmetric s8 codes at the
   group-``g`` steps (same ``SymQ`` contract as ``int8_bmm_qk``), formed
   ONCE per call before the kernel (``group_codes``; ``ops.flash_attention``
   forms them ahead of its head transposes, so XLA fuses the quantize into
   the relayout of the qkv output and the transposes move s8). The
   s32 MXU product dequantizes with the combined ``s_q[g]·s_k[g]·alpha``
   scale into an f32 (bm, bn) score tile that never leaves VMEM.
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
   multiplies the v code tile on the MXU into an s32 product,
   accumulated into two f32 region accumulators with the flash
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

Traffic: the layout pass reads q, k and v once in their stored dtype
and writes s8 codes (nibble-packed k/v at 4 bits); the kernel reads the
q codes once, writes the output once, and re-fetches the k/v codes once
per q-tile (the standard flash trade: ``ceil(M/bm)`` reads each — one at
256 tokens, where the default q-tile ``bm = 256`` covers the sequence,
four at 1,024). The (S, S) scores/codes round-trip — ``BH·S²·10`` bytes
on the composed path — is eliminated entirely: ≥3x whole-attention
traffic cut at DiT-XL/2 shapes
(``benchmarks/kernel_micro.py::traffic_attention_flash`` charges the kv
re-reads honestly).

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
from repro.kernels.int8_bmm import _sym_codes
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


def group_codes(x, s, g, bits: int):
    """x -> symmetric s8 codes at group ``g``'s step of the (G, 1) stack
    ``s``; ``g`` is a scalar, or a vector over x's leading axis (each row
    or slot at its own group)."""
    step = jnp.take(s.astype(jnp.float32)[:, 0], g, axis=0)
    step = step.reshape(step.shape + (1,) * (x.ndim - step.ndim))
    return _sym_codes(x, step, 2 ** (bits - 1))


def _code_pass(q, k, v, s_q, s_k, s_v, g_q, g_k, g_v, *, bits: int,
               mp: int, np_: int, bd: int, packed_kv: bool):
    """The layout pass before the kernel: float q/k/v -> s8 codes at their
    groups' steps (``group_codes``; s8 inputs are codes already), padded
    to the kernel's blocks with code 0, which is inert. ``packed_kv`` then
    nibble-packs the k/v codes along D."""
    def codes(x, s, g, rows):
        c = x if x.dtype == jnp.int8 else group_codes(x, s, g, bits)
        return jnp.pad(c, ((0, 0), (0, rows - x.shape[1]),
                           (0, bd - x.shape[2])))

    q8, k8, v8 = (codes(q, s_q, g_q, mp), codes(k, s_k, g_k, np_),
                  codes(v, s_v, g_v, np_))
    if packed_kv:
        k8, v8 = pack_int4(k8, axis=-1), pack_int4(v8, axis=-1)
    return q8, k8, v8


def _flash_kernel(g_ref, *refs, nkv: int, half: int, n_real: int, bn: int,
                  neg_inf: float, has_mask: bool, packed_kv: bool = False,
                  bd: int = 0):
    """Grid body at (b, m, n) — n (the kv tile) innermost.

    ``refs`` unpacks to the s8 code tiles (q, k, v[, mask8]), the
    group-``g`` rows of the stacked (G, 1) params (qk_scale, s1, scale1,
    scale2), the output ref and the four VMEM scratch refs (running max /
    denominator as (bm, 128) lane-broadcast stats, two (bm, D) f32 region
    accumulators). ``g_ref`` ([g_qk, g_pv]) feeds the index maps only.

    ``packed_kv``: k/v tiles arrive as (bn, bd/2) nibble-packed 4-bit
    codes and are widened to s8 here, halving the kv bytes streamed per
    q-tile.
    """
    del g_ref
    if has_mask:
        (q_ref, k_ref, v_ref, mask_ref, qs_ref, s1_ref, sc1_ref, sc2_ref,
         o_ref, m_ref, l_ref, acc1_ref, acc2_ref) = refs
    else:
        (q_ref, k_ref, v_ref, qs_ref, s1_ref, sc1_ref, sc2_ref, o_ref,
         m_ref, l_ref, acc1_ref, acc2_ref) = refs
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc1_ref[...] = jnp.zeros_like(acc1_ref)
        acc2_ref[...] = jnp.zeros_like(acc2_ref)

    k8, v8 = k_ref[0], v_ref[0]
    if packed_kv:                # widen two-nibbles-per-byte codes in VMEM
        k8, v8 = (jnp.stack(nibble_split(c), axis=2).reshape(
            c.shape[0], bd).astype(jnp.int8) for c in (k8, v8))

    # -- int8 QK^T for this tile (scores stay in VMEM) ----------------------
    s = jax.lax.dot_general(
        q_ref[0], k8, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32) * qs_ref[0, 0]

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
    # MXU negated, and their product is subtracted
    c2n = jnp.where(region1, 0.0, -jnp.clip(jnp.round(p / s2), 0, half)
                    ).astype(jnp.int8)

    # -- dual-region P·V with fp running-rescale ----------------------------
    dims = (((1,), (0,)), ((), ()))                  # ONE v-tile read
    d1 = jax.lax.dot_general(c1, v8, dims, preferred_element_type=jnp.int32)
    d2n = jax.lax.dot_general(c2n, v8, dims, preferred_element_type=jnp.int32)
    rho = corr * l_prev / l_new                      # <= 1; 0 at n == 0
    acc1_ref[...] = acc1_ref[...] * rho + d1.astype(jnp.float32)
    acc2_ref[...] = acc2_ref[...] * rho - d2n.astype(jnp.float32)
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

    q: (B, M, D); k, v: (Bk, N, D) with B = rep · Bk (GQA —
    the shared kv head is gathered via a ``b // rep`` index map).
    s_q/s_k: (Gq, 1) f32 symmetric steps; qk_scale: (Gq, 1) combined
    ``s_q[g]·s_k[g]·alpha`` (alpha = the softmax scale, folded by the
    caller). s1/s_v/scale1/scale2: (Gp, 1) f32 — the ``int8_pv`` pack
    params (``scale1 = s1·s_v``, ``scale2 = s2·s_v``). g_qk / g_pv: the
    TGQ groups for each pack side — python ints or traced scalars
    (scalar-prefetched together; no retrace across groups). mask:
    optional (B, M, N) boolean (True = attend), streamed as int8 tiles.

    The kernel streams s8 codes: float q, k and v are quantized at their
    group's steps in ONE layout pass before it (``_code_pass``), and s8
    q, k and v are taken as those codes already (``group_codes``: how
    ``ops.flash_attention`` hands them over, formed before its head
    transposes). ``packed_kv`` (4-bit only) nibble-packs the k/v codes
    along D in the same pass, so the kernel streams half the kv bytes per
    q-tile and widens nibbles in VMEM. Either way the codes are the same
    symmetric codes, so one oracle and one flash-vs-composed tolerance
    contract apply.
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
    assert bits == 4 or not packed_kv, "packed_kv: 4-bit codes only"
    half = 2 ** (bits - 1)
    bm_ = min(bm, _ceil(M))
    bn_ = kv_tile(M, N, bm) if bn is None else min(bn, _ceil(N))
    bd_ = _ceil(D)
    Mp, Np = _pad_to(M, bm_), _pad_to(N, bn_)

    g = jnp.stack([jnp.asarray(0 if g_qk is None else g_qk, jnp.int32),
                   jnp.asarray(0 if g_pv is None else g_pv, jnp.int32)])
    q, k, v = _code_pass(q, k, v, s_q, s_k, s_v, g[0], g[0], g[1],
                         bits=bits, mp=Mp, np_=Np, bd=bd_,
                         packed_kv=packed_kv)
    kv_bd = bd_ // 2 if packed_kv else bd_

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
                 (qk_scale, s1, scale1, scale2)]
    in_specs += [_group_param((1,), qk_row)] \
        + [_group_param((1,), pv_row)] * 3

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
    repeats each slot's group over its heads/query-groups); the layout
    pass codes kv row ``j`` at group ``g[j * rep]``'s steps.
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
    assert bits == 4 or not packed_kv, "packed_kv: 4-bit codes only"
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
    # kv row j serves q rows [j*rep, (j+1)*rep), which share a group
    # (slots), so it takes row j*rep's steps
    gk, gv = gqk.reshape(B2, rep)[:, 0], gpv.reshape(B2, rep)[:, 0]
    q, k, v = _code_pass(q, k, v, s_q, s_k, s_v, gqk, gk, gv, bits=bits,
                         mp=Mp, np_=Np, bd=bd_, packed_kv=packed_kv)
    kv_bd = bd_ // 2 if packed_kv else bd_

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
                 (qk_scale, s1, scale1, scale2)]
    in_specs += [_group_param((1,), qk_row)] \
        + [_group_param((1,), pv_row)] * 3

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
