"""Fused single-pass int8 serving kernels (the deployed W8A8 hot path).

Two kernels replace the old quantize -> int8_matmul -> (add) chain:

``int8_matmul_fq``
    Takes FP activations and quantizes each (bm, bk) tile **in VMEM**
    immediately before it is fed to the MXU — the standalone
    ``quantize_int8`` pass (an extra fp32 read + int8 write of the full
    activation through HBM) disappears. The epilogue applies the
    zero-point correction, the combined per-output-channel scale and the
    bias, so the FP result is written to HBM exactly once.

``int8_matmul_mrq_fq``
    Single-pass deployment of the MRQ two-region (PTQ4ViT-style twin
    uniform) input quantizer. The old path ran TWO full int8 matmuls
    (negative-region codes, positive-region codes) — 2x weight HBM
    traffic plus two (M, N) fp32 intermediates and an add. Here each
    weight tile is read once; the sign mask splits the activation tile
    into the two region codes in VMEM and feeds TWO s32 accumulators,
    each epilogued with its region scale. Weight traffic halves and the
    intermediates never exist.

TGQ (time-grouped quantization, the paper's §III-A) lives *inside* the
kernels: every activation-side parameter is stacked along a leading
(G,) group axis and the timestep group ``g`` — a traced scalar inside
the ``ddpm_sample`` lax.scan — is scalar-prefetched; the per-group row
is gathered by the BlockSpec index maps (``(g[0], 0, n)`` over the
stacks viewed as (G, 1, ·), so a one-row block fits the TPU tiling at
any G). The whole
sampling loop therefore stays ONE compiled executable with the int8
kernels inside; no per-group repacking or retracing.

``int8_matmul_fq_vec`` / ``int8_matmul_mrq_fq_vec`` are the
**vector-tgroup** variants: instead of one scalar-prefetched group, a
per-ROW ``(M,)`` int32 group vector rides as a (M, 1) VMEM operand and
the FULL (G, ·) param stacks stream in; each row gathers its own group's
params inside the kernel with an exact select over the G groups
(``_gather_rows``; bit-exact for the f32 scales and the s32 ``corr``
alike, and no MXU pass). A batch mixing slots at different
timesteps therefore runs as ONE call that streams the weights exactly
once; a constant group vector is bit-identical to the scalar-prefetch
sibling (asserted in tests/test_kernel_conformance.py).

Prologue/epilogue fusions (shared by the whole fused-linear family,
including ``int4_packed``): every kernel optionally absorbs the fp
elementwise chains that used to round-trip through HBM around it.

``nm`` (norm-modulate prologue)
    The kernel takes the PRE-norm activation plus per-row layernorm
    stats (mu, 1/sigma — computed by the wrapper on the unpadded rows
    with the exact ``nn.layers.layernorm_apply`` ops) and the per-batch
    adaLN (shift, scale) rows; it replays ``(x - mu) * rsig`` then
    ``x * (1 + scale) + shift`` in VMEM right before the quantize, so
    the normalized/modulated tensor never exists in HBM. x rows are
    batch-major, ``rows_per_batch`` to an entry: when that is a multiple
    of the row tile, each x tile lies in one entry and the index maps
    fetch its (1, ·) rows; otherwise each row selects its entry's row in
    VMEM (``_batch_rows``).

``gr`` (gate+residual epilogue)
    The dequantized output tile is scaled by the per-batch adaLN gate
    row and added to a streamed residual tile before the single HBM
    write — the separate ``x + g[:, None, :] * o`` pass disappears.

``ps`` (channel-balance prescale prologue)
    The channel-balance ``x_prescale`` divide (``x / ps`` — a DIVIDE,
    matching the fake-quant calibration bitwise) runs in the prologue
    between the modulate and the quantize; the matching ``w * ps`` fold
    happens at pack time, so channel-balanced ops run on real kernels.

All three are static specializations (absent fusions add no operands
and leave the original kernels byte-for-byte unchanged), and all three
compose with both the scalar-prefetch and vector-tgroup group gathers —
the DDPM scan still compiles ONCE with fusions active.

Tiling matches ``int8_matmul``: grid (M/bm, N/bn, K/bk), k innermost,
MXU-aligned blocks, s32 accumulator(s) in VMEM scratch. Non-aligned
shapes are zero-padded; padded K columns of x quantize to the zero
point but meet zero-padded weight rows, so they contribute nothing
(fusion operands pad inertly too: shift/scale with 0, prescale with 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int8_matmul import (
    DEFAULT_BK, DEFAULT_BM, DEFAULT_BN, _ceil, _group_param, _pad_to, _stack3,
)


# ---------------------------------------------------------------------------
# in-VMEM row gathers (shared by the vector-tgroup and fusion paths)
# ---------------------------------------------------------------------------
def _gather_rows(idx, table):
    """Per-row gather of a (G, ·) param stack: row i takes ``table[idx[i]]``.

    ``idx`` is a (rows, 1) int32 tile, ``table`` a loaded (G, n) value. A
    static select over the G rows is exact for every dtype, whatever the
    MXU's contract precision, since it needs no MXU pass (which takes no
    s32 operands at all). Indices outside [0, G) — padded rows only —
    take row 0.
    """
    out = jnp.broadcast_to(table[0:1], (idx.shape[0], table.shape[1]))
    for g in range(1, table.shape[0]):
        out = jnp.where(idx == g, table[g:g + 1], out)
    return out


# ---------------------------------------------------------------------------
# prologue/epilogue fusion plumbing (shared with int4_packed)
# ---------------------------------------------------------------------------
def _unpack_fusion_refs(refs, *, has_ps: bool = False, has_bv: bool = False,
                        has_nm: bool = False, has_gr: bool = False):
    """Split the conditional fusion operand refs appended after ``bias``.

    Order (present-only): ps, bv, mu, rsig, shift, scale, gate, resid.
    Returns an 8-tuple with ``None`` for absent operands.
    """
    it = iter(refs)
    ps = next(it) if has_ps else None
    bv = next(it) if has_bv else None
    mu = rsig = sh = sc = None
    if has_nm:
        mu, rsig, sh, sc = next(it), next(it), next(it), next(it)
    gate = res = None
    if has_gr:
        gate, res = next(it), next(it)
    return ps, bv, mu, rsig, sh, sc, gate, res


def _batch_rows(bv_ref, ref):
    """The per-batch adaLN values for the x tile's rows: the one batch
    entry's (1, n) row the index map fetched, or — with a row->batch
    operand ``bv_ref`` — each row's entry gathered by ``_gather_rows``."""
    if bv_ref is None:
        return ref[...]
    return _gather_rows(bv_ref[...], ref[...])


def _fusion_prologue(xf, ps_ref, bv_ref, mu_ref, rsig_ref, sh_ref, sc_ref):
    """Replay, in VMEM and in the fake-quant path's exact op order, the
    elementwise chain ahead of the quantize: layernorm (per-row stats
    pre-computed by the wrapper) -> adaLN modulate (per-batch rows,
    ``_batch_rows``) -> channel-balance divide."""
    if mu_ref is not None:
        xf = (xf - mu_ref[...]) * rsig_ref[...]
        xf = xf * (1.0 + _batch_rows(bv_ref, sc_ref)) + _batch_rows(
            bv_ref, sh_ref)
    if ps_ref is not None:
        xf = xf / ps_ref[...]
    return xf


def _fusion_epilogue(y, bv_ref, gate_ref, res_ref):
    """gate+residual epilogue: y -> resid + gate_rows * y before the
    single HBM write (per-batch gate rows, ``_batch_rows``)."""
    if gate_ref is not None:
        y = res_ref[...] + _batch_rows(bv_ref, gate_ref) * y
    return y


def _fusions(x, ps, nm, gr, rows_per_batch, *, has_g: bool, M, K, N, Mp,
             Kp, Np, bm_, bk_, bn_):
    """(in_specs, operands, flags) for the optional fusion inputs, in the
    ``_unpack_fusion_refs`` order; ``flags`` are the kernel's ``has_*``
    switches.

    ps : (K,) f32 channel-balance divisors (padded with 1 — inert).
    nm : (shift, scale) per-batch (B, K) adaLN modulate rows; the
         layernorm row stats are computed HERE on the unpadded ``x``
         with the exact ``layernorm_apply`` ops (mean/var/rsqrt,
         eps=1e-6), so the fused path is bit-identical to the unfused
         norm -> modulate chain.
    gr : (gate, resid) — (B, N) gate rows + (M, N) residual.
    rows_per_batch : x rows per batch entry (rows are batch-major),
         required by nm/gr. When it is a multiple of ``bm_`` every x tile
         lies in one batch entry, and the index maps fetch that entry's
         (1, ·) shift/scale/gate rows from the stacks viewed as (B, 1, ·).
         Otherwise a (M, 1) row->batch operand rides along and the kernel
         selects each row's entry from the whole (B, ·) stacks.
    ``has_g`` selects index-map arity (scalar-prefetch grids take a
    trailing g argument).
    """
    f32 = jnp.float32

    def im(f):
        return (lambda m, n, k, g: f(m, n, k)) if has_g else f
    per_batch = nm is not None or gr is not None
    if per_batch:
        assert rows_per_batch and M % rows_per_batch == 0, \
            ("norm_mod/gate_residual need rows_per_batch dividing M",
             rows_per_batch, M)
    tiled = per_batch and rows_per_batch % bm_ == 0
    specs, args = [], []

    def batch_stack(a, width, pad, col):
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad)))
        if tiled:              # x tile m lies in batch entry m // per_entry
            per_entry = rows_per_batch // bm_
            return _group_param((width,), im(
                lambda m, n, k: (m // per_entry, 0, col(n, k)))), _stack3(a)
        return pl.BlockSpec((a.shape[0], width),
                            im(lambda m, n, k: (0, col(n, k)))), a

    def rows_col(a):
        return (pl.BlockSpec((bm_, 1), im(lambda m, n, k: (m, 0))),
                jnp.pad(a, ((0, Mp - M), (0, 0))))

    if ps is not None:
        specs.append(pl.BlockSpec((1, bk_), im(lambda m, n, k: (0, k))))
        args.append(jnp.pad(jnp.asarray(ps, f32).reshape(1, K),
                            ((0, 0), (0, Kp - K)), constant_values=1.0))
    if per_batch and not tiled:
        bv = jnp.repeat(jnp.arange(M // rows_per_batch, dtype=jnp.int32),
                        rows_per_batch)
        spec, arg = rows_col(bv.reshape(M, 1))
        specs.append(spec)
        args.append(arg)
    if nm is not None:
        sh, sc = nm
        xf = x.astype(f32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        rsig = jax.lax.rsqrt(jnp.var(xf, axis=-1, keepdims=True) + 1e-6)
        for spec, arg in (rows_col(mu), rows_col(rsig),
                          batch_stack(sh, bk_, Kp - K, lambda n, k: k),
                          batch_stack(sc, bk_, Kp - K, lambda n, k: k)):
            specs.append(spec)
            args.append(arg)
    if gr is not None:
        gate, res = gr
        spec, arg = batch_stack(gate, bn_, Np - N, lambda n, k: n)
        specs += [spec, pl.BlockSpec((bm_, bn_), im(lambda m, n, k: (m, n)))]
        args += [arg, jnp.pad(res.astype(f32), ((0, Mp - M), (0, Np - N)))]
    flags = dict(has_ps=ps is not None, has_bv=per_batch and not tiled,
                 has_nm=nm is not None, has_gr=gr is not None)
    return specs, args, flags


def _fq_kernel(g_ref, *refs, nk: int, half: int, **fusions):
    """Grid body for ``int8_matmul_fq`` at grid point (m, n, k).

    Refs arrive as VMEM tiles already gathered by the BlockSpec index
    maps: x (bm, bk) fp32, w (bk, bn) int8, and the TGQ-resolved rows of
    the activation-side params — sx/zx (1, 1) and scale/corr (1, bn) are
    the group-``g`` slices of the stacked (G, ·) arrays (see the
    ``(g[0], 0, n)`` index maps below), so the body itself is
    group-agnostic. Both dot operands are s8 (the MXU multiplies s8 x s8
    into s32; it takes no s32 operands).
    ``acc_ref`` is a persistent (bm, bn) s32 scratch: zeroed at k == 0,
    accumulated over the K-traversal (k innermost), epilogued at
    k == nk - 1. ``g_ref`` itself is unused here — prefetched scalars
    exist to feed index maps. Optional fusion refs follow ``bias``
    (``_unpack_fusion_refs`` order); absent fusions leave the body
    identical to the unfused original.
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

    # fused-quantize prologue: fp tile -> signed codes in VMEM (the byte
    # range is [-half, half-1] — 8-bit uses the full s8 range, 6-bit
    # codes live in [-32, 31] inside the same int8 bytes)
    sx = sx_ref[0, 0]
    zx = zx_ref[0, 0]
    xf = _fusion_prologue(x_ref[...].astype(jnp.float32), ps_ref, bv_ref,
                          mu_ref, rsig_ref, sh_ref, sc_ref)
    xq = jnp.clip(jnp.round(xf / sx) + zx - half,
                  -half, half - 1).astype(jnp.int8)
    acc_ref[...] += jax.lax.dot_general(
        xq, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...] - corr_ref[...]
        y = acc.astype(jnp.float32) * scale_ref[...] + bias_ref[...]
        y = _fusion_epilogue(y, bv_ref, gate_ref, res_ref)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "rows_per_batch", "out_dtype",
                                             "interpret"))
def int8_matmul_fq(x, wq, sx, zx, scale, corr, bias=None, g=None, *,
                   ps=None, nm=None, gr=None, rows_per_batch=None, bits=8,
                   bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK,
                   out_dtype=jnp.float32, interpret=False):
    """y[M,N] = (q(x; sx[g], zx[g]) @ wq - corr[g]) * scale[g] (+ bias).

    x: (M,K) float, wq: (K,N) int8. Activation-side params are stacked
    along a leading TGQ group axis: sx/zx (G,1) f32, scale (G,N) f32
    (s_x[g]*s_w per channel), corr (G,N) i32 (z_eff[g]*colsum(wq)).
    g is the group index — python int or traced scalar (scalar-prefetched,
    gathered by the BlockSpec index maps; no retrace across groups).
    ``bits`` sets the code range (8 -> [-128, 127], 6 -> [-32, 31]);
    sub-byte widths keep byte storage here — the nibble-PACKED weight
    path lives in ``int4_packed``.

    Optional fusions (see module docstring): ``ps`` (K,) channel-balance
    divisors, ``nm=(shift, scale)`` (B,K) adaLN modulate rows (x must be
    PRE-norm), ``gr=(gate, resid)`` ((B,N), (M,N)) gate+residual
    epilogue, ``rows_per_batch`` x rows per batch entry (required by
    nm/gr).
    """
    half = 2 ** (bits - 1)
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2, (x.shape, wq.shape)
    G = scale.shape[0]
    assert sx.shape == (G, 1) and zx.shape == (G, 1), (sx.shape, zx.shape)
    assert corr.shape == (G, N), (corr.shape, (G, N))
    bm_, bn_, bk_ = min(bm, _ceil(M)), min(bn, _ceil(N)), min(bk, _ceil(K))
    Mp, Np, Kp = _pad_to(M, bm_), _pad_to(N, bn_), _pad_to(K, bk_)

    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    if g is None:
        g = 0
    fspecs, fargs, fusions = _fusions(
        x, ps, nm, gr, rows_per_batch, has_g=True, M=M, K=K, N=N, Mp=Mp,
        Kp=Kp, Np=Np, bm_=bm_, bk_=bk_, bn_=bn_)
    x = jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, Kp - K)))
    wq = jnp.pad(wq, ((0, Kp - K), (0, Np - N)))
    scale = jnp.pad(scale.astype(jnp.float32), ((0, 0), (0, Np - N)))
    corr = jnp.pad(corr.astype(jnp.int32), ((0, 0), (0, Np - N)))
    bias = jnp.pad(bias.astype(jnp.float32), (0, Np - N)).reshape(1, Np)

    nk = Kp // bk_
    grid = (Mp // bm_, Np // bn_, nk)
    # TGQ group gather: ``g`` rides as the single prefetched scalar (it is
    # read on the HOST side of the pipeline, before tiles stream in), and
    # every activation-side param picks its block row with ``g[0]`` — the
    # DMA engine fetches only group g's row of each stacked (G, ·) array.
    # A traced g (the tgroup inside ddpm_sample's scan) therefore changes
    # WHICH rows stream in, never the executable: one compile covers all
    # timestep groups.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda m, n, k, g: (m, k)),    # x tile
            pl.BlockSpec((bk_, bn_), lambda m, n, k, g: (k, n)),    # W tile
            _group_param((1,), lambda m, n, k, g: (g[0], 0, 0)),    # sx[g]
            _group_param((1,), lambda m, n, k, g: (g[0], 0, 0)),    # zx[g]
            _group_param((bn_,), lambda m, n, k, g: (g[0], 0, n)),  # scale[g]
            _group_param((bn_,), lambda m, n, k, g: (g[0], 0, n)),  # corr[g]
            pl.BlockSpec((1, bn_), lambda m, n, k, g: (0, n)),      # bias
        ] + fspecs,
        out_specs=pl.BlockSpec((bm_, bn_), lambda m, n, k, g: (m, n)),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_fq_kernel, nk=nk, half=half,
                          **fusions),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        interpret=interpret,
        name="int8_matmul_fq",
    )(jnp.asarray(g, jnp.int32).reshape(1), x, wq,
      _stack3(sx.astype(jnp.float32)), _stack3(zx.astype(jnp.float32)),
      _stack3(scale), _stack3(corr), bias, *fargs)
    return out[:M, :N]


def _mrq_kernel(g_ref, *refs, nk: int, half: int, **fusions):
    """Grid body for ``int8_matmul_mrq_fq`` at grid point (m, n, k).

    Same tiling/prefetch contract as ``_fq_kernel`` (group-``g`` rows of
    the stacked (G, ·) params are pre-gathered by the index maps), but
    with the MRQ twin-region structure: the fp32 x tile is split by sign
    into two DISJOINT int8 code tiles (each element is zero in exactly
    one), both multiplied against the SAME weight tile — one VMEM-resident
    W read feeding two s32 accumulators — and the epilogue recombines them
    with their per-region scales. That is what collapses the old
    two-matmul MRQ deployment into a single W traversal. The fusion
    prologue (norm-modulate, prescale) runs BEFORE the sign split, so the
    region selection sees the same values the fake-quant path would.
    """
    del g_ref
    x_ref, w_ref, sn_ref, sp_ref, scale_n_ref, scale_p_ref, bias_ref = \
        refs[:7]
    o_ref, acc_n_ref, acc_p_ref = refs[-3], refs[-2], refs[-1]
    ps_ref, bv_ref, mu_ref, rsig_ref, sh_ref, sc_ref, gate_ref, res_ref = \
        _unpack_fusion_refs(refs[7:-3], **fusions)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_n_ref[...] = jnp.zeros_like(acc_n_ref)
        acc_p_ref[...] = jnp.zeros_like(acc_p_ref)

    # region split in VMEM: sign mask -> two disjoint int8 code tiles
    xf = _fusion_prologue(x_ref[...].astype(jnp.float32), ps_ref, bv_ref,
                          mu_ref, rsig_ref, sh_ref, sc_ref)
    neg = xf < 0
    qn = jnp.where(neg, jnp.clip(jnp.round(xf / sn_ref[0, 0]), -half, 0),
                   0).astype(jnp.int8)
    qp = jnp.where(neg, 0, jnp.clip(jnp.round(xf / sp_ref[0, 0]), 0, half - 1)
                   ).astype(jnp.int8)
    w = w_ref[...]                            # ONE weight-tile read, two dots
    dims = (((1,), (0,)), ((), ()))
    acc_n_ref[...] += jax.lax.dot_general(qn, w, dims,
                                          preferred_element_type=jnp.int32)
    acc_p_ref[...] += jax.lax.dot_general(qp, w, dims,
                                          preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        y = (acc_n_ref[...].astype(jnp.float32) * scale_n_ref[...]
             + acc_p_ref[...].astype(jnp.float32) * scale_p_ref[...]
             + bias_ref[...])
        y = _fusion_epilogue(y, bv_ref, gate_ref, res_ref)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "rows_per_batch", "out_dtype",
                                             "interpret"))
def int8_matmul_mrq_fq(x, wq, s_neg, s_pos, scale_neg, scale_pos, bias=None,
                       g=None, *, ps=None, nm=None, gr=None,
                       rows_per_batch=None, bits=8,
                       bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK,
                       out_dtype=jnp.float32, interpret=False):
    """Single-pass MRQ matmul: one traversal of wq, dual s32 accumulators.

    y = s_neg[g]*s_w*(qn @ wq) + s_pos[g]*s_w*(qp @ wq) (+ bias) where
    qn/qp are the negative/positive two-region codes of x (disjoint
    support, selected by sign). s_neg/s_pos: (G,1) f32 region steps;
    scale_neg/scale_pos: (G,N) f32 combined region*weight scales.
    Optional ``ps``/``nm``/``gr``/``rows_per_batch`` fusions as
    ``int8_matmul_fq`` (the prologue runs before the sign split; prescale
    divisors are positive, so region selection is unchanged).
    """
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2, (x.shape, wq.shape)
    G = scale_neg.shape[0]
    assert s_neg.shape == (G, 1) and s_pos.shape == (G, 1)
    assert scale_pos.shape == (G, N)
    half = 2 ** (bits - 1)
    bm_, bn_, bk_ = min(bm, _ceil(M)), min(bn, _ceil(N)), min(bk, _ceil(K))
    Mp, Np, Kp = _pad_to(M, bm_), _pad_to(N, bn_), _pad_to(K, bk_)

    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    if g is None:
        g = 0
    fspecs, fargs, fusions = _fusions(
        x, ps, nm, gr, rows_per_batch, has_g=True, M=M, K=K, N=N, Mp=Mp,
        Kp=Kp, Np=Np, bm_=bm_, bk_=bk_, bn_=bn_)
    x = jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, Kp - K)))
    wq = jnp.pad(wq, ((0, Kp - K), (0, Np - N)))
    scale_neg = jnp.pad(scale_neg.astype(jnp.float32), ((0, 0), (0, Np - N)))
    scale_pos = jnp.pad(scale_pos.astype(jnp.float32), ((0, 0), (0, Np - N)))
    bias = jnp.pad(bias.astype(jnp.float32), (0, Np - N)).reshape(1, Np)

    nk = Kp // bk_
    grid = (Mp // bm_, Np // bn_, nk)
    # Same scalar-prefetch group gather as int8_matmul_fq (see the comment
    # there); here the gathered rows are the two region step sizes and the
    # two combined region*weight scale rows.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda m, n, k, g: (m, k)),    # x tile
            pl.BlockSpec((bk_, bn_), lambda m, n, k, g: (k, n)),    # W tile
            _group_param((1,), lambda m, n, k, g: (g[0], 0, 0)),    # s_neg[g]
            _group_param((1,), lambda m, n, k, g: (g[0], 0, 0)),    # s_pos[g]
            _group_param((bn_,), lambda m, n, k, g: (g[0], 0, n)),  # scale_neg
            _group_param((bn_,), lambda m, n, k, g: (g[0], 0, n)),  # scale_pos
            pl.BlockSpec((1, bn_), lambda m, n, k, g: (0, n)),      # bias
        ] + fspecs,
        out_specs=pl.BlockSpec((bm_, bn_), lambda m, n, k, g: (m, n)),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32),
                        pltpu.VMEM((bm_, bn_), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_mrq_kernel, nk=nk, half=half,
                          **fusions),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        interpret=interpret,
        name="int8_matmul_mrq_fq",
    )(jnp.asarray(g, jnp.int32).reshape(1), x, wq,
      _stack3(s_neg.astype(jnp.float32)), _stack3(s_pos.astype(jnp.float32)),
      _stack3(scale_neg), _stack3(scale_pos), bias, *fargs)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# vector-tgroup variants: per-ROW group indices, one weight stream
# ---------------------------------------------------------------------------
def _fq_vec_kernel(gv_ref, *refs, nk: int, half: int, **fusions):
    """Vector-tgroup body: same math as ``_fq_kernel`` but each ROW of the
    x tile quantizes/dequantizes with its own group's params, gathered
    in VMEM from the full (G, ·) stacks (no scalar prefetch, no per-group
    weight re-stream)."""
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
    acc_ref[...] += jax.lax.dot_general(
        xq, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        gv = gv_ref[...]
        scale_row = _gather_rows(gv, scale_ref[...])           # (bm, bn)
        corr_row = _gather_rows(gv, corr_ref[...])             # (bm, bn)
        acc = acc_ref[...] - corr_row
        y = acc.astype(jnp.float32) * scale_row + bias_ref[...]
        y = _fusion_epilogue(y, bv_ref, gate_ref, res_ref)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "rows_per_batch", "out_dtype",
                                             "interpret"))
def int8_matmul_fq_vec(x, wq, sx, zx, scale, corr, bias=None, gv=None, *,
                       ps=None, nm=None, gr=None, rows_per_batch=None, bits=8,
                       bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK,
                       out_dtype=jnp.float32, interpret=False):
    """``int8_matmul_fq`` with a per-ROW group vector.

    gv: (M,) int32 — row i quantizes with sx[gv[i]]/zx[gv[i]] and
    dequantizes with scale[gv[i]]/corr[gv[i]]. The weight matrix streams
    ONCE for the whole mixed-group batch; the full (G, ·) param stacks
    ride along instead (G ≤ ~10, negligible next to W). A constant gv is
    bit-identical to the scalar-prefetch path. Optional ``ps``/``nm``/
    ``gr``/``rows_per_batch`` fusions as ``int8_matmul_fq``.
    """
    half = 2 ** (bits - 1)
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2, (x.shape, wq.shape)
    G = scale.shape[0]
    assert sx.shape == (G, 1) and zx.shape == (G, 1), (sx.shape, zx.shape)
    assert corr.shape == (G, N), (corr.shape, (G, N))
    bm_, bn_, bk_ = min(bm, _ceil(M)), min(bn, _ceil(N)), min(bk, _ceil(K))
    Mp, Np, Kp = _pad_to(M, bm_), _pad_to(N, bn_), _pad_to(K, bk_)

    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    if gv is None:
        gv = jnp.zeros((M,), jnp.int32)
    gv = jnp.pad(jnp.asarray(gv, jnp.int32), (0, Mp - M)).reshape(Mp, 1)
    fspecs, fargs, fusions = _fusions(
        x, ps, nm, gr, rows_per_batch, has_g=False, M=M, K=K, N=N, Mp=Mp,
        Kp=Kp, Np=Np, bm_=bm_, bk_=bk_, bn_=bn_)
    x = jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, Kp - K)))
    wq = jnp.pad(wq, ((0, Kp - K), (0, Np - N)))
    scale = jnp.pad(scale.astype(jnp.float32), ((0, 0), (0, Np - N)))
    corr = jnp.pad(corr.astype(jnp.int32), ((0, 0), (0, Np - N)))
    bias = jnp.pad(bias.astype(jnp.float32), (0, Np - N)).reshape(1, Np)

    nk = Kp // bk_
    grid = (Mp // bm_, Np // bn_, nk)
    out = pl.pallas_call(
        functools.partial(_fq_vec_kernel, nk=nk, half=half,
                          **fusions),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, 1), lambda m, n, k: (m, 0)),     # gv rows
            pl.BlockSpec((bm_, bk_), lambda m, n, k: (m, k)),   # x tile
            pl.BlockSpec((bk_, bn_), lambda m, n, k: (k, n)),   # W tile
            pl.BlockSpec((G, 1), lambda m, n, k: (0, 0)),       # sx stack
            pl.BlockSpec((G, 1), lambda m, n, k: (0, 0)),       # zx stack
            pl.BlockSpec((G, bn_), lambda m, n, k: (0, n)),     # scale stack
            pl.BlockSpec((G, bn_), lambda m, n, k: (0, n)),     # corr stack
            pl.BlockSpec((1, bn_), lambda m, n, k: (0, n)),     # bias
        ] + fspecs,
        out_specs=pl.BlockSpec((bm_, bn_), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        interpret=interpret,
        name="int8_matmul_fq_vec",
    )(gv, x, wq, sx.astype(jnp.float32), zx.astype(jnp.float32),
      scale, corr, bias, *fargs)
    return out[:M, :N]


def _mrq_vec_kernel(gv_ref, *refs, nk: int, half: int, **fusions):
    """Vector-tgroup body for the MRQ twin-region matmul: per-row region
    steps from ``_gather_rows``, one W read feeding both accumulators."""
    x_ref, w_ref, sn_ref, sp_ref, scale_n_ref, scale_p_ref, bias_ref = \
        refs[:7]
    o_ref, acc_n_ref, acc_p_ref = refs[-3], refs[-2], refs[-1]
    ps_ref, bv_ref, mu_ref, rsig_ref, sh_ref, sc_ref, gate_ref, res_ref = \
        _unpack_fusion_refs(refs[7:-3], **fusions)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_n_ref[...] = jnp.zeros_like(acc_n_ref)
        acc_p_ref[...] = jnp.zeros_like(acc_p_ref)

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
    w = w_ref[...]                            # ONE weight-tile read, two dots
    dims = (((1,), (0,)), ((), ()))
    acc_n_ref[...] += jax.lax.dot_general(qn, w, dims,
                                          preferred_element_type=jnp.int32)
    acc_p_ref[...] += jax.lax.dot_general(qp, w, dims,
                                          preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        gv = gv_ref[...]
        scale_n_row = _gather_rows(gv, scale_n_ref[...])
        scale_p_row = _gather_rows(gv, scale_p_ref[...])
        y = (acc_n_ref[...].astype(jnp.float32) * scale_n_row
             + acc_p_ref[...].astype(jnp.float32) * scale_p_row
             + bias_ref[...])
        y = _fusion_epilogue(y, bv_ref, gate_ref, res_ref)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "rows_per_batch", "out_dtype",
                                             "interpret"))
def int8_matmul_mrq_fq_vec(x, wq, s_neg, s_pos, scale_neg, scale_pos,
                           bias=None, gv=None, *, ps=None, nm=None, gr=None,
                           rows_per_batch=None, bits=8, bm=DEFAULT_BM,
                           bn=DEFAULT_BN, bk=DEFAULT_BK,
                           out_dtype=jnp.float32, interpret=False):
    """``int8_matmul_mrq_fq`` with a per-ROW group vector (see
    ``int8_matmul_fq_vec`` for the one-weight-read contract)."""
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2, (x.shape, wq.shape)
    G = scale_neg.shape[0]
    assert s_neg.shape == (G, 1) and s_pos.shape == (G, 1)
    assert scale_pos.shape == (G, N)
    half = 2 ** (bits - 1)
    bm_, bn_, bk_ = min(bm, _ceil(M)), min(bn, _ceil(N)), min(bk, _ceil(K))
    Mp, Np, Kp = _pad_to(M, bm_), _pad_to(N, bn_), _pad_to(K, bk_)

    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    if gv is None:
        gv = jnp.zeros((M,), jnp.int32)
    gv = jnp.pad(jnp.asarray(gv, jnp.int32), (0, Mp - M)).reshape(Mp, 1)
    fspecs, fargs, fusions = _fusions(
        x, ps, nm, gr, rows_per_batch, has_g=False, M=M, K=K, N=N, Mp=Mp,
        Kp=Kp, Np=Np, bm_=bm_, bk_=bk_, bn_=bn_)
    x = jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, Kp - K)))
    wq = jnp.pad(wq, ((0, Kp - K), (0, Np - N)))
    scale_neg = jnp.pad(scale_neg.astype(jnp.float32), ((0, 0), (0, Np - N)))
    scale_pos = jnp.pad(scale_pos.astype(jnp.float32), ((0, 0), (0, Np - N)))
    bias = jnp.pad(bias.astype(jnp.float32), (0, Np - N)).reshape(1, Np)

    nk = Kp // bk_
    grid = (Mp // bm_, Np // bn_, nk)
    out = pl.pallas_call(
        functools.partial(_mrq_vec_kernel, nk=nk, half=half,
                          **fusions),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, 1), lambda m, n, k: (m, 0)),     # gv rows
            pl.BlockSpec((bm_, bk_), lambda m, n, k: (m, k)),   # x tile
            pl.BlockSpec((bk_, bn_), lambda m, n, k: (k, n)),   # W tile
            pl.BlockSpec((G, 1), lambda m, n, k: (0, 0)),       # s_neg stack
            pl.BlockSpec((G, 1), lambda m, n, k: (0, 0)),       # s_pos stack
            pl.BlockSpec((G, bn_), lambda m, n, k: (0, n)),     # scale_neg
            pl.BlockSpec((G, bn_), lambda m, n, k: (0, n)),     # scale_pos
            pl.BlockSpec((1, bn_), lambda m, n, k: (0, n)),     # bias
        ] + fspecs,
        out_specs=pl.BlockSpec((bm_, bn_), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32),
                        pltpu.VMEM((bm_, bn_), jnp.int32)],
        interpret=interpret,
        name="int8_matmul_mrq_fq_vec",
    )(gv, x, wq, s_neg.astype(jnp.float32), s_pos.astype(jnp.float32),
      scale_neg, scale_pos, bias, *fargs)
    return out[:M, :N]
