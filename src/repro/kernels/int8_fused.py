"""Fused single-pass int8 serving kernels (the deployed W8A8 hot path).

Two kernels replace the old quantize -> int8_matmul -> (add) chain:

``int8_matmul_fq``
    Takes FP activations and quantizes each row block **in VMEM**, once,
    before it is fed to the MXU — the standalone ``quantize_int8`` pass
    (an extra fp32 read + int8 write of the full activation through HBM)
    disappears. The epilogue applies the
    zero-point correction, the combined per-output-channel scale and the
    bias, so the FP result is written to HBM exactly once.

``int8_matmul_mrq_fq``
    Single-pass deployment of the MRQ two-region (PTQ4ViT-style twin
    uniform) input quantizer. The old path ran TWO full int8 matmuls
    (negative-region codes, positive-region codes) — 2x weight HBM
    traffic plus two (M, N) fp32 intermediates and an add. Here each
    weight tile is read once; the sign mask splits the activation block
    into the two region codes in VMEM and feeds TWO s32 dots, each
    epilogued with its region scale. Weight traffic halves and the
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

Tiling (``_fq_plan``, from the shapes alone): grid (M/bm, N/bn, 1) with
n innermost and ONE k step — the x block is (bm, K), the whole
contraction. Its index map ``(m, 0)`` does not move while n advances, so
each row block is fetched from HBM once (the next one is prefetched
during the last n step) and quantized once, at n == 0, into an int8
VMEM scratch (two for MRQ) that every n step of that row block reuses;
the (K, bn) weight blocks stream per n. The quantize runs over 32-row
slices of the block, so its f32 temporaries stay small at any K. A
single k step leaves the s32 dot the same integer sum as a k-tiled one,
so outputs are bit-identical to it. x enters in its stored dtype (the
f32 upcast happens in VMEM, exactly) and nothing is padded along K; M
and N are zero-padded only where the planned block does not divide
them (fusion operands pad inertly too: shift/scale with 0, prescale
with 1), and padded rows and columns are sliced away.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int8_matmul import _ceil, _group_param, _pad_to, _stack3


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


# ---------------------------------------------------------------------------
# tiling plan (shared by the four int8 fused kernels)
# ---------------------------------------------------------------------------
_ROW_TILES = (512, 256, 128, 64, 32, 16, 8)
_QUANT_ROWS = 32               # x rows per quantize slice (an s8 sublane tile)
_VMEM_BUDGET = 64 * 2 ** 20    # planned working set of one call
_VMEM_DEFAULT = 16 * 2 ** 20   # the compiler's default scoped VMEM limit
_VMEM_MAX = 96 * 2 ** 20       # of the v5e's 128 MiB


class FqPlan(NamedTuple):
    """Tiles and VMEM of one fused int8 call (``_fq_plan``)."""
    bm: int                    # x rows per block (the grid's m tile)
    bn: int                    # weight columns per block (the n tile)
    rows: int                  # x rows per quantize slice
    Mp: int                    # M, N padded to the blocks
    Np: int
    grid: tuple
    vmem_bytes: int            # planned working set
    vmem_limit_bytes: int      # passed to the compiler
    quant_passes: int          # times each x element is quantized

    @property
    def steps(self) -> int:
        return self.grid[0] * self.grid[1] * self.grid[2]


def _fq_vmem_bytes(bm, bn, K, rows, x_itemsize, mrq):
    """Planned VMEM working set of one call: double-buffered x, W, output
    and residual blocks, the code scratch, the epilogue's (bm, bn)
    values, one quantize slice's f32 temporaries and the lane-padded
    per-row columns (gv, mu, 1/sigma, row->batch), with 2 MiB for the
    small param blocks."""
    codes = 2 if mrq else 1
    return (2 * bm * K * x_itemsize + codes * bm * K + 2 * K * bn
            + 4 * bm * bn * 4 + (codes + 3) * bm * bn * 4
            + 4 * rows * K * 4 + 8 * bm * 128 * 4 + 2 * 2 ** 20)


def _col_tiles(N):
    """Weight-block widths to try, widest first: the whole of N, then the
    multiples of 128 that divide it; a ragged N that does not fit whole
    pads to 128-multiples."""
    tiles = [N] + [b for b in range(N - N % 128, 0, -128)
                   if N % b == 0 and b < N]
    return tiles + [512, 256, 128] if N % 128 else tiles


def _fq_plan(M, K, N, *, x_itemsize, mrq, rows_per_batch=None, bm=None,
             bn=None) -> FqPlan:
    """Tiles of a fused int8 call from its shapes (``bm``/``bn`` override).

    bm: one block for M <= 512 (rounded up to 8); above that, with a
    per-batch fusion (``rows_per_batch``), the largest row tile up to 512
    that divides a batch entry's rows, so the adaLN rows come from the
    index maps, else the largest of 512/256/128 dividing M. At bm >= 256
    each weight byte streamed feeds 2*bm >= 512 operations, above the
    v5e's int8 ridge (393 TOP/s over 819 GB/s, about 480), so streaming W
    once per row block does not bind.
    bn: the widest of ``_col_tiles(N)`` whose working set fits
    ``_VMEM_BUDGET`` (the next bm otherwise). Wider is faster: fewer grid
    steps, and at bn = N the weight block never moves, so W is read once
    a call.
    """
    if bm is not None:
        bms = [min(bm, _ceil(M))]
    elif M <= _ROW_TILES[0]:
        bms = [_ceil(M)]
    elif rows_per_batch and any(rows_per_batch % b == 0 for b in _ROW_TILES):
        bms = [b for b in _ROW_TILES if rows_per_batch % b == 0]
    else:
        bms = [b for b in _ROW_TILES[:3] if M % b == 0] or \
            list(_ROW_TILES[:3])
    bns = [min(bn, _ceil(N))] if bn is not None else _col_tiles(N)

    def rows_of(b):
        return _QUANT_ROWS if b % _QUANT_ROWS == 0 else b

    def need(b, c):
        return _fq_vmem_bytes(b, c, K, rows_of(b), x_itemsize, mrq)

    options = [(b, c) for b in bms for c in bns]
    bm_, bn_ = next((o for o in options if need(*o) <= _VMEM_BUDGET),
                    options[-1])
    Mp, Np = _pad_to(M, bm_), _pad_to(N, bn_)
    grid = (Mp // bm_, Np // bn_, 1)
    vmem = need(bm_, bn_)
    limit = min(max(_VMEM_DEFAULT, -(-3 * vmem // 2 // 2 ** 20) * 2 ** 20),
                _VMEM_MAX)
    # each row block is quantized at its first n step only
    quant_passes = grid[0] * bm_ // Mp
    return FqPlan(bm_, bn_, rows_of(bm_), Mp, Np, grid, vmem, limit,
                  quant_passes)


# ---------------------------------------------------------------------------
# kernel bodies: quantize the row block once, then one dot per n step
# ---------------------------------------------------------------------------
def _by_rows(bm, rows, body):
    """Run ``body(rs)`` over the row slices ``rs`` of a (bm, ·) block."""
    if rows == bm:
        body(pl.ds(0, bm))
        return

    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * rows, rows), rows))
        return carry
    jax.lax.fori_loop(0, bm // rows, step, 0)


def _row_params(grp_ref, rs, refs):
    """Activation-side params for the rows ``rs``: with a per-row group
    vector (``grp_ref`` the (bm, 1) gv block, ``rs`` None for the whole
    block) each row's group row of the full (G, ·) stacks, gathered by
    ``_gather_rows``; on the scalar-prefetch path (``grp_ref`` None) the
    group-g row the index map already fetched."""
    if grp_ref is None:
        return [r[...] for r in refs]
    gv = grp_ref[...] if rs is None else grp_ref[rs]
    return [_gather_rows(gv, r[...]) for r in refs]


def _row_fusion_refs(rs, bv_ref, mu_ref, rsig_ref):
    """The per-row fusion refs (row->batch, mu, 1/sigma) cut to ``rs``."""
    return [None if r is None else r.at[rs] for r in (bv_ref, mu_ref,
                                                      rsig_ref)]


_DOT = (((1,), (0,)), ((), ()))


def _fq_kernel(grp_ref, *refs, vec: bool, half: int, rows: int, **fusions):
    """Grid body of ``int8_matmul_fq`` / ``int8_matmul_fq_vec`` at (m, n, 0).

    Refs arrive as VMEM blocks placed by the index maps: x (bm, K) in its
    stored dtype, w (K, bn) int8, then the activation-side params — on
    the scalar-prefetch path (``vec`` False; ``grp_ref`` is the
    prefetched g, consumed by the index maps) sx/zx (1, 1) and
    scale/corr (1, bn), the group-g rows of the stacked (G, ·) arrays; on
    the vector-tgroup path ``grp_ref`` is the (bm, 1) per-row group block
    and the full (G, ·) stacks ride along, each row gathering its own
    group's params (``_row_params``). Optional fusion refs follow
    ``bias`` (``_unpack_fusion_refs`` order); ``xq_ref`` is the (bm, K)
    int8 code scratch.

    At n == 0 the fusion prologue and the quantize (the byte range is
    [-half, half-1]: 8-bit uses the full s8 range, 6-bit codes live in
    [-32, 31] inside the same bytes) fill ``xq_ref``; every n step then
    runs one s8 x s8 -> s32 MXU dot on it (the MXU takes no s32
    operands) and the epilogue: zero-point correction, combined scale,
    bias, gate+residual, one HBM write.
    """
    x_ref, w_ref, sx_ref, zx_ref, scale_ref, corr_ref, bias_ref = refs[:7]
    o_ref, xq_ref = refs[-2], refs[-1]
    ps_ref, bv_ref, mu_ref, rsig_ref, sh_ref, sc_ref, gate_ref, res_ref = \
        _unpack_fusion_refs(refs[7:-2], **fusions)
    gv_ref = grp_ref if vec else None

    @pl.when(pl.program_id(1) == 0)
    def _quantize():
        def slice_(rs):
            bv, mu, rsig = _row_fusion_refs(rs, bv_ref, mu_ref, rsig_ref)
            xf = _fusion_prologue(x_ref[rs].astype(jnp.float32), ps_ref, bv,
                                  mu, rsig, sh_ref, sc_ref)
            sx, zx = _row_params(gv_ref, rs, (sx_ref, zx_ref))
            xq_ref[rs] = jnp.clip(jnp.round(xf / sx) + zx - half,
                                  -half, half - 1).astype(jnp.int8)
        _by_rows(xq_ref.shape[0], rows, slice_)

    acc = jax.lax.dot_general(xq_ref[...], w_ref[...], _DOT,
                              preferred_element_type=jnp.int32)
    scale, corr = _row_params(gv_ref, None, (scale_ref, corr_ref))
    y = (acc - corr).astype(jnp.float32) * scale + bias_ref[...]
    y = _fusion_epilogue(y, bv_ref, gate_ref, res_ref)
    o_ref[...] = y.astype(o_ref.dtype)


def _mrq_kernel(grp_ref, *refs, vec: bool, half: int, rows: int,
                **fusions):
    """Grid body of ``int8_matmul_mrq_fq`` / ``int8_matmul_mrq_fq_vec``.

    The ``_fq_kernel`` contract with the MRQ twin-region structure: at
    n == 0 the prologue's f32 values are split by sign into two DISJOINT
    int8 code blocks (each element is zero in exactly one), ``qn_ref``
    and ``qp_ref``; every n step multiplies both against the SAME weight
    block — one VMEM-resident W read feeding two s32 dots — and the
    epilogue recombines them with their per-region scales. That is what
    collapses the old two-matmul MRQ deployment into a single W
    traversal. The fusion prologue (norm-modulate, prescale) runs BEFORE
    the sign split, so the region selection sees the same values the
    fake-quant path would.
    """
    x_ref, w_ref, sn_ref, sp_ref, scale_n_ref, scale_p_ref, bias_ref = \
        refs[:7]
    o_ref, qn_ref, qp_ref = refs[-3], refs[-2], refs[-1]
    ps_ref, bv_ref, mu_ref, rsig_ref, sh_ref, sc_ref, gate_ref, res_ref = \
        _unpack_fusion_refs(refs[7:-3], **fusions)
    gv_ref = grp_ref if vec else None

    @pl.when(pl.program_id(1) == 0)
    def _quantize():
        def slice_(rs):
            bv, mu, rsig = _row_fusion_refs(rs, bv_ref, mu_ref, rsig_ref)
            xf = _fusion_prologue(x_ref[rs].astype(jnp.float32), ps_ref, bv,
                                  mu, rsig, sh_ref, sc_ref)
            sn, sp = _row_params(gv_ref, rs, (sn_ref, sp_ref))
            neg = xf < 0
            qn_ref[rs] = jnp.where(neg, jnp.clip(jnp.round(xf / sn), -half, 0),
                                   0).astype(jnp.int8)
            qp_ref[rs] = jnp.where(neg, 0, jnp.clip(jnp.round(xf / sp), 0,
                                                    half - 1)).astype(jnp.int8)
        _by_rows(qn_ref.shape[0], rows, slice_)

    w = w_ref[...]                            # ONE weight-block read, two dots
    acc_n = jax.lax.dot_general(qn_ref[...], w, _DOT,
                                preferred_element_type=jnp.int32)
    acc_p = jax.lax.dot_general(qp_ref[...], w, _DOT,
                                preferred_element_type=jnp.int32)
    scale_n, scale_p = _row_params(gv_ref, None, (scale_n_ref, scale_p_ref))
    y = (acc_n.astype(jnp.float32) * scale_n
         + acc_p.astype(jnp.float32) * scale_p + bias_ref[...])
    y = _fusion_epilogue(y, bv_ref, gate_ref, res_ref)
    o_ref[...] = y.astype(o_ref.dtype)


def _fused_call(kernel, name, x, wq, row_params, col_params, bias, grp, *,
                vec, mrq, half, ps, nm, gr, rows_per_batch, bm, bn,
                out_dtype, interpret):
    """Plan, pad and launch one fused int8 call.

    ``row_params``: the two (G, 1) quantizer stacks (sx/zx, or the MRQ
    region steps); ``col_params``: the two (G, N) epilogue stacks
    (scale/corr, or the MRQ region scales), already in their dtypes.
    ``grp``: the scalar group g (``vec`` False, scalar-prefetched and
    gathered by the index maps) or the (M,) per-row group vector.
    """
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2, (x.shape, wq.shape)
    G = col_params[0].shape[0]
    per_batch = nm is not None or gr is not None
    plan = _fq_plan(M, K, N, x_itemsize=jnp.dtype(x.dtype).itemsize,
                    mrq=mrq, rows_per_batch=rows_per_batch if per_batch
                    else None, bm=bm, bn=bn)
    bm_, bn_, Mp, Np = plan.bm, plan.bn, plan.Mp, plan.Np

    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    fspecs, fargs, fusions = _fusions(
        x, ps, nm, gr, rows_per_batch, has_g=not vec, M=M, K=K, N=N, Mp=Mp,
        Kp=K, Np=Np, bm_=bm_, bk_=K, bn_=bn_)
    if Mp > M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    if Np > N:
        wq = jnp.pad(wq, ((0, 0), (0, Np - N)))
    row_params = [p.astype(jnp.float32) for p in row_params]
    col_params = [jnp.pad(p, ((0, 0), (0, Np - N))) for p in col_params]
    bias = jnp.pad(bias.astype(jnp.float32), (0, Np - N)).reshape(1, Np)
    out_spec = (bm_, bn_)
    scratch = [pltpu.VMEM((bm_, K), jnp.int8)] * (2 if mrq else 1)

    if vec:
        gv = jnp.pad(jnp.asarray(grp, jnp.int32), (0, Mp - M)).reshape(Mp, 1)
        grid_spec = pl.GridSpec(
            grid=plan.grid,
            in_specs=[
                pl.BlockSpec((bm_, 1), lambda m, n, k: (m, 0)),   # gv rows
                pl.BlockSpec((bm_, K), lambda m, n, k: (m, 0)),   # x block
                pl.BlockSpec((K, bn_), lambda m, n, k: (0, n)),   # W block
                pl.BlockSpec((G, 1), lambda m, n, k: (0, 0)),     # row stacks
                pl.BlockSpec((G, 1), lambda m, n, k: (0, 0)),
                pl.BlockSpec((G, bn_), lambda m, n, k: (0, n)),   # col stacks
                pl.BlockSpec((G, bn_), lambda m, n, k: (0, n)),
                pl.BlockSpec((1, bn_), lambda m, n, k: (0, n)),   # bias
            ] + fspecs,
            out_specs=pl.BlockSpec(out_spec, lambda m, n, k: (m, n)),
            scratch_shapes=scratch)
        args = (gv, x, wq, *row_params, *col_params, bias)
    else:
        # TGQ group gather: ``g`` rides as the single prefetched scalar
        # (read before the blocks stream in), and every activation-side
        # param picks its block row with ``g[0]`` — the DMA engine fetches
        # only group g's row of each stacked (G, ·) array. A traced g (the
        # tgroup inside ddpm_sample's scan) therefore changes WHICH rows
        # stream in, never the executable: one compile covers all groups.
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=plan.grid,
            in_specs=[
                pl.BlockSpec((bm_, K), lambda m, n, k, g: (m, 0)),     # x
                pl.BlockSpec((K, bn_), lambda m, n, k, g: (0, n)),     # W
                _group_param((1,), lambda m, n, k, g: (g[0], 0, 0)),   # rows
                _group_param((1,), lambda m, n, k, g: (g[0], 0, 0)),
                _group_param((bn_,), lambda m, n, k, g: (g[0], 0, n)),  # cols
                _group_param((bn_,), lambda m, n, k, g: (g[0], 0, n)),
                pl.BlockSpec((1, bn_), lambda m, n, k, g: (0, n)),     # bias
            ] + fspecs,
            out_specs=pl.BlockSpec(out_spec, lambda m, n, k, g: (m, n)),
            scratch_shapes=scratch)
        args = (jnp.asarray(grp, jnp.int32).reshape(1), x, wq,
                *map(_stack3, row_params), *map(_stack3, col_params), bias)
    out = pl.pallas_call(
        functools.partial(kernel, vec=vec, half=half, rows=plan.rows,
                          **fusions),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        # the code scratch is carried across n: only m may be split
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit_bytes),
        interpret=interpret,
        name=name,
    )(*args, *fargs)
    return out if (Mp, Np) == (M, N) else out[:M, :N]


# ---------------------------------------------------------------------------
# the four kernels: scalar-prefetch TGQ and per-row (vector-tgroup) groups
# ---------------------------------------------------------------------------
_STATIC = ("bits", "bm", "bn", "rows_per_batch", "out_dtype", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def int8_matmul_fq(x, wq, sx, zx, scale, corr, bias=None, g=None, *,
                   ps=None, nm=None, gr=None, rows_per_batch=None, bits=8,
                   bm=None, bn=None, out_dtype=jnp.float32, interpret=False):
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
    nm/gr). ``bm``/``bn`` override the planned tiles (``_fq_plan``).
    """
    G = scale.shape[0]
    N = wq.shape[1]
    assert sx.shape == (G, 1) and zx.shape == (G, 1), (sx.shape, zx.shape)
    assert corr.shape == (G, N), (corr.shape, (G, N))
    return _fused_call(
        _fq_kernel, "int8_matmul_fq", x, wq, (sx, zx),
        (scale.astype(jnp.float32), corr.astype(jnp.int32)), bias,
        0 if g is None else g, vec=False, mrq=False, half=2 ** (bits - 1),
        ps=ps, nm=nm, gr=gr, rows_per_batch=rows_per_batch, bm=bm, bn=bn,
        out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def int8_matmul_mrq_fq(x, wq, s_neg, s_pos, scale_neg, scale_pos, bias=None,
                       g=None, *, ps=None, nm=None, gr=None,
                       rows_per_batch=None, bits=8, bm=None, bn=None,
                       out_dtype=jnp.float32, interpret=False):
    """Single-pass MRQ matmul: one traversal of wq, dual s32 dots.

    y = s_neg[g]*s_w*(qn @ wq) + s_pos[g]*s_w*(qp @ wq) (+ bias) where
    qn/qp are the negative/positive two-region codes of x (disjoint
    support, selected by sign). s_neg/s_pos: (G,1) f32 region steps;
    scale_neg/scale_pos: (G,N) f32 combined region*weight scales.
    Optional ``ps``/``nm``/``gr``/``rows_per_batch`` fusions and
    ``bm``/``bn`` overrides as ``int8_matmul_fq`` (the prologue runs
    before the sign split; prescale divisors are positive, so region
    selection is unchanged).
    """
    G = scale_neg.shape[0]
    assert s_neg.shape == (G, 1) and s_pos.shape == (G, 1)
    assert scale_pos.shape == (G, wq.shape[1])
    return _fused_call(
        _mrq_kernel, "int8_matmul_mrq_fq", x, wq, (s_neg, s_pos),
        (scale_neg.astype(jnp.float32), scale_pos.astype(jnp.float32)),
        bias, 0 if g is None else g, vec=False, mrq=True,
        half=2 ** (bits - 1), ps=ps, nm=nm, gr=gr,
        rows_per_batch=rows_per_batch, bm=bm, bn=bn, out_dtype=out_dtype,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def int8_matmul_fq_vec(x, wq, sx, zx, scale, corr, bias=None, gv=None, *,
                       ps=None, nm=None, gr=None, rows_per_batch=None, bits=8,
                       bm=None, bn=None, out_dtype=jnp.float32,
                       interpret=False):
    """``int8_matmul_fq`` with a per-ROW group vector.

    gv: (M,) int32 — row i quantizes with sx[gv[i]]/zx[gv[i]] and
    dequantizes with scale[gv[i]]/corr[gv[i]]. The weight matrix streams
    once per row block for the whole mixed-group batch; the full (G, ·)
    param stacks ride along instead (G ≤ ~10, negligible next to W). A
    constant gv is bit-identical to the scalar-prefetch path. Optional
    ``ps``/``nm``/``gr``/``rows_per_batch`` fusions and ``bm``/``bn``
    overrides as ``int8_matmul_fq``.
    """
    G, N = scale.shape[0], wq.shape[1]
    assert sx.shape == (G, 1) and zx.shape == (G, 1), (sx.shape, zx.shape)
    assert corr.shape == (G, N), (corr.shape, (G, N))
    gv = jnp.zeros((x.shape[0],), jnp.int32) if gv is None else gv
    return _fused_call(
        _fq_kernel, "int8_matmul_fq_vec", x, wq, (sx, zx),
        (scale.astype(jnp.float32), corr.astype(jnp.int32)), bias, gv,
        vec=True, mrq=False, half=2 ** (bits - 1), ps=ps, nm=nm, gr=gr,
        rows_per_batch=rows_per_batch, bm=bm, bn=bn, out_dtype=out_dtype,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def int8_matmul_mrq_fq_vec(x, wq, s_neg, s_pos, scale_neg, scale_pos,
                           bias=None, gv=None, *, ps=None, nm=None, gr=None,
                           rows_per_batch=None, bits=8, bm=None, bn=None,
                           out_dtype=jnp.float32, interpret=False):
    """``int8_matmul_mrq_fq`` with a per-ROW group vector (see
    ``int8_matmul_fq_vec`` for the one-weight-stream contract)."""
    G = scale_neg.shape[0]
    assert s_neg.shape == (G, 1) and s_pos.shape == (G, 1)
    assert scale_pos.shape == (G, wq.shape[1])
    gv = jnp.zeros((x.shape[0],), jnp.int32) if gv is None else gv
    return _fused_call(
        _mrq_kernel, "int8_matmul_mrq_fq_vec", x, wq, (s_neg, s_pos),
        (scale_neg.astype(jnp.float32), scale_pos.astype(jnp.float32)),
        bias, gv, vec=True, mrq=True, half=2 ** (bits - 1), ps=ps, nm=nm,
        gr=gr, rows_per_batch=rows_per_batch, bm=bm, bn=bn,
        out_dtype=out_dtype, interpret=interpret)
