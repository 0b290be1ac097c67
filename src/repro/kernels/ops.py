"""Public jit'd wrappers around the Pallas kernels + the int8 deployment
converter that turns calibrated ``qparams`` + FP weights into packed int8
parameters consumed by ``QuantContext(kernel=True)``.

Serving path (single fused kernel family, see ``int8_fused``):

  - plain / TGQ-uniform inputs  -> ``int8_matmul_fq``   (fused-quantize
    prologue; no standalone quantize pass through HBM),
  - MRQ-signed (post-GELU) inputs -> ``int8_matmul_mrq_fq`` (single W
    traversal, dual region accumulators; replaces the two-matmul
    decomposition),
  - attention (activation x activation) -> ``flash_attention`` (the
    serving default, ``attn_impl="flash"``): the whole block as ONE
    ``flash_attn_mrq`` kernel — int8 QK^T, online softmax, MRQ codes and
    dual-region P·V with the (S, S) scores/codes never touching HBM; or
    ``int8_attention`` (``attn_impl="composed"``, the exactness oracle):
    symmetric QK^T (``int8_bmm_qk``), softmax straight to region-signed
    MRQ codes (``softmax_mrq_codes``), and dual-region P·V consuming the
    codes directly (``int8_bmm_pv``) — the probabilities never exist in
    HBM as floats. Both consume the SAME packs, built by
    ``pack_int8_qk`` / ``pack_int8_pv`` from the calibrated ``attn/qk``
    and ``attn/pv`` einsum qparams.

Bit-widths: the pack builders are bits-driven — w8a8 and w6a6 pack for
the byte-code ``int8_*`` kernel family (6-bit codes ride in full int8
bytes; only the code range changes), while w4a4 packs for the
nibble-PACKED ``int4_*`` family (``int4_packed``: two weight codes per
byte, per-K-group weight scales à la Q-DiT, and a packed-kv flash
variant). Every pack records its ``"bits"`` and the wrappers thread it
to the kernels as a static argument.

Activation-side parameters are packed STACKED along a leading (G,) TGQ
group axis — per-tensor quantizers pack as G=1 — and the timestep group
is a traced scalar resolved inside the kernels, so ``ddpm_sample``'s
lax.scan stays one compiled executable.

Channel-balanced ops (``x_prescale`` from HO's balance search) pack like
everything else: the balance divide folds into the kernels' quantize
prologue (the pack stores ``x_prescale`` and the wrappers thread it as
``ps=``) and its inverse folds into the weight codes at pack time
(``w * ps[:, None]`` — the calibrated ``ChannelQ`` saw exactly that
product, so the codes are unchanged). The linear wrappers additionally
accept the adaLN ``norm_mod=(shift, scale)`` / ``gate_residual=(gate,
residual)`` fusion seams (see ``int8_fused``), so the layernorm-modulate
chain before a matmul and the gate-scaled residual add after it run in
VMEM instead of round-tripping fp activations through HBM.

On this CPU container the wrappers run with ``interpret=True`` (kernel
body executed in Python for correctness); on a real TPU backend the same
calls compile to Mosaic. ``INTERPRET`` flips automatically.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantizers import (
    ChannelQ, MRQSignedQ, MRQSoftmaxQ, SymQ, TGQ, UniformQ,
)
from repro.quant.groups import resolve_group
from repro.kernels.int8_matmul import DEFAULT_BK, _ceil, int8_matmul
from repro.kernels.int8_fused import (
    int8_matmul_fq, int8_matmul_fq_vec, int8_matmul_mrq_fq,
    int8_matmul_mrq_fq_vec,
)
from repro.kernels.int4_packed import (
    int4_matmul_fq, int4_matmul_fq_vec, int4_matmul_mrq_fq,
    int4_matmul_mrq_fq_vec, pack_int4, unpack_int4,
)
from repro.kernels.int8_bmm import (
    int8_bmm_pv, int8_bmm_pv_vec, int8_bmm_qk, int8_bmm_qk_vec,
)
from repro.kernels.flash_attn_mrq import flash_attn_mrq, flash_attn_mrq_vec, \
    group_codes
from repro.kernels.softmax_mrq import (
    softmax_mrq, softmax_mrq_codes, softmax_mrq_codes_vec,
)
from repro.kernels.act_mrq import act_mrq
from repro.kernels import ref

INTERPRET = jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# int8 deployment path
# ---------------------------------------------------------------------------
def _unwrap_tgq(q):
    """Returns (inner_quantizer, is_tgq)."""
    if isinstance(q, TGQ):
        return q.inner, True
    return q, False


def _stack_param(p, is_tgq) -> jnp.ndarray:
    """Activation param -> (G, 1) f32 column (G=1 for per-tensor)."""
    a = jnp.asarray(p, jnp.float32)
    if not is_tgq:
        if a.ndim != 0:
            raise ValueError(f"per-tensor param must be scalar, got {a.shape}")
        return a.reshape(1, 1)
    if a.ndim != 1:
        raise ValueError(f"TGQ param must be stacked (G,), got {a.shape}")
    return a.reshape(-1, 1)


def _weight_codes(wq_q: ChannelQ, w, half: int = 128) -> Optional[tuple]:
    """(codes (K,N) int8, sw (N,) f32) or None if not a packable 2D linear.

    ``half`` follows the weight bit-width: 8-bit codes clip to ±127,
    6-bit to ±31 (stored in full int8 bytes either way)."""
    sw = jnp.asarray(wq_q.scale, jnp.float32).reshape(-1)
    w = jnp.asarray(w, jnp.float32)
    if w.ndim != 2 or sw.shape[0] != w.shape[-1]:
        return None
    codes = jnp.clip(jnp.round(w / sw[None, :]), -(half - 1), half - 1
                     ).astype(jnp.int8)
    return codes, sw


def _prescale_vec(qp: Dict[str, Any], w) -> Optional[jnp.ndarray]:
    """The op's channel-balance vector as a flat (K,) f32, or None.

    HO's balance search calibrated this op's quantizers on ``x / ps`` and
    ``w * ps`` — the kernels replay the divide in their quantize prologue
    (bitwise the fake-quant ``_q_in`` step; a multiply-by-inverse would
    drift by ulps) and the pack builders bake the multiply into the
    weight codes."""
    ps = qp.get("x_prescale")
    if ps is None:
        return None
    ps = jnp.asarray(ps, jnp.float32).reshape(-1)
    w = jnp.asarray(w)
    if w.ndim == 2 and ps.shape[0] != w.shape[0]:
        raise ValueError(
            f"x_prescale length {ps.shape[0]} != weight K {w.shape[0]}")
    return ps


def _balanced_w(w, ps: Optional[jnp.ndarray]):
    """Fold the balance multiply into the weight the codes are built from
    — the calibrated ``ChannelQ`` saw exactly ``w * ps``, so the codes
    (and the pack-time per-group absmax rescale at 4 bits) match what
    calibration measured."""
    if ps is None:
        return w
    return jnp.asarray(w, jnp.float32) * ps[:, None]


def pack_int8_linear(qp: Dict[str, Any], w: np.ndarray) -> Optional[dict]:
    """Pack one linear op for the fused int8 kernel. Accepts a per-tensor
    ``UniformQ`` or a time-grouped ``TGQ(UniformQ)`` activation quantizer
    and a ``ChannelQ`` weight quantizer. TGQ packs as stacked (G, ·)
    scale/zero/corr arrays gathered per-group inside the kernel.
    Bits-driven: 8- and 6-bit recipes pack here (byte codes, only the
    code range differs); 4-bit goes to ``pack_int4_linear``.

    Channel-balanced ops pack too: the quantizers were calibrated on
    x / ps and w * ps, so the weight codes are built from ``w * ps`` (the
    very tensor the ``ChannelQ`` saw) and the pack records ``x_prescale``
    for the kernel's in-prologue divide — no fake-quant fallback."""
    xq_q, is_tgq = _unwrap_tgq(qp.get("x"))
    if not isinstance(xq_q, UniformQ) or not isinstance(qp.get("w"), ChannelQ):
        return None
    wq_q: ChannelQ = qp["w"]
    bits = int(wq_q.bits)
    if bits not in (6, 8) or xq_q.bits != bits:
        return None
    half = 2 ** (bits - 1)
    try:
        sx = _stack_param(xq_q.scale, is_tgq)              # (G, 1)
        zx = _stack_param(xq_q.zero, is_tgq)               # (G, 1)
    except ValueError:
        return None
    ps = _prescale_vec(qp, w)
    cw = _weight_codes(wq_q, _balanced_w(w, ps), half)
    if cw is None:
        return None
    codes, sw = cw
    colsum = jnp.sum(codes.astype(jnp.int32), axis=0)      # (N,)
    z_eff = jnp.round(zx).astype(jnp.int32) - half         # (G, 1)
    pack = {
        "wq": codes,
        "sx": sx,
        "zx": zx,
        "scale": sx * sw[None, :],                          # (G, N)
        "corr": z_eff * colsum[None, :],                    # (G, N)
        "groups": int(sx.shape[0]),
        "bits": bits,
    }
    if ps is not None:
        pack["x_prescale"] = ps
    return pack


def pack_int8_mrq_linear(qp: Dict[str, Any], w: np.ndarray) -> Optional[dict]:
    """Pack a linear whose input is MRQ-signed (post-GELU fc2) — per-tensor
    ``MRQSignedQ`` or time-grouped ``TGQ(MRQSignedQ)`` — for the
    single-pass MRQ kernel (one W traversal, dual region accumulators).
    Channel-balanced ops pack with the prescale folded — see
    ``pack_int8_linear`` (the balance vector is positive, so the MRQ sign
    split is unaffected by the in-prologue divide)."""
    xq_q, is_tgq = _unwrap_tgq(qp.get("x"))
    if not isinstance(xq_q, MRQSignedQ) or not isinstance(
            qp.get("w"), ChannelQ):
        return None
    wq_q: ChannelQ = qp["w"]
    bits = int(wq_q.bits)
    if bits not in (6, 8) or xq_q.bits != bits:
        return None
    try:
        s_neg = _stack_param(xq_q.s_neg, is_tgq)           # (G, 1)
        s_pos = _stack_param(xq_q.s_pos, is_tgq)           # (G, 1)
    except ValueError:
        return None
    ps = _prescale_vec(qp, w)
    cw = _weight_codes(wq_q, _balanced_w(w, ps), 2 ** (bits - 1))
    if cw is None:
        return None
    codes, sw = cw
    pack = {
        "wq": codes,
        "s_neg": s_neg,
        "s_pos": s_pos,
        "scale_neg": s_neg * sw[None, :],                   # (G, N)
        "scale_pos": s_pos * sw[None, :],                   # (G, N)
        "groups": int(s_neg.shape[0]),
        "bits": bits,
    }
    if ps is not None:
        pack["x_prescale"] = ps
    return pack


# ---------------------------------------------------------------------------
# packed-int4 deployment path (nibble weights, per-K-group scales)
# ---------------------------------------------------------------------------
def _int4_group_codes(wq_q: ChannelQ, w) -> Optional[tuple]:
    """(codes3 (nk, group_k, N) int8 in [-7, 7], sw (nk, N) f32, group_k)
    or None if not a packable 2D linear.

    4-bit weights need finer granularity than one scale per output
    channel (Q-DiT): the K axis is re-scaled per group of ``group_k``
    rows — group_k is chosen to equal the int4 kernel's K tile, so each
    grid step is exactly one scale group. The calibrated per-channel
    ``wq_q.scale`` is superseded by the pack-time per-group absmax/7
    (a strict refinement: every group scale <= the channel scale)."""
    w = jnp.asarray(w, jnp.float32)
    sw_cal = jnp.asarray(wq_q.scale, jnp.float32).reshape(-1)
    if w.ndim != 2 or sw_cal.shape[0] != w.shape[-1]:
        return None
    K, N = w.shape
    group_k = min(DEFAULT_BK, _ceil(K))
    Kp = -group_k * (-K // group_k)
    nk = Kp // group_k
    w3 = jnp.pad(w, ((0, Kp - K), (0, 0))).reshape(nk, group_k, N)
    sw = jnp.maximum(jnp.max(jnp.abs(w3), axis=1), 1e-8) / 7.0   # (nk, N)
    codes3 = jnp.clip(jnp.round(w3 / sw[:, None, :]), -7, 7).astype(jnp.int8)
    return codes3, sw, group_k


def pack_int4_linear(qp: Dict[str, Any], w: np.ndarray) -> Optional[dict]:
    """Pack one linear op for ``int4_matmul_fq``: ``UniformQ`` /
    ``TGQ(UniformQ)`` activations + ``ChannelQ`` weights at 4 bits.
    Weights are nibble-packed two-per-byte; scale/corr carry the extra
    per-K-group axis (G, nk, N). Channel-balanced ops pack with the
    prescale folded (see ``pack_int8_linear``); the per-K-group absmax
    rescale runs on the balanced weight, matching calibration."""
    xq_q, is_tgq = _unwrap_tgq(qp.get("x"))
    if not isinstance(xq_q, UniformQ) or not isinstance(qp.get("w"), ChannelQ):
        return None
    wq_q: ChannelQ = qp["w"]
    if wq_q.bits != 4 or xq_q.bits != 4:
        return None
    try:
        sx = _stack_param(xq_q.scale, is_tgq)              # (G, 1)
        zx = _stack_param(xq_q.zero, is_tgq)               # (G, 1)
    except ValueError:
        return None
    ps = _prescale_vec(qp, w)
    gc = _int4_group_codes(wq_q, _balanced_w(w, ps))
    if gc is None:
        return None
    codes3, sw, group_k = gc
    N = codes3.shape[-1]
    colsum = jnp.sum(codes3.astype(jnp.int32), axis=1)     # (nk, N)
    z_eff = jnp.round(zx).astype(jnp.int32) - 8            # (G, 1)
    pack = {
        "wp": pack_int4(codes3.reshape(-1, N)),             # (Kp/2, N)
        "sx": sx,
        "zx": zx,
        "scale": sx[:, :, None] * sw[None],                 # (G, nk, N)
        "corr": z_eff[:, :, None] * colsum[None],           # (G, nk, N)
        "groups": int(sx.shape[0]),
        "group_k": int(group_k),
        "k": int(jnp.asarray(w).shape[0]),
        "bits": 4,
    }
    if ps is not None:
        pack["x_prescale"] = ps
    return pack


def pack_int4_mrq_linear(qp: Dict[str, Any], w: np.ndarray) -> Optional[dict]:
    """Pack an MRQ-signed-input linear (post-GELU fc2) for
    ``int4_matmul_mrq_fq``: nibble-packed weights, per-region per-K-group
    scales (G, nk, N), no zero-point correction. Channel-balanced ops
    pack with the prescale folded (see ``pack_int8_linear``)."""
    xq_q, is_tgq = _unwrap_tgq(qp.get("x"))
    if not isinstance(xq_q, MRQSignedQ) or not isinstance(
            qp.get("w"), ChannelQ):
        return None
    wq_q: ChannelQ = qp["w"]
    if wq_q.bits != 4 or xq_q.bits != 4:
        return None
    try:
        s_neg = _stack_param(xq_q.s_neg, is_tgq)           # (G, 1)
        s_pos = _stack_param(xq_q.s_pos, is_tgq)           # (G, 1)
    except ValueError:
        return None
    ps = _prescale_vec(qp, w)
    gc = _int4_group_codes(wq_q, _balanced_w(w, ps))
    if gc is None:
        return None
    codes3, sw, group_k = gc
    N = codes3.shape[-1]
    pack = {
        "wp": pack_int4(codes3.reshape(-1, N)),             # (Kp/2, N)
        "s_neg": s_neg,
        "s_pos": s_pos,
        "scale_neg": s_neg[:, :, None] * sw[None],          # (G, nk, N)
        "scale_pos": s_pos[:, :, None] * sw[None],          # (G, nk, N)
        "groups": int(s_neg.shape[0]),
        "group_k": int(group_k),
        "k": int(jnp.asarray(w).shape[0]),
        "bits": 4,
    }
    if ps is not None:
        pack["x_prescale"] = ps
    return pack


def _broadcast_groups(*cols):
    """Broadcast (1,1)/(G,1) stacked param columns to a common (G,1)."""
    G = max(int(c.shape[0]) for c in cols)
    out = []
    for c in cols:
        if c.shape[0] not in (1, G):
            return None
        out.append(jnp.broadcast_to(c, (G, 1)))
    return tuple(out) + (G,)


def pack_int8_qk(qp: Dict[str, Any]) -> Optional[dict]:
    """Pack an attention QK^T einsum for ``int8_bmm_qk``. Wants SYMMETRIC
    per-tensor quantizers on both activation operands — ``SymQ`` or
    time-grouped ``TGQ(SymQ)`` (group counts may differ; (1,·) params
    broadcast against the larger G)."""
    xq_q, x_tgq = _unwrap_tgq(qp.get("x"))
    bq_q, b_tgq = _unwrap_tgq(qp.get("b"))
    if not isinstance(xq_q, SymQ) or not isinstance(bq_q, SymQ):
        return None
    if xq_q.bits != bq_q.bits or xq_q.bits not in (4, 6, 8):
        return None
    try:
        s_q = _stack_param(xq_q.scale, x_tgq)              # (Gq, 1)
        s_k = _stack_param(bq_q.scale, b_tgq)              # (Gk, 1)
    except ValueError:
        return None
    bc = _broadcast_groups(s_q, s_k)
    if bc is None:
        return None
    s_q, s_k, G = bc
    return {
        "s_q": s_q,
        "s_k": s_k,
        "scale": s_q * s_k,                                 # (G, 1)
        "groups": G,
        "bits": int(xq_q.bits),
    }


def pack_int8_pv(qp: Dict[str, Any]) -> Optional[dict]:
    """Pack an attention P·V einsum for ``softmax_mrq_codes`` +
    ``int8_bmm_pv``: the probs side must be ``MRQSoftmaxQ`` (or
    ``TGQ(MRQSoftmaxQ)``), the value side ``SymQ`` / ``TGQ(SymQ)``."""
    xq_q, x_tgq = _unwrap_tgq(qp.get("x"))
    bq_q, b_tgq = _unwrap_tgq(qp.get("b"))
    if not isinstance(xq_q, MRQSoftmaxQ) or not isinstance(bq_q, SymQ):
        return None
    if xq_q.bits != bq_q.bits or xq_q.bits not in (4, 6, 8):
        return None
    try:
        s1 = _stack_param(xq_q.s1, x_tgq)                  # (Gp, 1)
        s_v = _stack_param(bq_q.scale, b_tgq)              # (Gv, 1)
    except ValueError:
        return None
    bc = _broadcast_groups(s1, s_v)
    if bc is None:
        return None
    s1, s_v, G = bc
    s2 = 1.0 / (2 ** (xq_q.bits - 1))
    return {
        "s1": s1,
        "s_v": s_v,
        "scale1": s1 * s_v,                                 # (G, 1)
        "scale2": s2 * s_v,                                 # (G, 1)
        "groups": G,
        "bits": int(xq_q.bits),
    }


def convert_for_kernels(qparams: Dict[str, dict],
                        weights: Dict[str, np.ndarray]) -> Dict[str, dict]:
    """Adds an 'int8' / 'int8_mrq' (byte codes, 8- or 6-bit) or 'int4' /
    'int4_mrq' (nibble-packed, per-K-group scales) pack to every eligible
    linear op and an 'int8_qk' / 'int8_pv' pack (bits-tagged, 8/6/4) to
    every eligible attention einsum — ``QuantContext(kernel=True)``
    dispatches on whichever pack key is present; the attention path fires
    exactly when BOTH attention packs of an op are present. The bit-width
    is read off the op's own quantizers, so one call handles w8a8, w6a6,
    and w4a4 recipes alike."""
    out = {}
    for name, qp in qparams.items():
        qp = dict(qp)
        if name in weights:
            for key, builder in (("int8", pack_int8_linear),
                                 ("int8_mrq", pack_int8_mrq_linear),
                                 ("int4", pack_int4_linear),
                                 ("int4_mrq", pack_int4_mrq_linear)):
                pack = builder(qp, weights[name])
                if pack is not None:
                    qp[key] = pack
                    break
        if name.endswith("/qk"):
            qpack = pack_int8_qk(qp)
            if qpack is not None:
                qp["int8_qk"] = qpack
        elif name.endswith("/pv"):
            ppack = pack_int8_pv(qp)
            if ppack is not None:
                qp["int8_pv"] = ppack
        out[name] = qp
    return out


def quantize_int8(x, scale, zero):
    """fp -> signed int8 codes (elementwise). Retained for the UNFUSED
    baseline and tests; the serving path quantizes inside
    ``int8_matmul_fq`` and never materializes these codes in HBM."""
    return ref.quantize_int8_ref(x, scale, zero)


def _group_index(pack: dict, tgroup):
    """Resolve the (possibly traced) TGQ group into a safe kernel index —
    the exact/clamp half of the shared ``repro.quant.groups`` contract.
    ``tgroup`` may also be a per-slot (B,) VECTOR (vector-tgroup batched
    path): the clamp maps elementwise and the wrappers below dispatch to
    the ``*_vec`` kernels, which stream the weights ONCE for the whole
    mixed-timestep batch and gather per-row activation params in VMEM."""
    return resolve_group(tgroup, pack["groups"])


def _is_vec(g) -> bool:
    """True when a resolved group index is a per-slot (B,) vector rather
    than a scalar (python int or traced 0-d)."""
    return getattr(g, "ndim", 0) == 1


def _rows_vec(g, n_rows: int):
    """Expand a per-slot (B,) group vector to one entry per matmul ROW.

    ``x.reshape(-1, K)`` keeps token rows batch-major contiguous, so slot
    b owns rows [b*rows_per_slot, (b+1)*rows_per_slot)."""
    B = int(g.shape[0])
    if n_rows % B != 0:
        raise ValueError(
            f"vector tgroup: {n_rows} matmul rows not divisible by "
            f"{B} slots")
    return jnp.repeat(jnp.asarray(g, jnp.int32), n_rows // B)


def _as_vec(g, B: int):
    """Lift a scalar group (e.g. a per-tensor G=1 pack resolving to 0) to
    a constant (B,) vector so it can ride the vector kernels alongside a
    genuinely mixed sibling pack. Constant vectors are bit-identical to
    the scalar-prefetch path (asserted by the conformance suite)."""
    if _is_vec(g):
        return jnp.asarray(g, jnp.int32)
    return jnp.full((B,), jnp.asarray(g, jnp.int32))


def _fusion_kwargs(pack: dict, xm, norm_mod, gate_residual) -> dict:
    """Kernel-side ``ps``/``nm``/``gr``/``rows_per_batch`` operands for one
    linear.

    ``norm_mod = (shift, scale)`` and ``gate_residual = (gate, residual)``
    carry per-BATCH (B, ·) adaLN rows (the residual is x-shaped). Matmul
    rows stay batch-major under ``x.reshape(-1, K)``, so batch entry b
    owns ``rows_per_batch`` consecutive rows. The channel-balance
    prescale rides the pack itself (``pack_int8_linear``)."""
    kw = {}
    ps = pack.get("x_prescale")
    if ps is not None:
        kw["ps"] = ps
    if norm_mod is None and gate_residual is None:
        return kw
    ref_rows = norm_mod[0] if norm_mod is not None else gate_residual[0]
    B = int(ref_rows.shape[0])
    n_rows = int(xm.shape[0])
    if n_rows % B != 0:
        raise ValueError(
            f"fusion rows: {n_rows} matmul rows not divisible by batch {B}")
    kw["rows_per_batch"] = n_rows // B
    if norm_mod is not None:
        sh, sc = norm_mod
        kw["nm"] = (jnp.asarray(sh, jnp.float32), jnp.asarray(sc, jnp.float32))
    if gate_residual is not None:
        gate, res = gate_residual
        res = jnp.asarray(res, jnp.float32)
        kw["gr"] = (jnp.asarray(gate, jnp.float32),
                    res.reshape(-1, res.shape[-1]))
    return kw


def int8_linear(x, pack: dict, bias=None, out_dtype=None, tgroup=None,
                norm_mod=None, gate_residual=None):
    """Fused quantize->matmul->dequant serving linear (TGQ-aware).

    ``tgroup`` may be a per-slot (B,) vector: the whole mixed-timestep
    batch then runs as ONE ``int8_matmul_fq_vec`` call — weights stream
    once, each row gathers its own group's quant params in VMEM.
    ``norm_mod``/``gate_residual`` fuse the surrounding adaLN elementwise
    chains into the kernel (see ``_fusion_kwargs``)."""
    out_dtype = out_dtype or x.dtype
    shape = x.shape
    xm = x.reshape(-1, shape[-1])
    g = _group_index(pack, tgroup)
    bias_f = None if bias is None else jnp.asarray(bias, jnp.float32)
    fkw = _fusion_kwargs(pack, xm, norm_mod, gate_residual)
    if _is_vec(g):
        y = int8_matmul_fq_vec(
            xm, pack["wq"], pack["sx"], pack["zx"], pack["scale"],
            pack["corr"], bias=bias_f, gv=_rows_vec(g, xm.shape[0]),
            bits=pack.get("bits", 8), out_dtype=out_dtype,
            interpret=INTERPRET, **fkw)
    else:
        y = int8_matmul_fq(
            xm, pack["wq"], pack["sx"], pack["zx"], pack["scale"],
            pack["corr"], bias=bias_f, g=g, bits=pack.get("bits", 8),
            out_dtype=out_dtype, interpret=INTERPRET, **fkw)
    return y.reshape(shape[:-1] + (pack["wq"].shape[1],))


def int8_linear_mrq(x, pack: dict, bias=None, out_dtype=None, tgroup=None,
                    norm_mod=None, gate_residual=None):
    """MRQ-input serving linear: single-pass kernel (one W traversal,
    in-kernel sign masking, dual region accumulators)."""
    out_dtype = out_dtype or x.dtype
    shape = x.shape
    xm = x.reshape(-1, shape[-1])
    g = _group_index(pack, tgroup)
    bias_f = None if bias is None else jnp.asarray(bias, jnp.float32)
    fkw = _fusion_kwargs(pack, xm, norm_mod, gate_residual)
    if _is_vec(g):
        y = int8_matmul_mrq_fq_vec(
            xm, pack["wq"], pack["s_neg"], pack["s_pos"],
            pack["scale_neg"], pack["scale_pos"], bias=bias_f,
            gv=_rows_vec(g, xm.shape[0]), bits=pack.get("bits", 8),
            out_dtype=out_dtype, interpret=INTERPRET, **fkw)
    else:
        y = int8_matmul_mrq_fq(
            xm, pack["wq"], pack["s_neg"], pack["s_pos"],
            pack["scale_neg"], pack["scale_pos"], bias=bias_f, g=g,
            bits=pack.get("bits", 8), out_dtype=out_dtype,
            interpret=INTERPRET, **fkw)
    return y.reshape(shape[:-1] + (pack["wq"].shape[1],))


def int4_linear(x, pack: dict, bias=None, out_dtype=None, tgroup=None,
                norm_mod=None, gate_residual=None):
    """Packed-int4 serving linear: nibble weights widen in the VMEM
    prologue, f32 accumulation with per-K-group dequant (TGQ-aware)."""
    out_dtype = out_dtype or x.dtype
    shape = x.shape
    xm = x.reshape(-1, shape[-1])
    g = _group_index(pack, tgroup)
    bias_f = None if bias is None else jnp.asarray(bias, jnp.float32)
    fkw = _fusion_kwargs(pack, xm, norm_mod, gate_residual)
    if _is_vec(g):
        y = int4_matmul_fq_vec(
            xm, pack["wp"], pack["sx"], pack["zx"], pack["scale"],
            pack["corr"], bias=bias_f, gv=_rows_vec(g, xm.shape[0]),
            group_k=pack["group_k"], out_dtype=out_dtype,
            interpret=INTERPRET, **fkw)
    else:
        y = int4_matmul_fq(
            xm, pack["wp"], pack["sx"], pack["zx"], pack["scale"],
            pack["corr"], bias=bias_f, g=g, group_k=pack["group_k"],
            out_dtype=out_dtype, interpret=INTERPRET, **fkw)
    return y.reshape(shape[:-1] + (pack["wp"].shape[1],))


def int4_linear_mrq(x, pack: dict, bias=None, out_dtype=None, tgroup=None,
                    norm_mod=None, gate_residual=None):
    """Packed-int4 MRQ-input serving linear (one nibble-weight traversal,
    dual region dots, per-K-group dequant)."""
    out_dtype = out_dtype or x.dtype
    shape = x.shape
    xm = x.reshape(-1, shape[-1])
    g = _group_index(pack, tgroup)
    bias_f = None if bias is None else jnp.asarray(bias, jnp.float32)
    fkw = _fusion_kwargs(pack, xm, norm_mod, gate_residual)
    if _is_vec(g):
        y = int4_matmul_mrq_fq_vec(
            xm, pack["wp"], pack["s_neg"], pack["s_pos"],
            pack["scale_neg"], pack["scale_pos"], bias=bias_f,
            gv=_rows_vec(g, xm.shape[0]), group_k=pack["group_k"],
            out_dtype=out_dtype, interpret=INTERPRET, **fkw)
    else:
        y = int4_matmul_mrq_fq(
            xm, pack["wp"], pack["s_neg"], pack["s_pos"],
            pack["scale_neg"], pack["scale_pos"], bias=bias_f, g=g,
            group_k=pack["group_k"], out_dtype=out_dtype,
            interpret=INTERPRET, **fkw)
    return y.reshape(shape[:-1] + (pack["wp"].shape[1],))


# ---------------------------------------------------------------------------
# int8 attention (the serving attention hot path)
# ---------------------------------------------------------------------------
def int8_attention(q, k, v, qk_pack: dict, pv_pack: dict, *, mask=None,
                   scale=1.0, tgroup=None, out_dtype=None):
    """End-to-end int8 grouped SDPA: QK^T -> fused softmax-MRQ -> P·V.

    q: (B, Sq, Hk, G, hd); k, v: (B, Skv, Hk, hd); mask broadcastable to
    (B, Hk, G, Sq, Skv) boolean or None; ``scale`` is the softmax
    1/sqrt(hd), folded into the QK^T dequant epilogue. Returns
    (B, Sq, Hk, G, hd). The probabilities travel between the softmax and
    P·V kernels as int8 region-signed codes — never as fp through HBM.
    ``tgroup`` may be a traced scalar (resolved per-pack; each kernel
    gathers its group row via scalar prefetch, so the surrounding
    ``ddpm_sample`` scan compiles once).
    """
    out_dtype = out_dtype or q.dtype
    B, Sq, Hk, G, hd = q.shape
    Skv = k.shape[1]
    BHG = B * Hk * G
    g_qk = _group_index(qk_pack, tgroup)
    g_pv = _group_index(pv_pack, tgroup)

    # GQA without materialized copies: q flattens to (B*Hk*G, ...) but k/v
    # stay (B*Hk, ...) — the kernels' b // rep batch index maps gather the
    # kv head shared by every query group, so k/v HBM traffic does not
    # scale with G.
    qf = q.transpose(0, 2, 3, 1, 4).reshape(BHG, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hk, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hk, Skv, hd)

    vec = _is_vec(g_qk) or _is_vec(g_pv)
    qk_bits = int(qk_pack.get("bits", 8))
    pv_bits = int(pv_pack.get("bits", 8))
    if vec:
        # Per-slot group vectors: one kernel call for the whole
        # mixed-timestep batch. q rows are slot-major after the transpose
        # (slot b owns Hk*G consecutive batch rows), so the per-slot
        # vector repeats Hk*G times; a scalar sibling pack (G=1) rides
        # along as a constant vector (bit-identical to scalar prefetch).
        gq = jnp.repeat(_as_vec(g_qk, B), Hk * G)              # (BHG,)
        gp = jnp.repeat(_as_vec(g_pv, B), Hk * G)              # (BHG,)
        scores = int8_bmm_qk_vec(
            qf, kf, qk_pack["s_q"], qk_pack["s_k"],
            qk_pack["scale"] * jnp.float32(scale), gv=gq,
            bits=qk_bits, interpret=INTERPRET)
    else:
        scores = int8_bmm_qk(
            qf, kf, qk_pack["s_q"], qk_pack["s_k"],
            qk_pack["scale"] * jnp.float32(scale), g=g_qk,
            bits=qk_bits, interpret=INTERPRET)
    scores = scores.reshape(B, Hk, G, Sq, Skv)
    if mask is not None:
        from repro.nn.ctx import NEG_INF
        scores = jnp.where(mask, scores, NEG_INF)

    if vec:
        rows_gv = jnp.broadcast_to(
            _as_vec(g_pv, B)[:, None, None, None], (B, Hk, G, Sq))
        codes = softmax_mrq_codes_vec(scores, pv_pack["s1"], gv=rows_gv,
                                      bits=pv_bits, interpret=INTERPRET)
        out = int8_bmm_pv_vec(
            codes.reshape(BHG, Sq, Skv), vf, pv_pack["s_v"],
            pv_pack["scale1"], pv_pack["scale2"], gv=gp, bits=pv_bits,
            out_dtype=out_dtype, interpret=INTERPRET)
    else:
        codes = softmax_mrq_codes(scores, pv_pack["s1"], g=g_pv,
                                  bits=pv_bits, interpret=INTERPRET)
        out = int8_bmm_pv(
            codes.reshape(BHG, Sq, Skv), vf, pv_pack["s_v"],
            pv_pack["scale1"], pv_pack["scale2"], g=g_pv, bits=pv_bits,
            out_dtype=out_dtype, interpret=INTERPRET)
    return out.reshape(B, Hk, G, Sq, hd).transpose(0, 3, 1, 2, 4)


def flash_attention(q, k, v, qk_pack: dict, pv_pack: dict, *, mask=None,
                    scale=1.0, tgroup=None, out_dtype=None):
    """Flash-style int8 grouped SDPA: ONE kernel per (batch·head, q-tile),
    no (S, S) scores/codes HBM round-trip.

    Same contract and packs as :func:`int8_attention` (which remains the
    composed three-kernel exactness oracle — ``attn_impl="composed"``):
    q: (B, Sq, Hk, G, hd); k, v: (B, Skv, Hk, hd); mask broadcastable to
    (B, Hk, G, Sq, Skv) boolean or None; ``scale`` folded into the QK^T
    dequant scale. The two pack sides resolve their TGQ groups
    independently (different group counts allowed) and both indices ride
    one scalar-prefetch vector, so the surrounding ``ddpm_sample`` scan
    still compiles once. Flash ≡ composed within
    ``ref.flash_vs_composed_atol`` (the online-rescale rounding
    contract); kv tiles stream with NEG_INF lane masking applied before
    the online max, so ragged Skv (e.g. S = 77) is exact.
    """
    out_dtype = out_dtype or q.dtype
    B, Sq, Hk, G, hd = q.shape
    Skv = k.shape[1]
    BHG = B * Hk * G
    g_qk = _group_index(qk_pack, tgroup)
    g_pv = _group_index(pv_pack, tgroup)

    bits = int(qk_pack.get("bits", 8))
    # s8 codes at each slot's steps BEFORE the head transposes: XLA fuses
    # the quantize into the relayout of q/k/v, and the transposes move s8
    q = group_codes(q, qk_pack["s_q"], g_qk, bits)
    k = group_codes(k, qk_pack["s_k"], g_qk, bits)
    v = group_codes(v, pv_pack["s_v"], g_pv, bits)
    qf = q.transpose(0, 2, 3, 1, 4).reshape(BHG, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hk, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hk, Skv, hd)
    mf = None
    if mask is not None:
        mf = jnp.broadcast_to(mask, (B, Hk, G, Sq, Skv)
                              ).reshape(BHG, Sq, Skv)

    if _is_vec(g_qk) or _is_vec(g_pv):
        # Vector-tgroup batched path: slot-major (BHG,) group vectors,
        # one flash call for the whole mixed-timestep batch (weights and
        # kv stream once; each batch row's params gather from the full
        # (G, ·) stacks via the per-row prefetch index maps).
        out = flash_attn_mrq_vec(
            qf, kf, vf, qk_pack["s_q"], qk_pack["s_k"],
            qk_pack["scale"] * jnp.float32(scale), pv_pack["s1"],
            pv_pack["s_v"], pv_pack["scale1"], pv_pack["scale2"],
            g_qk=jnp.repeat(_as_vec(g_qk, B), Hk * G),
            g_pv=jnp.repeat(_as_vec(g_pv, B), Hk * G),
            mask=mf, bits=bits, packed_kv=(bits == 4),
            out_dtype=out_dtype, interpret=INTERPRET)
    else:
        out = flash_attn_mrq(
            qf, kf, vf, qk_pack["s_q"], qk_pack["s_k"],
            qk_pack["scale"] * jnp.float32(scale), pv_pack["s1"],
            pv_pack["s_v"], pv_pack["scale1"], pv_pack["scale2"],
            g_qk=g_qk, g_pv=g_pv, mask=mf, bits=bits,
            packed_kv=(bits == 4), out_dtype=out_dtype,
            interpret=INTERPRET)
    return out.reshape(B, Hk, G, Sq, hd).transpose(0, 3, 1, 2, 4)


# ---------------------------------------------------------------------------
# fused activation kernels (public API)
# ---------------------------------------------------------------------------
def softmax_mrq_op(scores, s1, bits: int = 8, out_dtype=jnp.float32):
    return softmax_mrq(scores, s1, bits=bits, out_dtype=out_dtype,
                       interpret=INTERPRET)


def act_mrq_op(x, s_neg, s_pos, bits: int = 8, kind: str = "gelu",
               out_dtype=jnp.float32):
    return act_mrq(x, s_neg, s_pos, bits=bits, kind=kind, out_dtype=out_dtype,
                   interpret=INTERPRET)
