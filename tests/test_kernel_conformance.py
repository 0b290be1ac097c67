"""Cross-bit kernel conformance suite: EVERY kernel family against its
``ref.py`` oracle over one shared grid — bit-widths (w8a8 / w6a6 / w4a4)
x TGQ group counts G in {1, 3, 5} x ragged shapes (incl. the CLIP-style
S = 77) x mask / GQA. This file replaces the per-family copy-pasted
sweep loops that used to live in test_kernels_fused.py /
test_kernels_attn.py / test_flash_attn.py (those files keep their
structural and integration tests: block-shape overrides, TGQ repacking
equivalence, QuantContext routing, compile-once engine contracts).

Cases are built through the REAL pack builders (``kernels.ops.pack_*``),
so the suite conformance-tests the bits-driven packing layer together
with the kernels. All Pallas calls run in interpret mode on CPU.

Tolerance registry — the documented per-path numeric contract:

  - Byte-code paths (fused/MRQ linear at 8 and 6 bits, the composed
    attention trio at every bit-width): integer accumulation with one fp
    epilogue. Asserted BIT-IDENTICAL to the *jitted* oracle (the kernels
    execute under jit, where XLA may contract the epilogue multiply-add
    into an FMA; the eager ref dispatches op-by-op and can differ by
    1 ulp).
  - Flash vs its tile-faithful oracle: single-kv-tile runs are exact;
    multi-tile runs reassociate the online max/denominator rescale under
    jit fusion, leaving ~1 f32 ulp per rescale (atol 1e-5).
  - Packed-int4 linear family: the per-K-group dequantization
    accumulates in f32 once per K step; the oracle replays the same
    group order, leaving a few f32 ulp of reassociation slack (atol
    1e-4, observed ~0).
  - Flash packed-kv (bits=4): the nibble pre-pass is value-identical to
    quantizing in-kernel, so packed vs unpacked flash is BIT-IDENTICAL.
  - Flash vs composed: the online-rescale rounding contract, bounded by
    ``ref.flash_vs_composed_atol`` (dynamic in the pv pack and kv
    length).
  - Vector-tgroup variants (per-row group vectors, the mixed-timestep
    batched path): a CONSTANT group vector ``full((B,), g)`` is asserted
    BIT-IDENTICAL to the scalar-prefetch sibling, mixed vectors conform
    to the per-row ``*_vec_ref`` jitted oracles at the parent family's
    tolerance, and the ops wrappers' batched dispatch is asserted
    bit-identical to stacking per-slot scalar-tgroup calls.
  - Prologue/epilogue fusions (channel-balance prescale, adaLN
    norm-modulate, gate+residual — each alone and all three combined):
    the fused kernels inherit the parent family's tolerance against the
    ``*_fused_ref`` jitted oracles — BIT-IDENTICAL for the byte-code
    linears (the prologue/epilogue run in the same f32 op order the
    oracle jits), atol 1e-4 for the packed-int4 family (per-K-group f32
    accumulation, observed ~2e-6).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantizers import (
    ChannelQ, MRQSignedQ, MRQSoftmaxQ, SymQ, TGQ, UniformQ,
    channel_scale_from_absmax, weight_absmax,
)
from repro.kernels import (
    flash_attn_mrq, int8_bmm_pv, int8_bmm_qk, pack_int4, softmax_mrq_codes,
    unpack_int4,
)
from repro.kernels import ops, ref
from repro.kernels.flash_attn_mrq import flash_attn_mrq_vec
from repro.kernels.int8_bmm import int8_bmm_pv_vec, int8_bmm_qk_vec
from repro.kernels.softmax_mrq import softmax_mrq_codes_vec

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # optional dep
    HAVE_HYPOTHESIS = False

BITS = {"w8a8": 8, "w6a6": 6, "w4a4": 4}
GROUPS = (1, 3, 5)

# (M, K, N) — MXU-aligned, ragged, sub-tile, and multi-K-tile shapes
MM_SHAPES = [(8, 16, 8), (64, 96, 80), (7, 13, 5), (130, 257, 129),
             (1, 5, 3), (64, 512, 96)]
# (B, Sq, Skv, D, bn) — batched attention incl. ragged S=77 and 1-row q
ATTN_SHAPES = [(1, 8, 8, 8, 128), (3, 7, 13, 5, 8), (1, 130, 129, 17, 64),
               (2, 77, 77, 24, 32), (2, 1, 5, 3, 8)]

# atol per conformance path; 0.0 means bit-identical to the jitted oracle
TOLERANCES = {
    "linear": 0.0,              # int8/int6 fused linear (s32 accumulation)
    "linear_mrq": 0.0,          # int8/int6 single-pass MRQ linear
    "int4_linear": 1e-4,        # f32 per-K-group accumulation
    "int4_linear_mrq": 1e-4,
    "attn_qk": 0.0,             # composed trio: integer kernels
    "attn_codes": 0.0,
    "attn_pv": 0.0,
    "flash": 1e-5,              # vs the tile-faithful jitted oracle
    "flash_packed_kv": 0.0,     # packed vs unpacked 4-bit flash
    "vec_const": 0.0,           # constant group vector == scalar prefetch
    "linear_vec": 0.0,          # mixed vector vs the per-row jitted oracle
    "linear_mrq_vec": 0.0,
    "int4_linear_vec": 1e-4,
    "int4_linear_mrq_vec": 1e-4,
    "attn_qk_vec": 0.0,
    "attn_codes_vec": 0.0,
    "attn_pv_vec": 0.0,
    "flash_vec": 1e-5,
    "linear_fused": 0.0,        # prologue/epilogue fusions: byte-code
    "linear_mrq_fused": 0.0,    # linears stay bit-identical
    "int4_linear_fused": 1e-4,
    "int4_linear_mrq_fused": 1e-4,
    "linear_fused_vec": 0.0,
    "linear_mrq_fused_vec": 0.0,
    "int4_linear_fused_vec": 1e-4,
    "int4_linear_mrq_fused_vec": 1e-4,
}


def _jit_ref(fn, **static):
    return jax.jit(functools.partial(fn, **static))


def _assert_conforms(path, got, want):
    got, want = np.asarray(got), np.asarray(want)
    if TOLERANCES[path] == 0.0:
        np.testing.assert_array_equal(got, want, err_msg=path)
    else:
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=TOLERANCES[path], err_msg=path)


# ---------------------------------------------------------------------------
# case builders (through the real quantizers + pack builders)
# ---------------------------------------------------------------------------
def _uniform_linear_case(M, K, N, G, bits, seed):
    half = 2 ** (bits - 1)
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (M, K)) * 2.0
    w = jax.random.normal(kw, (K, N)) * 0.05
    bias = jax.random.normal(kb, (N,))
    qp = {"x": TGQ(UniformQ(scale=jnp.linspace(0.01, 0.05, G),
                            zero=jnp.round(jnp.linspace(0.7 * half,
                                                        1.17 * half, G)),
                            bits=bits)),
          "w": ChannelQ(channel_scale_from_absmax(weight_absmax(w), bits),
                        bits)}
    return x, w, bias, qp


def _mrq_linear_case(M, K, N, G, bits, seed):
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    x = jax.nn.gelu(jax.random.normal(kx, (M, K)) * 1.5)
    w = jax.random.normal(kw, (K, N)) * 0.05
    bias = jax.random.normal(kb, (N,))
    qp = {"x": TGQ(MRQSignedQ(s_neg=jnp.geomspace(1e-4, 2e-3, G),
                              s_pos=jnp.geomspace(1e-3, 2e-2, G),
                              bits=bits)),
          "w": ChannelQ(channel_scale_from_absmax(weight_absmax(w), bits),
                        bits)}
    return x, w, bias, qp


def _attn_qparams(G, bits, seed=0):
    qk = {"x": TGQ(SymQ(scale=jnp.linspace(0.01, 0.05, G), bits=bits)),
          "b": TGQ(SymQ(scale=jnp.linspace(0.02, 0.06, G), bits=bits))}
    pv = {"x": TGQ(MRQSoftmaxQ(s1=jnp.geomspace(3e-4, 6e-3, G), bits=bits)),
          "b": TGQ(SymQ(scale=jnp.linspace(0.01, 0.04, G), bits=bits))}
    return qk, pv


def _attn_case(B, Sq, Skv, D, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (B, Sq, D)) * 2.0
    k = jax.random.normal(k2, (B, Skv, D)) * 2.0
    v = jax.random.normal(k3, (B, Skv, D)) * 1.5
    return q, k, v


def _g_probes(G):
    return (0,) if G == 1 else (0, G - 1)


# ---------------------------------------------------------------------------
# fused linear family (uniform activations)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", MM_SHAPES, ids=lambda s: "x".join(map(
    str, s)))
@pytest.mark.parametrize("bname", sorted(BITS))
def test_linear_conformance(bname, shape):
    bits = BITS[bname]
    M, K, N = shape
    for G in GROUPS:
        x, w, bias, qp = _uniform_linear_case(M, K, N, G, bits,
                                              seed=M * K + N + G)
        if bits == 4:
            pack = ops.pack_int4_linear(qp, w)
            assert pack is not None and pack["bits"] == 4
            want_fn = _jit_ref(ref.int4_matmul_fq_ref,
                               group_k=pack["group_k"])
            for g in _g_probes(G):
                got = ops.int4_linear(x, pack, bias=bias, tgroup=g)
                want = want_fn(x, pack["wp"], pack["sx"], pack["zx"],
                               pack["scale"], pack["corr"], bias, g=g)
                _assert_conforms("int4_linear", got, want)
        else:
            pack = ops.pack_int8_linear(qp, w)
            assert pack is not None and pack["bits"] == bits
            want_fn = _jit_ref(ref.int8_matmul_fq_ref, bits=bits)
            for g in _g_probes(G):
                got = ops.int8_linear(x, pack, bias=bias, tgroup=g)
                want = want_fn(x, pack["wq"], pack["sx"], pack["zx"],
                               pack["scale"], pack["corr"], bias, g=g)
                _assert_conforms("linear", got, want)


@pytest.mark.parametrize("shape", MM_SHAPES, ids=lambda s: "x".join(map(
    str, s)))
@pytest.mark.parametrize("bname", sorted(BITS))
def test_linear_mrq_conformance(bname, shape):
    bits = BITS[bname]
    M, K, N = shape
    for G in GROUPS:
        x, w, bias, qp = _mrq_linear_case(M, K, N, G, bits,
                                          seed=M + K * N + G)
        if bits == 4:
            pack = ops.pack_int4_mrq_linear(qp, w)
            assert pack is not None and pack["bits"] == 4
            want_fn = _jit_ref(ref.int4_matmul_mrq_fq_ref,
                               group_k=pack["group_k"])
            for g in _g_probes(G):
                got = ops.int4_linear_mrq(x, pack, bias=bias, tgroup=g)
                want = want_fn(x, pack["wp"], pack["s_neg"], pack["s_pos"],
                               pack["scale_neg"], pack["scale_pos"], bias,
                               g=g)
                _assert_conforms("int4_linear_mrq", got, want)
        else:
            pack = ops.pack_int8_mrq_linear(qp, w)
            assert pack is not None and pack["bits"] == bits
            want_fn = _jit_ref(ref.int8_matmul_mrq_fq_ref, bits=bits)
            for g in _g_probes(G):
                got = ops.int8_linear_mrq(x, pack, bias=bias, tgroup=g)
                want = want_fn(x, pack["wq"], pack["s_neg"], pack["s_pos"],
                               pack["scale_neg"], pack["scale_pos"], bias,
                               g=g)
                _assert_conforms("linear_mrq", got, want)


# ---------------------------------------------------------------------------
# prologue/epilogue fusions on the linear families: channel-balance
# prescale (ps), adaLN norm-modulate (nm), gate+residual (gr)
# ---------------------------------------------------------------------------
FUSION_SHAPES = [(8, 16, 8), (7, 13, 5), (130, 257, 129), (64, 512, 96)]


def _fusion_operands(M, K, N, seed):
    """Per-batch adaLN rows + a positive channel-balance vector. B is a
    proper divisor of M so the row->batch map exercises row grouping
    (M=7 makes every row its own batch)."""
    B = next(b for b in (4, 3, 2, 7, 1) if M % b == 0)
    ks = jax.random.split(jax.random.PRNGKey(seed + 101), 5)
    ps = jnp.exp(jax.random.uniform(ks[0], (K,), minval=-1.0, maxval=1.0))
    nm = (jax.random.normal(ks[1], (B, K)) * 0.5,
          jax.random.normal(ks[2], (B, K)) * 0.2)
    gr = (jax.random.normal(ks[3], (B, N)) * 0.8,
          jax.random.normal(ks[4], (M, N)))
    bv = jnp.repeat(jnp.arange(B, dtype=jnp.int32), M // B)
    return ps, nm, gr, bv


_FUSION_COMBOS = ("ps", "nm", "gr", "all")     # each alone + all three


@pytest.mark.parametrize("shape", FUSION_SHAPES, ids=lambda s: "x".join(map(
    str, s)))
@pytest.mark.parametrize("bname", sorted(BITS))
def test_linear_fusion_conformance(bname, shape):
    """Fused-prologue/epilogue uniform linears through the ops dispatch
    (pack built WITH ``x_prescale`` for the ps combos) vs the
    ``*_fused_ref`` jitted oracles, scalar-prefetch and mixed-vector
    tgroup paths."""
    bits = BITS[bname]
    M, K, N = shape
    ps, nm, gr, bv = _fusion_operands(M, K, N, seed=M + K + N)
    for G in GROUPS:
        x, w, bias, qp = _uniform_linear_case(M, K, N, G, bits,
                                              seed=M * K + N + G)
        qp_ps = dict(qp, x_prescale=ps)
        if bits == 4:
            pack = ops.pack_int4_linear(qp, w)
            pack_ps = ops.pack_int4_linear(qp_ps, w)
            lin, path = ops.int4_linear, "int4_linear_fused"
            want_fn = _jit_ref(ref.int4_matmul_fq_fused_ref,
                               group_k=pack["group_k"])
            vec_fn = _jit_ref(ref.int4_matmul_fq_vec_fused_ref,
                              group_k=pack["group_k"])
            wargs = ("wp", "sx", "zx", "scale", "corr")
        else:
            pack = ops.pack_int8_linear(qp, w)
            pack_ps = ops.pack_int8_linear(qp_ps, w)
            lin, path = ops.int8_linear, "linear_fused"
            want_fn = _jit_ref(ref.int8_matmul_fq_fused_ref, bits=bits)
            vec_fn = _jit_ref(ref.int8_matmul_fq_vec_fused_ref, bits=bits)
            wargs = ("wq", "sx", "zx", "scale", "corr")
        np.testing.assert_array_equal(np.asarray(pack_ps["x_prescale"]),
                                      np.asarray(ps))
        g = G - 1
        for combo in _FUSION_COMBOS:
            p = pack_ps if combo in ("ps", "all") else pack
            nm_i = nm if combo in ("nm", "all") else None
            gr_i = gr if combo in ("gr", "all") else None
            got = lin(x, p, bias=bias, tgroup=g, norm_mod=nm_i,
                      gate_residual=gr_i)
            want = want_fn(x, *(p[a] for a in wargs), bias, g=g,
                           ps=p.get("x_prescale"), nm=nm_i, gr=gr_i, bv=bv)
            _assert_conforms(path, got, want)
        if G > 1:
            gv = _mix_rows(M, G)
            got = lin(x, pack_ps, bias=bias, tgroup=gv, norm_mod=nm,
                      gate_residual=gr)
            want = vec_fn(x, *(pack_ps[a] for a in wargs), bias, gv=gv,
                          ps=ps, nm=nm, gr=gr, bv=bv)
            _assert_conforms(path + "_vec", got, want)


@pytest.mark.parametrize("shape", FUSION_SHAPES, ids=lambda s: "x".join(map(
    str, s)))
@pytest.mark.parametrize("bname", sorted(BITS))
def test_linear_mrq_fusion_conformance(bname, shape):
    """Same fusion sweep on the single-pass MRQ linears — the prologue
    runs BEFORE the sign split (the balance vector is positive, so the
    region assignment is untouched)."""
    bits = BITS[bname]
    M, K, N = shape
    ps, nm, gr, bv = _fusion_operands(M, K, N, seed=M * 2 + K + N)
    for G in GROUPS:
        x, w, bias, qp = _mrq_linear_case(M, K, N, G, bits,
                                          seed=M + K * N + G)
        qp_ps = dict(qp, x_prescale=ps)
        if bits == 4:
            pack = ops.pack_int4_mrq_linear(qp, w)
            pack_ps = ops.pack_int4_mrq_linear(qp_ps, w)
            lin, path = ops.int4_linear_mrq, "int4_linear_mrq_fused"
            want_fn = _jit_ref(ref.int4_matmul_mrq_fq_fused_ref,
                               group_k=pack["group_k"])
            vec_fn = _jit_ref(ref.int4_matmul_mrq_fq_vec_fused_ref,
                              group_k=pack["group_k"])
            wargs = ("wp", "s_neg", "s_pos", "scale_neg", "scale_pos")
        else:
            pack = ops.pack_int8_mrq_linear(qp, w)
            pack_ps = ops.pack_int8_mrq_linear(qp_ps, w)
            lin, path = ops.int8_linear_mrq, "linear_mrq_fused"
            want_fn = _jit_ref(ref.int8_matmul_mrq_fq_fused_ref, bits=bits)
            vec_fn = _jit_ref(ref.int8_matmul_mrq_fq_vec_fused_ref,
                              bits=bits)
            wargs = ("wq", "s_neg", "s_pos", "scale_neg", "scale_pos")
        g = G - 1
        for combo in _FUSION_COMBOS:
            p = pack_ps if combo in ("ps", "all") else pack
            nm_i = nm if combo in ("nm", "all") else None
            gr_i = gr if combo in ("gr", "all") else None
            got = lin(x, p, bias=bias, tgroup=g, norm_mod=nm_i,
                      gate_residual=gr_i)
            want = want_fn(x, *(p[a] for a in wargs), bias, g=g,
                           ps=p.get("x_prescale"), nm=nm_i, gr=gr_i, bv=bv)
            _assert_conforms(path, got, want)
        if G > 1:
            gv = _mix_rows(M, G)
            got = lin(x, pack_ps, bias=bias, tgroup=gv, norm_mod=nm,
                      gate_residual=gr)
            want = vec_fn(x, *(pack_ps[a] for a in wargs), bias, gv=gv,
                          ps=ps, nm=nm, gr=gr, bv=bv)
            _assert_conforms(path + "_vec", got, want)


# ---------------------------------------------------------------------------
# composed attention trio (QK^T -> softmax-MRQ codes -> P·V)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", ATTN_SHAPES, ids=lambda s: "x".join(map(
    str, s[:4])))
@pytest.mark.parametrize("bname", sorted(BITS))
def test_attention_composed_conformance(bname, shape):
    bits = BITS[bname]
    B, Sq, Skv, D, _ = shape
    for G in GROUPS:
        qk_qp, pv_qp = _attn_qparams(G, bits, seed=sum(shape) + G)
        qk_pack = ops.pack_int8_qk(qk_qp)
        pv_pack = ops.pack_int8_pv(pv_qp)
        assert qk_pack["bits"] == bits and pv_pack["bits"] == bits
        q, k, v = _attn_case(B, Sq, Skv, D, seed=sum(shape) + G)
        qk_ref = _jit_ref(ref.int8_bmm_qk_ref, bits=bits)
        sm_ref = _jit_ref(ref.softmax_mrq_codes_ref, bits=bits)
        pv_ref = _jit_ref(ref.int8_bmm_pv_ref, bits=bits)
        for g in _g_probes(G):
            scores = int8_bmm_qk(q, k, qk_pack["s_q"], qk_pack["s_k"],
                                 qk_pack["scale"], g=g, bits=bits,
                                 interpret=True)
            _assert_conforms("attn_qk", scores,
                             qk_ref(q, k, qk_pack["s_q"], qk_pack["s_k"],
                                    qk_pack["scale"], g=g))
            codes = softmax_mrq_codes(scores, pv_pack["s1"], g=g, bits=bits,
                                      interpret=True)
            assert codes.dtype == jnp.int8
            _assert_conforms("attn_codes", codes,
                             sm_ref(scores, pv_pack["s1"], g=g))
            out = int8_bmm_pv(codes, v, pv_pack["s_v"], pv_pack["scale1"],
                              pv_pack["scale2"], g=g, bits=bits,
                              interpret=True)
            _assert_conforms("attn_pv", out,
                             pv_ref(codes, v, pv_pack["s_v"],
                                    pv_pack["scale1"], pv_pack["scale2"],
                                    g=g))


# ---------------------------------------------------------------------------
# flash attention (single fused kernel; packed-kv at 4 bits)
# ---------------------------------------------------------------------------
def _flash(q, k, v, qk_pack, pv_pack, g, scale, bn, bits, packed_kv=False):
    return flash_attn_mrq(
        q, k, v, qk_pack["s_q"], qk_pack["s_k"], qk_pack["scale"] * scale,
        pv_pack["s1"], pv_pack["s_v"], pv_pack["scale1"], pv_pack["scale2"],
        g_qk=g, g_pv=g, bits=bits, packed_kv=packed_kv, bn=bn,
        interpret=True)


@pytest.mark.parametrize("shape", ATTN_SHAPES, ids=lambda s: "x".join(map(
    str, s[:4])))
@pytest.mark.parametrize("bname", sorted(BITS))
def test_flash_conformance(bname, shape):
    bits = BITS[bname]
    B, Sq, Skv, D, bn = shape
    scale = D ** -0.5
    for G in GROUPS:
        qk_qp, pv_qp = _attn_qparams(G, bits, seed=sum(shape) + G)
        qk_pack = ops.pack_int8_qk(qk_qp)
        pv_pack = ops.pack_int8_pv(pv_qp)
        q, k, v = _attn_case(B, Sq, Skv, D, seed=sum(shape) + 7 * G)
        want_fn = _jit_ref(ref.flash_attn_mrq_ref, bits=bits, bn=bn,
                           scale=scale)
        for g in _g_probes(G):
            got = _flash(q, k, v, qk_pack, pv_pack, g, scale, bn, bits,
                         packed_kv=(bits == 4))
            want = want_fn(q, k, v, qk_pack, pv_pack, g_qk=g, g_pv=g)
            _assert_conforms("flash", got, want)
            if bits == 4:
                # the nibble pre-pass must be value-identical to in-kernel
                # quantization: packed-kv == unpacked bit-for-bit
                unpacked = _flash(q, k, v, qk_pack, pv_pack, g, scale, bn,
                                  bits, packed_kv=False)
                _assert_conforms("flash_packed_kv", got, unpacked)


@pytest.mark.parametrize("G", GROUPS)
@pytest.mark.parametrize("bname", sorted(BITS))
def test_flash_vs_composed_tolerance(bname, G):
    """Flash == the composed trio within ``ref.flash_vs_composed_atol``
    (the online-rescale rounding contract), at every bit-width and TGQ
    group — multi-kv-tile so the online path actually rescales."""
    bits = BITS[bname]
    B, Sq, Skv, D, bn = 2, 77, 77, 24, 32
    scale = D ** -0.5
    qk_qp, pv_qp = _attn_qparams(G, bits, seed=17 + G)
    qk_pack = ops.pack_int8_qk(qk_qp)
    pv_pack = ops.pack_int8_pv(pv_qp)
    q, k, v = _attn_case(B, Sq, Skv, D, seed=29 + G)
    composed_fn = _jit_ref(ref.int8_attention_ref, bits=bits, scale=scale)
    for g in _g_probes(G):
        got = _flash(q, k, v, qk_pack, pv_pack, g, scale, bn, bits,
                     packed_kv=(bits == 4))
        composed = composed_fn(q, k, v, qk_pack, pv_pack, g=g)
        atol = ref.flash_vs_composed_atol(pv_pack, g, Skv, bits=bits)
        diff = float(jnp.max(jnp.abs(got - composed)))
        assert diff <= atol, (bname, G, g, diff, atol)


@pytest.mark.parametrize("bname", sorted(BITS))
def test_flash_mask_and_gqa_conformance(bname):
    """Mask: flash with a boolean mask matches the masked oracle. GQA: a
    q batch of rep x the kv batch gathers the shared kv tile via b//rep —
    bit-identical to feeding materialized kv copies. Both per bit-width
    (packed-kv on at 4 bits)."""
    bits = BITS[bname]
    G, scale, bn = 3, 24 ** -0.5, 32
    qk_qp, pv_qp = _attn_qparams(G, bits, seed=5)
    qk_pack = ops.pack_int8_qk(qk_qp)
    pv_pack = ops.pack_int8_pv(pv_qp)
    packed = bits == 4

    B, Sq, Skv, D = 2, 33, 77, 24
    q, k, v = _attn_case(B, Sq, Skv, D, seed=31)
    mask = jax.random.bernoulli(jax.random.PRNGKey(6), 0.8, (B, Sq, Skv))
    mask = mask.at[:, :, 0].set(True)          # no fully-masked rows
    got = flash_attn_mrq(
        q, k, v, qk_pack["s_q"], qk_pack["s_k"], qk_pack["scale"] * scale,
        pv_pack["s1"], pv_pack["s_v"], pv_pack["scale1"], pv_pack["scale2"],
        g_qk=1, g_pv=1, mask=mask, bits=bits, packed_kv=packed, bn=bn,
        interpret=True)
    want = _jit_ref(ref.flash_attn_mrq_ref, bits=bits, bn=bn, scale=scale)(
        q, k, v, qk_pack, pv_pack, mask=mask, g_qk=1, g_pv=1)
    _assert_conforms("flash", got, want)

    rep = 3
    qg, _, _ = _attn_case(B * rep, Sq, Skv, D, seed=37)
    shared = _flash(qg, k, v, qk_pack, pv_pack, 1, scale, bn, bits,
                    packed_kv=packed)
    copied = _flash(qg, jnp.repeat(k, rep, axis=0),
                    jnp.repeat(v, rep, axis=0), qk_pack, pv_pack, 1, scale,
                    bn, bits, packed_kv=packed)
    np.testing.assert_array_equal(np.asarray(shared), np.asarray(copied))


# ---------------------------------------------------------------------------
# vector-tgroup variants: per-row group vectors (mixed-timestep batches)
# ---------------------------------------------------------------------------
def _mix_rows(n, G, salt=0):
    """Deterministic per-row group vector hitting every group in [0, G)."""
    return jnp.asarray((np.arange(n) * 7 + salt) % G, jnp.int32)


def _flash_vec(q, k, v, qk_pack, pv_pack, gv, scale, bn, bits,
               packed_kv=False):
    return flash_attn_mrq_vec(
        q, k, v, qk_pack["s_q"], qk_pack["s_k"], qk_pack["scale"] * scale,
        pv_pack["s1"], pv_pack["s_v"], pv_pack["scale1"], pv_pack["scale2"],
        g_qk=gv, g_pv=gv, bits=bits, packed_kv=packed_kv, bn=bn,
        interpret=True)


@pytest.mark.parametrize("shape", MM_SHAPES, ids=lambda s: "x".join(map(
    str, s)))
@pytest.mark.parametrize("bname", sorted(BITS))
def test_linear_vector_tgroup_conformance(bname, shape):
    """Vector-tgroup linears through the ops dispatch: a CONSTANT per-row
    group vector ``full((M,), g)`` is bit-identical to the scalar-prefetch
    sibling, and a MIXED vector matches the per-row jitted oracle."""
    bits = BITS[bname]
    M, K, N = shape
    for G in GROUPS:
        x, w, bias, qp = _uniform_linear_case(M, K, N, G, bits,
                                              seed=M * K + N + G)
        if bits == 4:
            pack = ops.pack_int4_linear(qp, w)
            fwd = functools.partial(ops.int4_linear, x, pack, bias=bias)
            vec_ref = _jit_ref(ref.int4_matmul_fq_vec_ref,
                               group_k=pack["group_k"])
            args = (x, pack["wp"], pack["sx"], pack["zx"], pack["scale"],
                    pack["corr"], bias)
            path = "int4_linear_vec"
        else:
            pack = ops.pack_int8_linear(qp, w)
            fwd = functools.partial(ops.int8_linear, x, pack, bias=bias)
            vec_ref = _jit_ref(ref.int8_matmul_fq_vec_ref, bits=bits)
            args = (x, pack["wq"], pack["sx"], pack["zx"], pack["scale"],
                    pack["corr"], bias)
            path = "linear_vec"
        for g in _g_probes(G):
            _assert_conforms("vec_const",
                             fwd(tgroup=jnp.full((M,), g, jnp.int32)),
                             fwd(tgroup=g))
        if G > 1:
            gv = _mix_rows(M, G)
            _assert_conforms(path, fwd(tgroup=gv), vec_ref(*args, gv=gv))


@pytest.mark.parametrize("shape", MM_SHAPES, ids=lambda s: "x".join(map(
    str, s)))
@pytest.mark.parametrize("bname", sorted(BITS))
def test_linear_mrq_vector_tgroup_conformance(bname, shape):
    bits = BITS[bname]
    M, K, N = shape
    for G in GROUPS:
        x, w, bias, qp = _mrq_linear_case(M, K, N, G, bits,
                                          seed=M + K * N + G)
        if bits == 4:
            pack = ops.pack_int4_mrq_linear(qp, w)
            fwd = functools.partial(ops.int4_linear_mrq, x, pack, bias=bias)
            vec_ref = _jit_ref(ref.int4_matmul_mrq_fq_vec_ref,
                               group_k=pack["group_k"])
            args = (x, pack["wp"], pack["s_neg"], pack["s_pos"],
                    pack["scale_neg"], pack["scale_pos"], bias)
            path = "int4_linear_mrq_vec"
        else:
            pack = ops.pack_int8_mrq_linear(qp, w)
            fwd = functools.partial(ops.int8_linear_mrq, x, pack, bias=bias)
            vec_ref = _jit_ref(ref.int8_matmul_mrq_fq_vec_ref, bits=bits)
            args = (x, pack["wq"], pack["s_neg"], pack["s_pos"],
                    pack["scale_neg"], pack["scale_pos"], bias)
            path = "linear_mrq_vec"
        for g in _g_probes(G):
            _assert_conforms("vec_const",
                             fwd(tgroup=jnp.full((M,), g, jnp.int32)),
                             fwd(tgroup=g))
        if G > 1:
            gv = _mix_rows(M, G)
            _assert_conforms(path, fwd(tgroup=gv), vec_ref(*args, gv=gv))


@pytest.mark.parametrize("shape", ATTN_SHAPES, ids=lambda s: "x".join(map(
    str, s[:4])))
@pytest.mark.parametrize("bname", sorted(BITS))
def test_attention_composed_vector_tgroup_conformance(bname, shape):
    """Composed trio with per-batch-row group vectors: constant vector ==
    scalar prefetch bit-for-bit at every stage; mixed vectors match the
    per-row jitted oracles."""
    bits = BITS[bname]
    B, Sq, Skv, D, _ = shape
    for G in GROUPS:
        qk_qp, pv_qp = _attn_qparams(G, bits, seed=sum(shape) + G)
        qk_pack = ops.pack_int8_qk(qk_qp)
        pv_pack = ops.pack_int8_pv(pv_qp)
        q, k, v = _attn_case(B, Sq, Skv, D, seed=sum(shape) + G)
        for g in _g_probes(G):
            gv = jnp.full((B,), g, jnp.int32)
            scores = int8_bmm_qk(q, k, qk_pack["s_q"], qk_pack["s_k"],
                                 qk_pack["scale"], g=g, bits=bits,
                                 interpret=True)
            _assert_conforms(
                "vec_const",
                int8_bmm_qk_vec(q, k, qk_pack["s_q"], qk_pack["s_k"],
                                qk_pack["scale"], gv=gv, bits=bits,
                                interpret=True),
                scores)
            rows = jnp.broadcast_to(gv[:, None], scores.shape[:-1])
            codes = softmax_mrq_codes(scores, pv_pack["s1"], g=g, bits=bits,
                                      interpret=True)
            _assert_conforms(
                "vec_const",
                softmax_mrq_codes_vec(scores, pv_pack["s1"], gv=rows,
                                      bits=bits, interpret=True),
                codes)
            _assert_conforms(
                "vec_const",
                int8_bmm_pv_vec(codes, v, pv_pack["s_v"], pv_pack["scale1"],
                                pv_pack["scale2"], gv=gv, bits=bits,
                                interpret=True),
                int8_bmm_pv(codes, v, pv_pack["s_v"], pv_pack["scale1"],
                            pv_pack["scale2"], g=g, bits=bits,
                            interpret=True))
        if G > 1:
            gv = _mix_rows(B, G)
            scores = int8_bmm_qk_vec(q, k, qk_pack["s_q"], qk_pack["s_k"],
                                     qk_pack["scale"], gv=gv, bits=bits,
                                     interpret=True)
            _assert_conforms(
                "attn_qk_vec", scores,
                _jit_ref(ref.int8_bmm_qk_vec_ref, bits=bits)(
                    q, k, qk_pack["s_q"], qk_pack["s_k"], qk_pack["scale"],
                    gv=gv))
            rows = jnp.broadcast_to(gv[:, None], scores.shape[:-1])
            codes = softmax_mrq_codes_vec(scores, pv_pack["s1"], gv=rows,
                                          bits=bits, interpret=True)
            assert codes.dtype == jnp.int8
            _assert_conforms(
                "attn_codes_vec", codes,
                _jit_ref(ref.softmax_mrq_codes_vec_ref, bits=bits)(
                    scores, pv_pack["s1"], gv=rows))
            out = int8_bmm_pv_vec(codes, v, pv_pack["s_v"],
                                  pv_pack["scale1"], pv_pack["scale2"],
                                  gv=gv, bits=bits, interpret=True)
            _assert_conforms(
                "attn_pv_vec", out,
                _jit_ref(ref.int8_bmm_pv_vec_ref, bits=bits)(
                    codes, v, pv_pack["s_v"], pv_pack["scale1"],
                    pv_pack["scale2"], gv=gv))


@pytest.mark.parametrize("shape", ATTN_SHAPES, ids=lambda s: "x".join(map(
    str, s[:4])))
@pytest.mark.parametrize("bname", sorted(BITS))
def test_flash_vector_tgroup_conformance(bname, shape):
    bits = BITS[bname]
    B, Sq, Skv, D, bn = shape
    scale = D ** -0.5
    for G in GROUPS:
        qk_qp, pv_qp = _attn_qparams(G, bits, seed=sum(shape) + G)
        qk_pack = ops.pack_int8_qk(qk_qp)
        pv_pack = ops.pack_int8_pv(pv_qp)
        q, k, v = _attn_case(B, Sq, Skv, D, seed=sum(shape) + 7 * G)
        for g in _g_probes(G):
            gv = jnp.full((B,), g, jnp.int32)
            _assert_conforms(
                "vec_const",
                _flash_vec(q, k, v, qk_pack, pv_pack, gv, scale, bn, bits,
                           packed_kv=(bits == 4)),
                _flash(q, k, v, qk_pack, pv_pack, g, scale, bn, bits,
                       packed_kv=(bits == 4)))
        if G > 1:
            gv = _mix_rows(B, G)
            got = _flash_vec(q, k, v, qk_pack, pv_pack, gv, scale, bn, bits,
                             packed_kv=(bits == 4))
            want = _jit_ref(ref.flash_attn_mrq_vec_ref, bits=bits, bn=bn,
                            scale=scale)(q, k, v, qk_pack, pv_pack,
                                         g_qk=gv, g_pv=gv)
            _assert_conforms("flash_vec", got, want)
            if bits == 4:
                unpacked = _flash_vec(q, k, v, qk_pack, pv_pack, gv, scale,
                                      bn, bits, packed_kv=False)
                _assert_conforms("flash_packed_kv", got, unpacked)


@pytest.mark.parametrize("vec", [False, True], ids=["scalar", "vec"])
@pytest.mark.parametrize("bname", sorted(BITS))
def test_flash_several_q_tiles_one_kv_tile(bname, vec):
    """M = N = 512 at the default bm 256 and kv tile: two q tiles stream
    the same whole-sequence kv tile (the 1,024-token serving grid in
    small), with per-row mixed groups on the vector path."""
    bits = BITS[bname]
    B, S, D, G = 3, 512, 8, 5
    scale = D ** -0.5
    qk_qp, pv_qp = _attn_qparams(G, bits, seed=41)
    qk_pack = ops.pack_int8_qk(qk_qp)
    pv_pack = ops.pack_int8_pv(pv_qp)
    q, k, v = _attn_case(B, S, S, D, seed=43)
    if vec:
        gv = _mix_rows(B, G, salt=1)
        got = _flash_vec(q, k, v, qk_pack, pv_pack, gv, scale, None, bits,
                         packed_kv=(bits == 4))
        want = _jit_ref(ref.flash_attn_mrq_vec_ref, bits=bits,
                        scale=scale)(q, k, v, qk_pack, pv_pack, g_qk=gv,
                                     g_pv=gv)
        _assert_conforms("flash_vec", got, want)
    else:
        got = _flash(q, k, v, qk_pack, pv_pack, G - 1, scale, None, bits,
                     packed_kv=(bits == 4))
        want = _jit_ref(ref.flash_attn_mrq_ref, bits=bits, scale=scale)(
            q, k, v, qk_pack, pv_pack, g_qk=G - 1, g_pv=G - 1)
        _assert_conforms("flash", got, want)


@pytest.mark.parametrize("bname", sorted(BITS))
def test_ops_vector_tgroup_matches_per_slot(bname):
    """The ops-layer contract of the vector-tgroup batched path: ONE call
    over a batch whose slots sit at different timestep groups is bit-
    identical to stacking per-slot scalar-tgroup calls — for the linear
    wrappers (3-D activations, group rows expanded per slot) and both
    attention wrappers (slot-major B·Hk·G row expansion)."""
    bits = BITS[bname]
    G = 3
    tg = jnp.asarray([2, 0, 1], jnp.int32)               # B = 3 slots
    B, T, K, N = 3, 6, 32, 24
    x2, w, bias, qp = _uniform_linear_case(B * T, K, N, G, bits, seed=13)
    x3 = x2.reshape(B, T, K)
    if bits == 4:
        pack = ops.pack_int4_linear(qp, w)
        lin = functools.partial(ops.int4_linear, pack=pack, bias=bias)
    else:
        pack = ops.pack_int8_linear(qp, w)
        lin = functools.partial(ops.int8_linear, pack=pack, bias=bias)
    got = lin(x3, tgroup=tg)
    want = jnp.concatenate([lin(x3[b:b + 1], tgroup=int(tg[b]))
                            for b in range(B)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    Sq, Skv, Hk, Gq, hd = 9, 13, 2, 2, 8
    qk_qp, pv_qp = _attn_qparams(G, bits, seed=3)
    qk_pack = ops.pack_int8_qk(qk_qp)
    pv_pack = ops.pack_int8_pv(pv_qp)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(kq, (B, Sq, Hk, Gq, hd)) * 1.5
    k = jax.random.normal(kk, (B, Skv, Hk, hd)) * 1.5
    v = jax.random.normal(kv, (B, Skv, Hk, hd))
    for attn in (ops.int8_attention, ops.flash_attention):
        got = attn(q, k, v, qk_pack, pv_pack, scale=hd ** -0.5, tgroup=tg)
        want = jnp.concatenate([
            attn(q[b:b + 1], k[b:b + 1], v[b:b + 1], qk_pack, pv_pack,
                 scale=hd ** -0.5, tgroup=int(tg[b])) for b in range(B)])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# end-to-end acceptance: a w4a4 artifact serves through the packed-int4
# kernels, compiled once, agreeing with its own fake-quant oracle
# ---------------------------------------------------------------------------
def test_engine_w4a4_serves_packed_int4_compile_once(tiny_dit, monkeypatch):
    """ServeEngine with a w4a4 QuantArtifact lowers every packed linear
    onto `int4_matmul_fq` / `int4_matmul_mrq_fq` (counted inside the
    scan body), traces ONCE across all timestep groups, and the samples
    agree with the fake-quant oracle on the same artifact.  At
    d_model <= group_k the per-K-group weight scales coincide with the
    per-channel fake-quant scales, so the only divergence is f32
    accumulation order inside the kernel — atol 1e-4 on samples whose
    std is ~1.  (Models with K > group_k genuinely refine the weights
    per group; their oracle is the kernel-vs-ref sweep above, not a
    sample-level identity.)"""
    from repro.diffusion import DiffusionCfg, make_schedule
    from repro.kernels import ops as kops
    from repro.models import dit_apply
    from repro.quant import QuantRecipe, quantize
    from repro.serving import GenRequest, ServeEngine

    cfg, p = tiny_dit
    dif = DiffusionCfg(T=40, tgq_groups=4)
    sched = make_schedule(dif)
    art = quantize(p, cfg, dif, QuantRecipe(bits="w4a4", method="range",
                                            n_per_group=1, calib_batch=1))
    assert art.has_kernel_packs
    n_int4 = sum(1 for qp in art.qparams.values()
                 if "int4" in qp or "int4_mrq" in qp)
    assert n_int4 > 0, "w4a4 quantize() must emit packed-int4 linears"
    assert not any("int8" in qp or "int8_mrq" in qp
                   for qp in art.qparams.values()), \
        "w4a4 linears must not take the byte-code kernels"

    calls = {"fq": 0, "mrq": 0}
    for key, fname in (("fq", "int4_matmul_fq"), ("mrq",
                                                  "int4_matmul_mrq_fq")):
        orig = getattr(kops, fname)
        monkeypatch.setattr(kops, fname, functools.partial(
            lambda orig, key, *a, **kw: (
                calls.__setitem__(key, calls[key] + 1), orig(*a, **kw))[1],
            orig, key))

    traces = []
    orig_apply = dit_apply

    def traced_apply(*a, **kw):
        traces.append(1)
        return orig_apply(*a, **kw)

    import repro.serving.engine as eng_mod
    monkeypatch.setattr(eng_mod, "dit_apply", traced_apply)

    reqs = [GenRequest(request_id=i, label=i % cfg.n_classes, steps=4,
                       cfg_scale=1.5, seed=40 + i) for i in range(2)]
    eng = ServeEngine(p, cfg, dif, sched, ctx=art.context(), microbatch=2,
                      step_buckets=(4,))
    res = eng.serve(reqs)
    assert len(traces) == 1, "sampler retraced across timestep groups"
    assert calls["fq"] > 0, "int4 uniform kernel never fired"
    assert calls["mrq"] > 0, "int4 MRQ (post-GELU fc2) kernel never fired"
    n_fq, n_mrq = calls["fq"], calls["mrq"]
    kern = np.stack([res[i].sample for i in range(2)])
    assert np.isfinite(kern).all()

    eng_fake = ServeEngine(p, cfg, dif, sched, ctx=art.context(kernel=False),
                           microbatch=2, step_buckets=(4,))
    res_fake = eng_fake.serve(reqs)
    assert calls["fq"] == n_fq and calls["mrq"] == n_mrq, \
        "fake-quant oracle must not touch the int4 kernels"
    fake = np.stack([res_fake[i].sample for i in range(2)])
    np.testing.assert_allclose(kern, fake, rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# nibble packing: exhaustive byte sweep + property-based round-trips
# ---------------------------------------------------------------------------
def test_nibble_split_exhaustive_bytes():
    """Every one of the 256 byte patterns splits into two codes in
    [-8, 7] and re-packs to the identical byte — the sign-extension
    ((u ^ 8) - 8) has no wrap/overflow corner anywhere in its domain."""
    from repro.kernels import nibble_split
    bytes_all = jnp.arange(-128, 128, dtype=jnp.int32).astype(jnp.int8)
    lo, hi = nibble_split(bytes_all)
    assert int(lo.min()) >= -8 and int(lo.max()) <= 7
    assert int(hi.min()) >= -8 and int(hi.max()) <= 7
    interleaved = jnp.stack([lo, hi], axis=1).reshape(-1).astype(jnp.int8)
    repacked = pack_int4(interleaved)
    np.testing.assert_array_equal(np.asarray(repacked),
                                  np.asarray(bytes_all))


def test_pack_int4_odd_length_pads_inert_zero():
    codes = jnp.array([[-8, 7], [3, -1], [5, 2]], jnp.int8)    # odd K=3
    packed = pack_int4(codes)                                  # (2, 2)
    assert packed.shape == (2, 2)
    full = unpack_int4(packed)                                 # (4, 2)
    np.testing.assert_array_equal(np.asarray(full[3]), np.zeros(2))
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed, k=3)),
                                  np.asarray(codes))


_hyp_skip = pytest.mark.skipif(not HAVE_HYPOTHESIS,
                               reason="hypothesis not installed")

if HAVE_HYPOTHESIS:
    @_hyp_skip
    @settings(max_examples=60, deadline=None)
    @given(k=st.integers(1, 40), n=st.integers(1, 9),
           axis=st.sampled_from([0, 1, -1]),
           seed=st.integers(0, 2 ** 31 - 1))
    def test_nibble_roundtrip_property(k, n, axis, seed):
        """pack -> unpack identity over random int4 tensors along any
        axis, including odd lengths (one inert zero-pad row)."""
        rng = np.random.default_rng(seed)
        codes = rng.integers(-8, 8, size=(k, n)).astype(np.int8)
        dim = codes.shape[axis]
        packed = pack_int4(jnp.asarray(codes), axis=axis)
        assert packed.shape[axis if axis >= 0 else packed.ndim + axis] \
            == (dim + 1) // 2
        out = unpack_int4(packed, k=dim, axis=axis)
        np.testing.assert_array_equal(np.asarray(out), codes)

    @_hyp_skip
    @settings(max_examples=30, deadline=None)
    @given(k=st.integers(1, 30), n=st.integers(1, 6),
           seed=st.integers(0, 2 ** 31 - 1))
    def test_packed_dequant_matches_unpacked_property(k, n, seed):
        """Dequantizing through the packed representation loses nothing:
        unpack(pack(codes)) * scale == codes * scale elementwise."""
        rng = np.random.default_rng(seed)
        codes = rng.integers(-8, 8, size=(k, n)).astype(np.int8)
        scale = rng.uniform(1e-4, 1e-1, size=(1, n)).astype(np.float32)
        via_pack = np.asarray(unpack_int4(pack_int4(jnp.asarray(codes)),
                                          k=k)).astype(np.float32) * scale
        np.testing.assert_array_equal(via_pack,
                                      codes.astype(np.float32) * scale)
else:                                          # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_nibble_roundtrip_property():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_packed_dequant_matches_unpacked_property():
        pass
