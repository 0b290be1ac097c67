"""Flash-style fused int8 MRQ attention (`kernels/flash_attn_mrq.py`) —
structural and integration tests (the kernel-vs-oracle and
flash-vs-composed shape x bits x group sweeps live in
tests/test_kernel_conformance.py):

- flash vs the COMPOSED three-kernel exactness oracle: bit-tight when
  one kv tile holds the whole row (the online path degenerates to plain
  softmax), and within the documented `ref.flash_vs_composed_atol`
  contract across mixed group repacks and hand-built w6a6 packs;
- the ragged-sequence NEG_INF regression (S=77-style odd lengths whose
  zero-padded kv lanes would otherwise poison the online max);
- mask + GQA equivalence through `ops.flash_attention`;
- `QuantContext.attn_impl` routing ('flash' default / 'composed' /
  invalid), and the engine contract: with the flash default, exactly ONE
  attention kernel fires per block inside a step executable that traces
  once across all timestep groups.

All Pallas calls run in interpret mode on CPU. Kernel-vs-oracle
comparisons allow a few f32 ulp (multi-tile accumulator updates may fuse
differently under jit than the oracle's unrolled loop); flash-vs-composed
uses the documented tolerance contract.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.contexts import QuantContext
from repro.core.quantizers import MRQSoftmaxQ, SymQ, TGQ
from repro.kernels import flash_attn_mrq, int8_bmm_pv, int8_bmm_qk, \
    softmax_mrq_codes
from repro.kernels import ops, ref
from repro.kernels.flash_attn_mrq import flash_attn_mrq_vec


def _attn_qparams(G, seed=0):
    qk = {"x": TGQ(SymQ(scale=jnp.linspace(0.01, 0.05, G), bits=8)),
          "b": TGQ(SymQ(scale=jnp.linspace(0.02, 0.06, G), bits=8))}
    pv = {"x": TGQ(MRQSoftmaxQ(s1=jnp.geomspace(3e-4, 6e-3, G), bits=8)),
          "b": TGQ(SymQ(scale=jnp.linspace(0.01, 0.04, G), bits=8))}
    return qk, pv


def _packs(G, seed=0):
    qk_qp, pv_qp = _attn_qparams(G, seed)
    return ops.pack_int8_qk(qk_qp), ops.pack_int8_pv(pv_qp)


def _case(B, M, N, D, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (B, M, D)) * 2.0
    k = jax.random.normal(k2, (B, N, D)) * 2.0
    v = jax.random.normal(k3, (B, N, D)) * 1.5
    return q, k, v


def _flash(q, k, v, qk_pack, pv_pack, g, scale, bn, bits=8):
    return flash_attn_mrq(
        q, k, v, qk_pack["s_q"], qk_pack["s_k"],
        qk_pack["scale"] * jnp.float32(scale), pv_pack["s1"],
        pv_pack["s_v"], pv_pack["scale1"], pv_pack["scale2"],
        g_qk=g, g_pv=g, bits=bits, bn=bn, interpret=True)


def _composed(q, k, v, qk_pack, pv_pack, g, scale, bits=8):
    """The composed three-KERNEL path on flattened operands."""
    scores = int8_bmm_qk(q, k, qk_pack["s_q"], qk_pack["s_k"],
                         qk_pack["scale"] * jnp.float32(scale), g=g,
                         bits=bits, interpret=True)
    codes = softmax_mrq_codes(scores, pv_pack["s1"], g=g, bits=bits,
                              interpret=True)
    return int8_bmm_pv(codes, v, pv_pack["s_v"], pv_pack["scale1"],
                       pv_pack["scale2"], g=g, bits=bits, interpret=True)


# ---------------------------------------------------------------------------
# flash vs composed: exactness when one tile holds the row, the documented
# tolerance contract when the online rescale actually runs
# ---------------------------------------------------------------------------
def test_flash_single_tile_matches_composed():
    """bn >= Skv: the online softmax degenerates to the plain row softmax
    (one max, one denominator), so flash reproduces the composed
    three-kernel output to f32 ulp."""
    B, M, N, D = 2, 24, 40, 16
    qk_pack, pv_pack = _packs(G=2)
    q, k, v = _case(B, M, N, D, seed=1)
    for g in (0, 1):
        out = _flash(q, k, v, qk_pack, pv_pack, g, D ** -0.5, bn=128)
        want = _composed(q, k, v, qk_pack, pv_pack, g, D ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=0, atol=1e-5)


def test_flash_vs_composed_mixed_group_repack():
    """Per-tensor (G=1) qk pack against a TGQ pv pack — the HO-search
    output shape — resolves each side's group independently and stays
    within tolerance; the stacked packs are equivalent to repacking the
    selected group alone."""
    G = 4
    qk_qp = {"x": SymQ(scale=jnp.float32(0.03), bits=8),
             "b": SymQ(scale=jnp.float32(0.04), bits=8)}
    pv_qp = {"x": TGQ(MRQSoftmaxQ(s1=jnp.geomspace(4e-4, 5e-3, G), bits=8)),
             "b": TGQ(SymQ(scale=jnp.linspace(0.01, 0.04, G), bits=8))}
    qk_pack = ops.pack_int8_qk(qk_qp)
    pv_pack = ops.pack_int8_pv(pv_qp)
    assert qk_pack["groups"] == 1 and pv_pack["groups"] == G
    B, M, N, D, bn = 2, 9, 45, 8, 16
    q, k, v = _case(B, M, N, D, seed=3)
    for g in range(G):
        out = flash_attn_mrq(
            q, k, v, qk_pack["s_q"], qk_pack["s_k"],
            qk_pack["scale"] * jnp.float32(D ** -0.5), pv_pack["s1"],
            pv_pack["s_v"], pv_pack["scale1"], pv_pack["scale2"],
            g_qk=0, g_pv=g, bn=bn, interpret=True)
        pv_g = ops.pack_int8_pv(
            {"x": pv_qp["x"].select(g), "b": pv_qp["b"].select(g)})
        repack = flash_attn_mrq(
            q, k, v, qk_pack["s_q"], qk_pack["s_k"],
            qk_pack["scale"] * jnp.float32(D ** -0.5), pv_g["s1"],
            pv_g["s_v"], pv_g["scale1"], pv_g["scale2"],
            g_qk=0, g_pv=0, bn=bn, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(repack))
        # composed with the same per-side groups (qk g=0, pv g): compose
        # the kernels directly since each takes one g per call
        scores = int8_bmm_qk(q, k, qk_pack["s_q"], qk_pack["s_k"],
                             qk_pack["scale"] * jnp.float32(D ** -0.5),
                             g=0, interpret=True)
        codes = softmax_mrq_codes(scores, pv_pack["s1"], g=g,
                                  interpret=True)
        want = int8_bmm_pv(codes, v, pv_pack["s_v"], pv_pack["scale1"],
                           pv_pack["scale2"], g=g, interpret=True)
        diff = float(jnp.max(jnp.abs(out - want)))
        assert diff <= ref.flash_vs_composed_atol(pv_pack, g, N)


def test_flash_w6a6_within_tolerance():
    """The bit-width knob threads through every stage (q/k/v code range,
    region split, s2 = 1/2^{k-1}); w6a6 flash matches w6a6 composed
    within the bits-aware contract."""
    B, M, N, D, bn = 2, 11, 50, 8, 16
    bits = 6
    s_q = jnp.full((1, 1), 0.08, jnp.float32)
    s_k = jnp.full((1, 1), 0.09, jnp.float32)
    s1 = jnp.full((1, 1), 8e-3, jnp.float32)
    s_v = jnp.full((1, 1), 0.07, jnp.float32)
    half = 2 ** (bits - 1)
    qk_pack = {"s_q": s_q, "s_k": s_k, "scale": s_q * s_k, "groups": 1}
    pv_pack = {"s1": s1, "s_v": s_v, "scale1": s1 * s_v,
               "scale2": (1.0 / half) * s_v, "groups": 1}
    q, k, v = _case(B, M, N, D, seed=4)
    out = _flash(q, k, v, qk_pack, pv_pack, 0, D ** -0.5, bn, bits=bits)
    want = _composed(q, k, v, qk_pack, pv_pack, 0, D ** -0.5, bits=bits)
    diff = float(jnp.max(jnp.abs(out - want)))
    atol = ref.flash_vs_composed_atol(pv_pack, 0, N, bits=bits)
    assert diff <= atol, (diff, atol)


# ---------------------------------------------------------------------------
# ragged sequences: NEG_INF lane masking BEFORE the online max
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("N", [77, 33, 130])
def test_flash_ragged_odd_lengths(N):
    """S not a multiple of the kv tile: padded lanes must be NEG_INF
    masked before the running-max update. The regression construction
    makes every REAL score strongly negative, so an unmasked zero-padded
    lane (int8 codes 0 -> score exactly 0) would capture the row max,
    collapse every real exp() toward zero and poison the denominator —
    producing O(1) garbage instead of the composed output."""
    B, M, D, bn = 2, 9, 8, 32
    qk_pack, pv_pack = _packs(G=2)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(N), 3)
    q = jax.random.normal(k1, (B, M, D)) * 2.0
    # shift k so q·k^T lands far below zero for every real lane
    k = jax.random.normal(k2, (B, N, D)) * 0.5 - 2.0 * jnp.sign(
        q.sum(axis=(1, 2), keepdims=True))
    v = jax.random.normal(k3, (B, N, D))
    for g in (0, 1):
        out = _flash(q, k, v, qk_pack, pv_pack, g, 1.0, bn)
        want = _composed(q, k, v, qk_pack, pv_pack, g, 1.0)
        assert float(jnp.min(ref.int8_bmm_qk_ref(
            q, k, qk_pack["s_q"], qk_pack["s_k"], qk_pack["scale"],
            g=g).max(axis=-1))) < -0.5, "regression needs negative scores"
        diff = float(jnp.max(jnp.abs(out - want)))
        assert diff <= ref.flash_vs_composed_atol(pv_pack, g, N), diff
        # and the probabilities still sum to ~1 through the quantizer:
        # a poisoned denominator would shrink the output toward zero
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=0.2, atol=0.05)


def test_flash_user_mask_matches_composed():
    """ops.flash_attention with a boolean mask (streamed as int8 lanes)
    == ops.int8_attention with the same mask, within tolerance."""
    B, Sq, Skv, Hk, Gq, hd = 2, 7, 21, 2, 2, 8
    qk_qp, pv_qp = _attn_qparams(3, seed=5)
    qk_pack, pv_pack = ops.pack_int8_qk(qk_qp), ops.pack_int8_pv(pv_qp)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(k1, (B, Sq, Hk, Gq, hd)) * 2
    k = jax.random.normal(k2, (B, Skv, Hk, hd)) * 2
    v = jax.random.normal(k3, (B, Skv, Hk, hd))
    mask = jax.random.bernoulli(k4, 0.7, (B, 1, 1, Sq, Skv))
    mask = mask.at[..., :1].set(True)            # no fully-masked rows
    out = ops.flash_attention(q, k, v, qk_pack, pv_pack, mask=mask,
                              scale=hd ** -0.5, tgroup=1)
    want = ops.int8_attention(q, k, v, qk_pack, pv_pack, mask=mask,
                              scale=hd ** -0.5, tgroup=1)
    atol = ref.flash_vs_composed_atol(pv_pack, 1, Skv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=0, atol=atol)

    # multi-tile mask streaming (kernel-level: bn < Skv, int8 mask lanes
    # NEG_INF'd ahead of the online max alongside the ragged lanes)
    BHG = B * Hk * Gq
    qf = q.transpose(0, 2, 3, 1, 4).reshape(BHG, Sq, hd)
    kf = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (B, Hk, Gq, Skv, hd)).reshape(BHG, Skv, hd)
    vf = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (B, Hk, Gq, Skv, hd)).reshape(BHG, Skv, hd)
    mf = jnp.broadcast_to(mask, (B, Hk, Gq, Sq, Skv)).reshape(BHG, Sq, Skv)
    out_t = flash_attn_mrq(
        qf, kf, vf, qk_pack["s_q"], qk_pack["s_k"],
        qk_pack["scale"] * jnp.float32(hd ** -0.5), pv_pack["s1"],
        pv_pack["s_v"], pv_pack["scale1"], pv_pack["scale2"],
        g_qk=1, g_pv=1, mask=mf, bn=8, interpret=True)
    out_t = out_t.reshape(B, Hk, Gq, Sq, hd).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(np.asarray(out_t), np.asarray(want),
                               rtol=0, atol=atol)


def _pallas_operands(jaxpr):
    """The operand avals of the one ``pallas_call`` inside ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return [v.aval for v in eqn.invars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found = _pallas_operands(sub)
            if found:
                return found
    return None


@pytest.mark.parametrize("path", ["scalar", "vec", "ops"])
@pytest.mark.parametrize("bits", [8, 4])
def test_flash_streams_int8_codes(bits, path):
    """q, k and v reach the kernel as s8 codes formed once before it, not
    as f32 for the kernel to quantize at every grid step: in the wrappers'
    layout pass for float inputs, and ahead of the head transposes on the
    served path (``ops.flash_attention``). At 4 bits the k/v codes are
    nibble-packed, two per byte along D."""
    B, M, N, D = 2, 9, 20, 16
    qk_pack, pv_pack = _packs(G=3)
    q, k, v = (x.astype(jnp.bfloat16) for x in _case(B, M, N, D, seed=12))
    if path == "ops":
        qk_pack, pv_pack = (dict(p, bits=bits) for p in (qk_pack, pv_pack))
        fn = functools.partial(
            ops.flash_attention, q[:, :, None, None], k[:, :, None],
            v[:, :, None], qk_pack, pv_pack, tgroup=jnp.asarray([2, 0]))
    else:
        g = jnp.asarray([2, 0], jnp.int32) if path == "vec" else 1
        fn = functools.partial(
            flash_attn_mrq_vec if path == "vec" else flash_attn_mrq,
            q, k, v, qk_pack["s_q"], qk_pack["s_k"], qk_pack["scale"],
            pv_pack["s1"], pv_pack["s_v"], pv_pack["scale1"],
            pv_pack["scale2"], g_qk=g, g_pv=g, bits=bits,
            packed_kv=(bits == 4), interpret=True)
    avals = _pallas_operands(jax.make_jaxpr(fn)().jaxpr)
    q8, k8, v8 = avals[1:4]                 # after the prefetched groups
    assert (q8.dtype, k8.dtype, v8.dtype) == (jnp.int8,) * 3
    assert q8.shape[-1] == D
    assert k8.shape[-1] == v8.shape[-1] == (D // 2 if bits == 4 else D)
    # four param rows remain: qk_scale, s1, scale1, scale2
    assert len(avals) == 1 + 3 + 4


def test_flash_gqa_shared_kv():
    """rep query-group batches share each kv head via the b // rep index
    map — identical to feeding materialized kv copies."""
    B, rep, M, N, D, bn = 2, 3, 9, 20, 8, 8
    qk_pack, pv_pack = _packs(G=2)
    q, _, v_ = _case(B * rep, M, N, D, seed=7)
    k = jax.random.normal(jax.random.PRNGKey(8), (B, N, D)) * 2
    v = jax.random.normal(jax.random.PRNGKey(9), (B, N, D))
    out = _flash(q, k, v, qk_pack, pv_pack, 1, D ** -0.5, bn)
    want = _flash(q, jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0),
                  qk_pack, pv_pack, 1, D ** -0.5, bn)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ---------------------------------------------------------------------------
# QuantContext attn_impl routing
# ---------------------------------------------------------------------------
def test_quant_context_attn_impl_routing(monkeypatch):
    qk_qp, pv_qp = _attn_qparams(2, seed=10)
    qparams = {"attn/qk": dict(qk_qp, int8_qk=ops.pack_int8_qk(qk_qp)),
               "attn/pv": dict(pv_qp, int8_pv=ops.pack_int8_pv(pv_qp))}
    B, S, Hk, hd = 1, 6, 2, 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(k1, (B, S, Hk, 1, hd))
    k = jax.random.normal(k2, (B, S, Hk, hd))
    v = jax.random.normal(k3, (B, S, Hk, hd))

    calls = {"flash": 0, "composed": 0}
    orig_f, orig_c = ops.flash_attention, ops.int8_attention
    monkeypatch.setattr(ops, "flash_attention", lambda *a, **kw: (
        calls.__setitem__("flash", calls["flash"] + 1), orig_f(*a, **kw))[1])
    monkeypatch.setattr(ops, "int8_attention", lambda *a, **kw: (
        calls.__setitem__("composed", calls["composed"] + 1),
        orig_c(*a, **kw))[1])

    y_flash = QuantContext(qparams=qparams, kernel=True, tgroup=0).attention(
        "attn", q, k, v, scale=hd ** -0.5)      # default impl == flash
    assert calls == {"flash": 1, "composed": 0}
    y_comp = QuantContext(qparams=qparams, kernel=True, tgroup=0,
                          attn_impl="composed").attention(
        "attn", q, k, v, scale=hd ** -0.5)
    assert calls == {"flash": 1, "composed": 1}
    # single kv tile at this size: the two impls agree to f32 ulp
    np.testing.assert_allclose(np.asarray(y_flash), np.asarray(y_comp),
                               rtol=0, atol=1e-5)
    with pytest.raises(ValueError, match="attn_impl"):
        QuantContext(qparams=qparams, kernel=True,
                     attn_impl="fused").attention(
            "attn", q, k, v, scale=hd ** -0.5)


# ---------------------------------------------------------------------------
# serving: ONE attention kernel per block, one trace across all groups
# ---------------------------------------------------------------------------
def test_engine_flash_compile_once_single_attention_kernel(tiny_dit,
                                                           monkeypatch):
    """With the flash default, the engine's w8a8 step executable lowers
    each block's attention to exactly ONE kernel (`flash_attn_mrq`) —
    the composed trio must not fire at all — traced ONCE across all
    timestep groups of the scan, with finite samples."""
    from repro.diffusion import DiffusionCfg, make_schedule
    from repro.kernels import ops as kops
    from repro.serving import GenRequest, ServeEngine
    from repro.serving.quickcal import range_calibrate

    cfg, p = tiny_dit
    dif = DiffusionCfg(T=40, tgq_groups=4)
    sched = make_schedule(dif)
    qp, weights = range_calibrate(p, cfg, dif, sched, n_per_group=1, batch=1)
    qp2 = kops.convert_for_kernels(qp, weights)
    ctx = QuantContext(qparams=qp2, kernel=True)          # flash default

    calls = {"flash": 0, "qk": 0, "sm": 0, "pv": 0}
    for key, fname in (("flash", "flash_attn_mrq"), ("qk", "int8_bmm_qk"),
                       ("sm", "softmax_mrq_codes"), ("pv", "int8_bmm_pv")):
        orig = getattr(kops, fname)
        monkeypatch.setattr(kops, fname, functools.partial(
            lambda orig, key, *a, **kw: (
                calls.__setitem__(key, calls[key] + 1), orig(*a, **kw))[1],
            orig, key))

    traces = []
    from repro.models import dit_apply as orig_apply
    import repro.serving.engine as eng_mod
    monkeypatch.setattr(eng_mod, "dit_apply", lambda *a, **kw: (
        traces.append(1), orig_apply(*a, **kw))[1])

    eng = ServeEngine(p, cfg, dif, sched, ctx=ctx, microbatch=2,
                      step_buckets=(4,))
    reqs = [GenRequest(request_id=i, label=i % cfg.n_classes, steps=4,
                       cfg_scale=1.5, seed=70 + i) for i in range(2)]
    res = eng.serve(reqs)
    assert len(traces) == 1, "sampler retraced across timestep groups"
    assert calls["flash"] == cfg.n_layers, calls
    assert calls["qk"] == calls["sm"] == calls["pv"] == 0, \
        f"composed kernels fired alongside flash: {calls}"
    s = np.stack([res[i].sample for i in range(2)])
    assert np.isfinite(s).all()


# ---------------------------------------------------------------------------
# modeled traffic: the (S,S) round-trip is eliminated
# ---------------------------------------------------------------------------
def test_flash_traffic_floor():
    from repro.kernels.flash_attn_mrq import DEFAULT_BM
    from benchmarks.kernel_micro import traffic_attention_flash
    # DiT-XL/2 attention: 256 tokens, 16 heads, hd 72 — one q-tile at
    # the kernel's default bm, so K/V genuinely stream from HBM once
    assert DEFAULT_BM >= 256
    t = traffic_attention_flash(BH=16, S=256, D=72)
    # acceptance floor: >= 3x whole-attention traffic cut at S >= 256
    assert t["composed"] / t["flash"] >= 3.0
    # what was eliminated is exactly the (S,S) scores (f32 write+read)
    # + codes (int8 write+read) round-trip
    assert t["scores_codes_eliminated"] == 16 * 256 * 256 * 10
    assert t["composed"] - t["flash"] == t["scores_codes_eliminated"]
    # flash reads q/k/v and writes out, each once, in f32
    assert t["flash"] == 4 * 16 * 256 * 72 * 4

    # the model charges kv RE-READS honestly when bm < S (the kernel
    # re-fetches every k/v tile once per q-tile): 2 q-tiles at bm=128
    t2 = traffic_attention_flash(BH=16, S=256, D=72, bm=128)
    assert t2["flash"] == 16 * 256 * 72 * 4 * (2 + 2 * 2)
    assert t2["composed"] == t["composed"]
    # still a large win, but smaller — and never overstated
    assert t["composed"] / t2["flash"] < t["composed"] / t["flash"]
    assert t["composed"] / t2["flash"] >= 2.0
