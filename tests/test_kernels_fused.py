"""Fused int8 serving kernels — structural and integration tests: block
shape overrides, TGQ group sweeps (bit-identical to per-group
repacking), fused-vs-unfused equivalence, kernel-path routing for
TGQ-wrapped ops, and the compile-once contract of ``ddpm_sample`` with
``QuantContext(kernel=True)``. The kernel-vs-oracle shape x bits x group
sweeps live in tests/test_kernel_conformance.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.contexts import QuantContext
from repro.core.quantizers import (
    ChannelQ, MRQSignedQ, TGQ, UniformQ, channel_scale_from_absmax,
    uniform_params_from_range, weight_absmax,
)
from repro.kernels import int8_matmul, int8_matmul_fq, int8_matmul_mrq_fq
from repro.kernels import ops, ref


def _rand_case(M, K, N, G, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k1, (M, K)) * 2.0
    wq = jax.random.randint(k2, (K, N), -128, 128, jnp.int32).astype(jnp.int8)
    sx = (jax.random.uniform(k3, (G, 1)) * 0.05 + 0.01).astype(jnp.float32)
    zx = jnp.round(jax.random.uniform(k1, (G, 1)) * 200.0)
    scale = (jax.random.uniform(k2, (G, N)) * 1e-3 + 1e-5).astype(jnp.float32)
    colsum = jnp.sum(wq.astype(jnp.int32), axis=0)
    corr = (jnp.round(zx).astype(jnp.int32) - 128) * colsum[None, :]
    bias = jax.random.normal(k3, (N,))
    return x, wq, sx, zx, scale, corr, bias


# ---------------------------------------------------------------------------
# fused-quantize matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("block", [(32, 128), (128, 256)])
def test_int8_matmul_fq_block_shapes(block):
    """Overridden (bm, bn) tiles: several row and column blocks over
    ragged M and N (K is always one block)."""
    bm, bn = block
    x, wq, sx, zx, scale, corr, _ = _rand_case(100, 300, 290, G=2, seed=1)
    out = int8_matmul_fq(x, wq, sx, zx, scale, corr, g=1, bm=bm, bn=bn,
                         interpret=True)
    want = ref.int8_matmul_fq_ref(x, wq, sx, zx, scale, corr, g=1)
    assert float(jnp.max(jnp.abs(out - want))) <= 1e-4


# (kernel, fusion, bm): B = 4 entries of T = 64 rows, so bm 64 takes the
# adaLN rows from the index maps and bm 128 ("nm-rows") selects each
# row's entry in VMEM; bm 64 and 128 quantize in 2 and 4 row slices.
_TILE_KERNELS = ("fq", "mrq", "fq_vec", "mrq_vec")
_TILE_FUSIONS = (("none", 64), ("nm", 64), ("nm-rows", 128), ("gr", 64),
                 ("ps", 64))


@pytest.mark.parametrize("fusion,bm", _TILE_FUSIONS,
                         ids=[f for f, _ in _TILE_FUSIONS])
@pytest.mark.parametrize("kernel", _TILE_KERNELS)
def test_int8_fused_tiles_bit_identical(kernel, fusion, bm):
    """Several m and n tiles (4 or 2 row blocks x 3 weight blocks, N
    ragged) with the overrides: the int8 codes quantized at each row
    block's first n step are reused by its other n steps and refreshed
    at the next row block, bit-identically to the jitted oracles."""
    from repro.kernels.int8_fused import (
        int8_matmul_fq_vec, int8_matmul_mrq_fq_vec,
    )
    B, T, K, N, G = 4, 64, 200, 300, 3
    M = B * T
    x, wq, sx, zx, scale, corr, bias = _rand_case(M, K, N, G, seed=21)
    ks = jax.random.split(jax.random.PRNGKey(22), 5)
    bv = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
    fkw = {}
    if fusion in ("nm", "nm-rows"):
        fkw["nm"] = (jax.random.normal(ks[0], (B, K)) * 0.5,
                     jax.random.normal(ks[1], (B, K)) * 0.2)
    elif fusion == "gr":
        fkw["gr"] = (jax.random.normal(ks[2], (B, N)) * 0.8,
                     jax.random.normal(ks[3], (M, N)))
    elif fusion == "ps":
        fkw["ps"] = jnp.exp(jax.random.uniform(ks[4], (K,), minval=-1.0,
                                               maxval=1.0))
    rkw = dict(fkw, bv=bv)
    if "nm" in fkw or "gr" in fkw:
        fkw["rows_per_batch"] = T
    gv = jnp.repeat(jnp.arange(B, dtype=jnp.int32) % G, T)
    if kernel.startswith("mrq"):
        x = jax.nn.gelu(x)
        params = (x, wq, sx * 0.1, sx, scale * 0.1, scale, bias)
        kern = int8_matmul_mrq_fq_vec if kernel == "mrq_vec" else \
            int8_matmul_mrq_fq
        oracle = ref.int8_matmul_mrq_fq_vec_fused_ref \
            if kernel == "mrq_vec" else ref.int8_matmul_mrq_fq_fused_ref
    else:
        params = (x, wq, sx, zx, scale, corr, bias)
        kern = int8_matmul_fq_vec if kernel == "fq_vec" else int8_matmul_fq
        oracle = ref.int8_matmul_fq_vec_fused_ref if kernel == "fq_vec" \
            else ref.int8_matmul_fq_fused_ref
    grp = {"gv": gv} if kernel.endswith("_vec") else {"g": 2}
    got = kern(*params, **grp, bm=bm, bn=128, interpret=True, **fkw)
    want = jax.jit(oracle)(*params, **grp, **rkw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# DiT-XL/2's linears as served: (site, K, N, MRQ input, per-batch fusion).
# The pool runs 4,096 token rows at 256 tokens (8 slots x 2 CFG halves)
# and at 1,024 (2 x 2); the per-sample sites take one row per entry.
_XL2_SITES = [
    ("x_proj", 16, 1152, False, False), ("t_mlp1", 256, 1152, False, False),
    ("t_mlp2", 1152, 1152, False, False), ("ada", 1152, 6912, False, False),
    ("qkv", 1152, 3456, False, True), ("proj", 1152, 1152, False, True),
    ("fc1", 1152, 4608, False, True), ("fc2", 4608, 1152, True, True),
    ("final_ada", 1152, 2304, False, False), ("final", 1152, 16, False, True),
]
_PER_SAMPLE = ("t_mlp1", "t_mlp2", "ada", "final_ada")


@pytest.mark.parametrize("tokens", [256, 1024])
@pytest.mark.parametrize("site,K,N,mrq,fused", _XL2_SITES,
                         ids=[c[0] for c in _XL2_SITES])
def test_fq_plan_at_served_shapes(site, K, N, mrq, fused, tokens):
    """The planned tiles quantize each x element once, keep the adaLN
    rows on the index-map path, stream each weight byte into at least 512
    operations at 4,096 rows, fit the VMEM limit they ask for, and take
    under 200 grid steps a call (the k-tiled grid took up to 5,760)."""
    from repro.kernels.int8_fused import _fq_plan
    entries = 4096 // tokens
    M = entries if site in _PER_SAMPLE else 4096
    plan = _fq_plan(M, K, N, x_itemsize=2, mrq=mrq,
                    rows_per_batch=tokens if fused else None)
    assert plan.quant_passes == 1
    if fused:
        assert tokens % plan.bm == 0
    if M == 4096:
        assert plan.bm >= 256
    assert plan.vmem_bytes <= plan.vmem_limit_bytes <= 128 * 2 ** 20
    assert plan.steps < 200
    assert plan.grid == (plan.Mp // plan.bm, plan.Np // plan.bn, 1)
    assert plan.Np == N and plan.Mp == max(M, 8)


def test_int8_matmul_fq_matches_unfused_pipeline():
    """Fused == standalone quantize pass + pre-quantized-codes matmul."""
    M, K, N = 64, 160, 48
    x, wq, sx, zx, scale, corr, bias = _rand_case(M, K, N, G=2, seed=7)
    g = 1
    xq = ops.quantize_int8(x, sx[g, 0], zx[g, 0])
    unfused = int8_matmul(xq, wq, scale[g], corr[g], bias, interpret=True)
    fused = int8_matmul_fq(x, wq, sx, zx, scale, corr, bias, g=g,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


# ---------------------------------------------------------------------------
# single-pass MRQ matmul
# ---------------------------------------------------------------------------
def test_mrq_single_pass_matches_two_matmul_decomposition():
    """The collapsed kernel reproduces the old twin-region TWO-matmul path."""
    M, K, N = 48, 96, 64
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.nn.gelu(jax.random.normal(k1, (M, K)) * 2.0)
    wq = jax.random.randint(k2, (K, N), -128, 128, jnp.int32).astype(jnp.int8)
    s_neg, s_pos = jnp.float32(1.5e-3), jnp.float32(2.5e-2)
    sw = jax.random.uniform(k1, (N,)) * 1e-2 + 1e-4
    half = 128
    neg = x < 0
    qn = jnp.where(neg, jnp.clip(jnp.round(x / s_neg), -half, 0),
                   0).astype(jnp.int8)
    qp = jnp.where(neg, 0, jnp.clip(jnp.round(x / s_pos), 0, half - 1)
                   ).astype(jnp.int8)
    zc = jnp.zeros((N,), jnp.int32)
    yn = int8_matmul(qn, wq, s_neg * sw, zc, interpret=True)
    yp = int8_matmul(qp, wq, s_pos * sw, zc, interpret=True)
    two_pass = yn + yp
    one_pass = int8_matmul_mrq_fq(
        x, wq, s_neg.reshape(1, 1), s_pos.reshape(1, 1),
        (s_neg * sw).reshape(1, -1), (s_pos * sw).reshape(1, -1),
        interpret=True)
    np.testing.assert_allclose(np.asarray(one_pass), np.asarray(two_pass),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# adaLN fusions: per-batch rows by index map vs per-row select
# ---------------------------------------------------------------------------
def _int4_operands(wq, scale, corr, group_k):
    """int4 form of an int8 case: nibble-packed 4-bit codes and per-K-group
    (G, nk, N) scale/correction stacks."""
    from repro.kernels.int4_packed import pack_int4
    K = wq.shape[0]
    w4 = jnp.clip(wq.astype(jnp.int32) // 16, -8, 7).astype(jnp.int8)
    nk = K // group_k
    return (pack_int4(w4, axis=0), jnp.repeat(scale[:, None], nk, axis=1),
            jnp.repeat(corr[:, None] // 16, nk, axis=1))


@pytest.mark.parametrize("kernel", ["int8_fq", "int8_mrq_vec", "int4_fq_vec",
                                    "int4_mrq"])
def test_fusion_batch_rows_by_index_map_match_select(kernel):
    """When a batch entry spans whole row tiles (rows_per_batch a multiple
    of bm, as at DiT-XL/2: 256 tokens, bm 128), the norm-modulate
    prologue and gate epilogue take each tile's adaLN row from the index
    map instead of selecting it per row among all entries. Both paths
    give bit-identical results (bm 96 forces the select path)."""
    from repro.kernels.int4_packed import (
        int4_matmul_fq_vec, int4_matmul_mrq_fq,
    )
    from repro.kernels.int8_fused import int8_matmul_mrq_fq_vec
    B, T, K, N, G = 3, 128, 256, 128, 3
    M = B * T
    x, wq, sx, zx, scale, corr, bias = _rand_case(M, K, N, G, seed=11)
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    nm = (jax.random.normal(ks[0], (B, K)) * 0.5,
          jax.random.normal(ks[1], (B, K)) * 0.2)
    gr = (jax.random.normal(ks[2], (B, N)) * 0.8,
          jax.random.normal(ks[3], (M, N)))
    gv = jnp.repeat(jnp.arange(B, dtype=jnp.int32) % G, T)
    s_neg, s_pos = sx * 0.1, sx
    if kernel == "int8_fq":
        call = lambda **kw: int8_matmul_fq(x, wq, sx, zx, scale, corr, bias,
                                           g=1, **kw)
    elif kernel == "int8_mrq_vec":
        call = lambda **kw: int8_matmul_mrq_fq_vec(
            x, wq, s_neg, s_pos, scale * 0.1, scale, bias, gv=gv, **kw)
    else:
        wp, sc4, corr4 = _int4_operands(wq, scale, corr, 128)
        if kernel == "int4_fq_vec":
            call = lambda **kw: int4_matmul_fq_vec(
                x, wp, sx, zx, sc4, corr4, bias, gv=gv, group_k=128, **kw)
        else:
            call = lambda **kw: int4_matmul_mrq_fq(
                x, wp, s_neg, s_pos, sc4 * 0.1, sc4, bias, g=1, group_k=128,
                **kw)
    by_map = call(nm=nm, gr=gr, rows_per_batch=T, bm=128, interpret=True)
    by_select = call(nm=nm, gr=gr, rows_per_batch=T, bm=96, interpret=True)
    assert bool(jnp.all(jnp.isfinite(by_map)))
    np.testing.assert_array_equal(np.asarray(by_map), np.asarray(by_select))


# ---------------------------------------------------------------------------
# TGQ packing: group sweep bit-identical to per-group repacking
# ---------------------------------------------------------------------------
def _tgq_uniform_qp(key, K, N, G):
    kx, kw = jax.random.split(key)
    w = jax.random.normal(kw, (K, N)) * 0.05
    scales = jnp.linspace(0.01, 0.05, G)
    zeros = jnp.round(jnp.linspace(90.0, 150.0, G))
    qp = {"x": TGQ(UniformQ(scale=scales, zero=zeros, bits=8)),
          "w": ChannelQ(channel_scale_from_absmax(weight_absmax(w), 8), 8)}
    return qp, w


def test_tgq_uniform_pack_group_sweep():
    """Every group g of the stacked pack is bit-identical to repacking the
    scalar group-g quantizer on its own (the old per-group Python path)."""
    K, N, G = 96, 80, 5
    qp, w = _tgq_uniform_qp(jax.random.PRNGKey(0), K, N, G)
    pack = ops.pack_int8_linear(qp, np.asarray(w))
    assert pack is not None and pack["groups"] == G
    x = jax.random.normal(jax.random.PRNGKey(1), (33, K)) * 2
    tq: TGQ = qp["x"]
    for g in range(G):
        qp_g = {"x": tq.select(g), "w": qp["w"]}
        pack_g = ops.pack_int8_linear(qp_g, np.asarray(w))
        assert pack_g is not None and pack_g["groups"] == 1
        y_tgq = ops.int8_linear(x, pack, tgroup=g)
        y_repack = ops.int8_linear(x, pack_g)
        np.testing.assert_array_equal(np.asarray(y_tgq), np.asarray(y_repack))


def test_tgq_mrq_pack_group_sweep():
    K, N, G = 64, 48, 4
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    w = jax.random.normal(kw, (K, N)) * 0.05
    qp = {"x": TGQ(MRQSignedQ(s_neg=jnp.linspace(1e-3, 3e-3, G),
                              s_pos=jnp.linspace(1e-2, 4e-2, G), bits=8)),
          "w": ChannelQ(channel_scale_from_absmax(weight_absmax(w), 8), 8)}
    pack = ops.pack_int8_mrq_linear(qp, np.asarray(w))
    assert pack is not None and pack["groups"] == G
    x = jax.nn.gelu(jax.random.normal(kx, (17, K)) * 1.5)
    tq: TGQ = qp["x"]
    for g in range(G):
        pack_g = ops.pack_int8_mrq_linear({"x": tq.select(g), "w": qp["w"]},
                                          np.asarray(w))
        y_tgq = ops.int8_linear_mrq(x, pack, tgroup=g)
        y_repack = ops.int8_linear_mrq(x, pack_g)
        np.testing.assert_array_equal(np.asarray(y_tgq), np.asarray(y_repack))


# ---------------------------------------------------------------------------
# routing: TGQ-wrapped W8A8 linears take the kernel path (no fallback)
# ---------------------------------------------------------------------------
def test_tgq_uniform_routes_through_kernel():
    K, N, G = 64, 32, 4
    qp, w = _tgq_uniform_qp(jax.random.PRNGKey(4), K, N, G)
    qp2 = ops.convert_for_kernels({"lin": qp}, {"lin": np.asarray(w)})
    assert "int8" in qp2["lin"], "TGQ(UniformQ) must pack, not fall back"
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, K))
    for g in range(G):
        y_kern = QuantContext(qparams=qp2, kernel=True,
                              tgroup=g).linear("lin", x, w)
        y_fake = QuantContext(qparams=qp2, tgroup=g).linear("lin", x, w)
        np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_fake),
                                   rtol=1e-4, atol=1e-4)


def test_tgq_mrq_routes_through_kernel():
    K, N, G = 48, 32, 3
    kw = jax.random.PRNGKey(6)
    w = jax.random.normal(kw, (K, N)) * 0.05
    x = jax.nn.gelu(jax.random.normal(jax.random.PRNGKey(7), (2, 7, K)))
    qp = {"fc2": {
        "x": TGQ(MRQSignedQ(s_neg=jnp.full((G,), float(-x.min()) / 128),
                            s_pos=jnp.full((G,), float(x.max()) / 128),
                            bits=8)),
        "w": ChannelQ(channel_scale_from_absmax(weight_absmax(w), 8), 8)}}
    qp2 = ops.convert_for_kernels(qp, {"fc2": np.asarray(w)})
    assert "int8_mrq" in qp2["fc2"]
    y_kern = QuantContext(qparams=qp2, kernel=True, tgroup=1).linear(
        "fc2", x, w)
    y_fake = QuantContext(qparams=qp2, tgroup=1).linear("fc2", x, w)
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_fake),
                               rtol=1e-3, atol=2e-3)


def test_channel_balanced_ops_pack_with_prescale_folded():
    """Ops with an x_prescale (PTQ4DiT-style channel balancing) pack like
    everything else: the balance divide runs in the kernel's quantize
    prologue (``pack["x_prescale"]``) and its inverse is baked into the
    weight codes (built from w*ps — exactly the tensor the calibrated
    ``ChannelQ`` saw). Kernel path ≡ fake-quant path bit-for-bit."""
    K, N = 24, 16
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (K, N)) * 0.05)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, K))
    ps = jnp.linspace(0.5, 2.0, K)
    ws = jnp.asarray(w) * ps[:, None]
    s, z = uniform_params_from_range((x / ps).min(), (x / ps).max(), 8)
    qp = {"lin": {
        "x": UniformQ(s, z, 8),
        "w": ChannelQ(channel_scale_from_absmax(weight_absmax(ws), 8), 8),
        "x_prescale": ps}}
    out = ops.convert_for_kernels(qp, {"lin": w})
    assert "int8" in out["lin"], "channel-balanced op must pack"
    np.testing.assert_array_equal(np.asarray(out["lin"]["int8"]["x_prescale"]),
                                  np.asarray(ps, np.float32))
    # the packed codes must be the codes calibration measured (on w*ps)
    codes_cal = np.asarray(jnp.clip(
        jnp.round(ws / qp["lin"]["w"].scale.reshape(1, -1)), -127, 127),
        np.int8)
    np.testing.assert_array_equal(np.asarray(out["lin"]["int8"]["wq"]),
                                  codes_cal)
    y_fake = QuantContext(qparams=out).linear("lin", x, jnp.asarray(w))
    y_kern = QuantContext(qparams=out, kernel=True).linear(
        "lin", x, jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(y_fake), np.asarray(y_kern),
                               rtol=0, atol=1e-5)


def test_per_tensor_pack_still_works():
    """Plain UniformQ packs as G=1 and ignores any tgroup passed at serve."""
    x = jax.random.normal(jax.random.PRNGKey(0), (11, 24))
    w = jax.random.normal(jax.random.PRNGKey(1), (24, 16)) * 0.05
    s, z = uniform_params_from_range(x.min(), x.max(), 8)
    qp = {"x": UniformQ(s, z, 8),
          "w": ChannelQ(channel_scale_from_absmax(weight_absmax(w), 8), 8)}
    pack = ops.pack_int8_linear(qp, np.asarray(w))
    assert pack["groups"] == 1
    y0 = ops.int8_linear(x, pack)
    y9 = ops.int8_linear(x, pack, tgroup=9)     # clamped to the only group
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y9))


# ---------------------------------------------------------------------------
# modeled HBM-traffic floors (the structural saving the fusion buys)
# ---------------------------------------------------------------------------
def test_traffic_model_floors():
    from benchmarks.kernel_micro import (traffic_int8_linear,
                                         traffic_mrq_linear)
    # DiT-XL/2 fc2-shaped case: one W pass instead of two -> >=1.5x
    t = traffic_mrq_linear(256, 4608, 1152)
    assert t["unfused"] / t["fused"] >= 1.5
    # plain linear: the fused path must not charge the standalone
    # quantize-pass bytes (fp32 read + int8 write of x) while the unfused
    # path must include them
    M, K, N = 256, 2048, 2048
    t = traffic_int8_linear(M, K, N)
    assert t["unfused"] - t["fused"] >= M * K * 1 + M * K * 4
    assert t["fused"] == M * K * 4 + K * N + M * N * 4


def test_fusion_residue_traffic_model():
    """The adaLN prologue/epilogue fusions: every chain byte in the XL/2
    block is served by a fusion (zero uncharged residue), the charged
    fused-operand bytes are strictly below the eliminated chain bytes at
    every fused site, and the block aggregate clears the >=1.15x CI gate
    vs the pre-fusion baseline."""
    from benchmarks.kernel_micro import (
        fused_block_traffic, traffic_gate_residual_fusion,
        traffic_norm_mod_fusion)
    t = fused_block_traffic()
    assert t["residue_adaln_residual"] == 0
    assert t["unfused"] / t["fused"] >= 1.15
    for name, fusion, ts in t["sites"]:
        if fusion is not None:
            assert ts["charged_bytes"] < ts["chain_bytes"], name
            assert ts["fused"] < ts["unfused"], name
    # per-site models at the fc2 shape: the gate+residual epilogue saves
    # the full 12B/elt output chain minus the streamed residual + gate
    M, B, K, N = 1024, 4, 4608, 1152
    tg = traffic_gate_residual_fusion(M, B, K, N)
    assert tg["unfused"] - tg["fused"] == 8 * M * N - 4 * B * N
    tn = traffic_norm_mod_fusion(M, B, N, K)
    assert tn["unfused"] - tn["fused"] == 4 * M * N - 16 * M - 8 * B * N


# ---------------------------------------------------------------------------
# compile-once contract: one executable across all timestep groups
# ---------------------------------------------------------------------------
def test_ddpm_sample_kernel_path_compiles_once(monkeypatch):
    """``ddpm_sample`` with ``QuantContext(kernel=True)`` and TGQ-packed
    int8 linears must trace/compile ONCE — the traced group index is
    resolved inside the kernel, never by Python-level repacking."""
    from repro.diffusion import DiffusionCfg, ddpm_sample, make_schedule
    from repro.kernels import ops as kops

    B, H, W_, C = 2, 4, 4, 1
    K = H * W_ * C
    G = 4
    dif = DiffusionCfg(T=40, tgq_groups=G)
    sched = make_schedule(dif)
    qp, w = _tgq_uniform_qp(jax.random.PRNGKey(8), K, K, G)
    qp2 = ops.convert_for_kernels({"lin": qp}, {"lin": np.asarray(w)})
    assert "int8" in qp2["lin"]
    qctx = QuantContext(qparams=qp2, kernel=True)

    kernel_calls = []
    orig_fq = kops.int8_matmul_fq
    monkeypatch.setattr(
        kops, "int8_matmul_fq",
        lambda *a, **k: (kernel_calls.append(1), orig_fq(*a, **k))[1])

    traces = []

    def eps_fn(x, t, y, ctx):
        traces.append(1)                      # fires once per (re)trace
        out = ctx.linear("lin", x.reshape(x.shape[0], -1), w)
        return out.reshape(x.shape)

    sample = jax.jit(lambda key: ddpm_sample(
        eps_fn, dif, sched, (B, H, W_, C), jnp.zeros((B,), jnp.int32), key,
        steps=8, ctx=qctx))
    out1 = sample(jax.random.PRNGKey(0))
    n_traces_first = len(traces)
    n_kernel_first = len(kernel_calls)
    assert n_traces_first == 1, "sampler retraced across timestep groups"
    assert n_kernel_first >= 1, "int8 kernel path was not taken"
    out2 = sample(jax.random.PRNGKey(1))
    assert len(traces) == n_traces_first, "second call recompiled"
    assert len(kernel_calls) == n_kernel_first
    assert bool(jnp.all(jnp.isfinite(out1))) and bool(
        jnp.all(jnp.isfinite(out2)))


# ---------------------------------------------------------------------------
# end-to-end: channel-balanced w8a8 serves fully on kernels (zero
# fallback packs), fused adaLN prologues/epilogues active, compiled once
# ---------------------------------------------------------------------------
def test_engine_w8a8_channel_balance_zero_fallback_fused_serve(
        tiny_dit, monkeypatch):
    """The prescale-fold regression: a ``channel_balance=True`` HO w8a8
    artifact packs EVERY quantized matmul — ``fallback_ops()`` is empty,
    the serve-CLI fallback warning is None, the balance vectors ride the
    packs — and the engine serves it through the fused int8 kernels with
    the adaLN norm-modulate/gate-residual fusions live, tracing ONCE.
    The kernel samples agree with the same artifact's fake-quant oracle
    (which runs the identical chains UNFUSED in fp via the ctx helpers),
    so this is also the engine-level fused == unfused contract. Edge
    projections (x_proj / final) must be packed too."""
    import functools
    from repro.diffusion import DiffusionCfg, make_schedule
    from repro.kernels import ops as kops
    from repro.launch.serve import fake_quant_fallback_warning
    from repro.models import dit_apply
    from repro.quant import QuantRecipe, quantize
    from repro.serving import GenRequest, ServeEngine

    cfg, p = tiny_dit
    dif = DiffusionCfg(T=40, tgq_groups=4)
    sched = make_schedule(dif)
    art = quantize(p, cfg, dif, QuantRecipe(
        bits="w8a8", method="ho", rounds=1, n_alpha=4, n_per_group=2,
        calib_batch=2, channel_balance=True))
    assert art.has_kernel_packs
    assert art.fallback_ops() == [], \
        "channel-balanced ops must pack (prescale folds into the kernel)"
    assert fake_quant_fallback_warning(art) is None
    balanced = [n for n, qp in art.qparams.items() if "x_prescale" in qp]
    assert balanced, "channel_balance=True produced no balance vectors"
    for n in balanced:
        pack = art.qparams[n].get("int8") or art.qparams[n].get("int8_mrq")
        assert pack is not None and "x_prescale" in pack, n
    for n in ("x_proj", "final"):
        assert any(k in art.qparams.get(n, {})
                   for k in ("int8", "int8_mrq")), \
            f"edge projection {n} must serve quantized"

    calls = {"n": 0}
    for fname in ("int8_matmul_fq", "int8_matmul_mrq_fq"):
        orig = getattr(kops, fname)
        monkeypatch.setattr(kops, fname, functools.partial(
            lambda orig, *a, **kw: (calls.__setitem__("n", calls["n"] + 1),
                                    orig(*a, **kw))[1], orig))
    traces = []
    orig_apply = dit_apply

    def traced_apply(*a, **kw):
        traces.append(1)
        return orig_apply(*a, **kw)

    import repro.serving.engine as eng_mod
    monkeypatch.setattr(eng_mod, "dit_apply", traced_apply)

    reqs = [GenRequest(request_id=i, label=i % cfg.n_classes, steps=4,
                       cfg_scale=1.5, seed=60 + i) for i in range(2)]
    eng = ServeEngine(p, cfg, dif, sched, ctx=art.context(), microbatch=2,
                      step_buckets=(4,))
    res = eng.serve(reqs)
    assert len(traces) == 1, \
        "fused prologues broke the compile-once contract"
    assert calls["n"] > 0, "int8 kernels never fired"
    kern = np.stack([res[i].sample for i in range(2)])
    assert np.isfinite(kern).all()

    eng_fake = ServeEngine(p, cfg, dif, sched, ctx=art.context(kernel=False),
                           microbatch=2, step_buckets=(4,))
    fake = np.stack([eng_fake.serve(reqs)[i].sample for i in range(2)])
    np.testing.assert_allclose(kern, fake, rtol=0, atol=1e-4)
