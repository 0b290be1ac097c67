"""Kernel micro-benchmarks: correctness-at-scale sweeps plus the analytic
TPU benefit model for each Pallas kernel (wall-clock on CPU interpret mode
is meaningless; the TPU win is structural and computed from traffic).
Every traffic ratio here is a ROOFLINE MODEL, not a chip measurement.

  int8_matmul_fq     : fused-quantize prologue removes the standalone
                       quantize pass (fp32 read + int8 write of the full
                       activation through HBM) and the dequant round trip.
  int8_matmul_mrq_fq : single W traversal for the MRQ twin-region linear
                       (the old deployment paid TWO full int8 matmuls:
                       2x weight bytes, two (M,N) f32 intermediates + add).
  softmax_mrq        : probs tile stays in VMEM; saves read+write of the
                       (rows, cols) f32 probs per attention.
  act_mrq            : saves read+write of the (tokens, d_ff) hidden tensor.
  int8_bmm_qk /      : the composed int8 attention path. The headline
  softmax_mrq_codes /  saving is the PROBS tensor: the fp path writes +
  int8_bmm_pv          reads the (S,S) f32 probabilities through HBM
                       every attention; the fused path moves int8 CODES
                       instead — 4x less probs traffic (1B write + 1B
                       read vs 4B + 4B).
  flash_attn_mrq     : the flash-style fused kernel subsumes all three —
                       scores, softmax state and prob codes stay in
                       VMEM, so the ENTIRE (S,S) HBM round-trip (f32
                       scores write+read + int8 codes write+read, 10B
                       per score element) is eliminated: >=3x whole-
                       attention traffic cut vs composed at DiT-XL/2
                       shapes.

  int4_matmul_fq /   : nibble-packed weights (two 4-bit codes per byte,
  int4_matmul_mrq_fq   per-K-group scales) HALVE the weight stream vs
                       int8 — ~1.88x weight-traffic cut at DiT linear
                       shapes after charging the per-group metadata
                       (asserted >= 1.8x under ``--int4``).

  vector-tgroup      : the ``*_vec`` kernel variants take a per-row
  (``--vector-tgq``)   group VECTOR instead of one prefetched scalar, so
                       a batch whose slots sit at DIFFERENT diffusion
                       timesteps shares one launch — the weight stream
                       is paid once per dispatch, independent of the
                       active-slot count (asserted), where the scalar-
                       prefetch alternative re-streams the weights per
                       slot.

  prologue/epilogue   : the adaLN fp islands around the linears fold
  fusions               into the kernels — norm-modulate (layernorm +
  (``--residue``)       shift/scale) runs in the quantize PROLOGUE, the
                        gate+residual add in the dequant EPILOGUE, the
                        channel-balance prescale divide in the quantize
                        step — so the normalized fp activation and the
                        pre-gate matmul output never round-trip HBM.
                        ``--residue`` audits the whole DiT block: every
                        adaLN/residual fp byte is either fused (operand
                        streams charged) or named as a remaining
                        island; asserts ZERO uncharged adaLN/residual
                        bytes and >= 1.15x modeled block traffic vs the
                        pre-fusion baseline.

The traffic functions are importable (tests assert the structural-saving
floors, e.g. >=1.5x for the MRQ linear, >=2x probs traffic for fused
attention, >=3x whole-attention for flash at S>=256, >=1.8x weight
bytes for packed int4, >=1.15x block traffic for the adaLN fusions).
``--attn`` prints only the attention rows (``make bench-attn``);
``--flash`` only the flash rows (``make bench-flash``); ``--int4`` only
the packed-int4 rows (``make bench-int4``); ``--vector-tgq`` only the
vector-tgroup rows (``make bench-vector-tgq``); ``--residue`` only the
fusion-residue audit (``make bench-residue``).
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.kernels import (act_mrq, flash_attn_mrq, int8_bmm_pv, int8_bmm_qk,
                           int8_matmul, int8_matmul_fq, int8_matmul_mrq_fq,
                           softmax_mrq, softmax_mrq_codes, ref)


# ---------------------------------------------------------------------------
# analytic HBM-traffic models (bytes)
# ---------------------------------------------------------------------------
def traffic_int8_linear(M: int, K: int, N: int) -> dict:
    """W8A8 linear with a per-tensor/TGQ-uniform input.

    unfused — the pre-fusion serving chain:
      quantize pass:  read fp32 x (4B/elt) + write int8 codes (1B/elt),
      int8 matmul:    read codes (1B) + read int8 W (1B), write s32 (4B),
      dequant pass:   read s32 (4B) + write fp32 y (4B).
    fused — int8_matmul_fq: read fp32 x once, read W once, write fp32 y
      once; codes and s32 accumulator never leave VMEM.
    """
    quant_pass = M * K * 4 + M * K * 1
    matmul = M * K * 1 + K * N * 1 + M * N * 4
    dequant = M * N * 4 + M * N * 4
    return {"unfused": quant_pass + matmul + dequant,
            "fused": M * K * 4 + K * N * 1 + M * N * 4}


def traffic_mrq_linear(M: int, K: int, N: int) -> dict:
    """MRQ-signed-input linear (post-GELU fc2).

    unfused — the two-matmul twin-region decomposition:
      region split:   read fp32 x (4B) + write qn AND qp codes (2x1B),
      two matmuls:    read qn + qp (2x1B), read int8 W TWICE (2x1B),
                      write two fp32 (M,N) intermediates (2x4B),
      combine:        read both intermediates + write fp32 y (3x4B).
    fused — int8_matmul_mrq_fq: read fp32 x once, read W ONCE (sign mask
      + dual accumulators in VMEM), write fp32 y once.
    """
    split = M * K * 4 + 2 * M * K * 1
    two_matmuls = 2 * M * K * 1 + 2 * K * N * 1 + 2 * M * N * 4
    combine = 3 * M * N * 4
    return {"unfused": split + two_matmuls + combine,
            "fused": M * K * 4 + K * N * 1 + M * N * 4}


def traffic_int4_linear(M: int, K: int, N: int, group_k: int = 256) -> dict:
    """W4A4 linear (``int4_matmul_fq``) vs the W8A8 fused path: the
    weight stream HALVES (two codes per byte) at the price of per-K-group
    metadata — one f32 scale + one s32 zero-correction per
    (K-group, out-channel), i.e. ``ceil(K/group_k) * N * 8`` bytes.  At
    DiT linear shapes (K >= 2048, group_k = 256) the metadata is ~6% of
    the nibble payload, so the weight-traffic cut lands at ~1.88x
    (asserted >= 1.8x in CI via ``--int4``).  Activation read and output
    write are identical between the two paths (fp32 in / fp32 out; codes
    never leave VMEM), so ``fused_int8``/``fused_int4`` differ only by
    the weight stream."""
    nk = -(-K // group_k)
    kp = nk * group_k                      # pack-time padding (code-0 rows)
    int8_weight = K * N * 1
    int4_weight = (kp * N) // 2 + nk * N * (4 + 4)
    return {"int8_weight": int8_weight, "int4_weight": int4_weight,
            "fused_int8": M * K * 4 + int8_weight + M * N * 4,
            "fused_int4": M * K * 4 + int4_weight + M * N * 4}


def traffic_int4_mrq_linear(M: int, K: int, N: int,
                            group_k: int = 256) -> dict:
    """W4A4 MRQ linear (``int4_matmul_mrq_fq``): same nibble payload as
    the uniform path; the metadata is the twin-region scale pair
    (scale_neg + scale_pos, 2 x f32 per (K-group, out-channel)) and no
    zero-correction (both regions are symmetric) — the same 8 bytes per
    (group, channel), so the same ~1.88x weight cut."""
    nk = -(-K // group_k)
    kp = nk * group_k
    int8_weight = K * N * 1
    int4_weight = (kp * N) // 2 + nk * N * (4 + 4)
    return {"int8_weight": int8_weight, "int4_weight": int4_weight,
            "fused_int8": M * K * 4 + int8_weight + M * N * 4,
            "fused_int4": M * K * 4 + int4_weight + M * N * 4}


def traffic_norm_mod_fusion(M: int, B: int, K: int, N: int) -> dict:
    """A linear site with the adaLN norm-modulate chain fused into its
    quantize prologue (qkv / fc1 / the final projection).

    unfused — the PR-8 baseline: the fused linear
      (``traffic_int8_linear['fused']``) PLUS the elementwise chain as
      an XLA pass: read fp32 x (4B/elt) + write the normalized+modulated
      fp32 x (4B) that the linear then reads — 8 bytes/elt of x.
    fused — the chain's write/read disappears; what remains is charged
      HONESTLY: one extra fp32 read of x for the row stats (the mean/var
      reduction runs outside the kernel), the (M, 1) mu/rsig stream
      (write + read, 16 bytes/row) and the per-batch (B, K) shift/scale
      rows (8 bytes/elt) the prologue gathers in VMEM.
    """
    base = M * K * 4 + K * N * 1 + M * N * 4
    chain = 8 * M * K
    charged = 4 * M * K + 16 * M + 8 * B * K
    return {"unfused": base + chain, "fused": base + charged,
            "chain_bytes": chain, "charged_bytes": charged}


def traffic_gate_residual_fusion(M: int, B: int, K: int, N: int) -> dict:
    """A linear site with the adaLN gate + residual add fused into its
    dequant epilogue (proj / fc2).

    unfused — PR-8 baseline: the fused linear plus the
      ``x + g * y`` chain as an XLA pass over the (M, N) output: read y
      (4B/elt) + read the residual (4B) + write the new x (4B) — 12
      bytes/elt.
    fused — the epilogue consumes y in VMEM and writes the gated sum as
      the kernel's single output; charged: the streamed residual tile
      (4B/elt) and the per-batch (B, N) gate rows (4B/elt).
    """
    base = M * K * 4 + K * N * 1 + M * N * 4
    chain = 12 * M * N
    charged = 4 * M * N + 4 * B * N
    return {"unfused": base + chain, "fused": base + charged,
            "chain_bytes": chain, "charged_bytes": charged}


def fused_block_traffic(M: int = 1024, B: int = 4, d: int = 1152,
                        f: int = 4608) -> dict:
    """Whole-DiT-block linear traffic, PR-8 baseline vs fused prologues/
    epilogues, at the XL/2 serving shape (B CFG-paired slots x M/B
    tokens). Returns per-site entries plus aggregates and the residue:
    adaLN/residual chain bytes served by NO fusion (must be zero — every
    chain in the block rides a seam). The post-GELU island is reported
    separately (``gelu_island_bytes``): it is charged on neither path
    and excluded from the residue contract (it feeds the MRQ quantizer,
    not an adaLN chain)."""
    sites = [
        ("xl2_ada", B, d, 6 * d, None),
        ("xl2_qkv", M, d, 3 * d, "nm"),
        ("xl2_proj", M, d, d, "gr"),
        ("xl2_fc1", M, d, f, "nm"),
        ("xl2_fc2", M, f, d, "gr"),
    ]
    per_site, unfused, fused, residue = [], 0, 0, 0
    for name, m, k, n, fusion in sites:
        if fusion == "nm":
            t = traffic_norm_mod_fusion(m, B, k, n)
        elif fusion == "gr":
            t = traffic_gate_residual_fusion(m, B, k, n)
        else:
            base = m * k * 4 + k * n * 1 + m * n * 4
            t = {"unfused": base, "fused": base, "chain_bytes": 0,
                 "charged_bytes": 0}
        # a chain byte is residue iff the site has a chain but no fusion
        # serving it — today every chain is fused, so this stays 0
        t["residue_bytes"] = 0 if fusion is not None else t["chain_bytes"]
        per_site.append((name, fusion, t))
        unfused += t["unfused"]
        fused += t["fused"]
        residue += t["residue_bytes"]
    return {"sites": per_site, "unfused": unfused, "fused": fused,
            "residue_adaln_residual": residue,
            "gelu_island_bytes": 8 * M * f}


def traffic_vector_tgq_linear(M_per_slot: int, K: int, N: int,
                              n_slots: int, bits: int = 8,
                              group_k: int = 256) -> dict:
    """Weight traffic for ONE mixed-timestep dispatch over ``n_slots``
    slots of ``M_per_slot`` activation rows each.

    per_slot — the scalar-prefetch alternative: slots sitting at
      different timestep groups cannot share a launch (the TGQ group
      index is a single prefetched scalar baked into the param index
      maps), so each slot dispatches separately and re-streams the
      weight matrix — ``n_slots`` weight reads per chunk step.
    vector — the ``*_vec`` kernel: the (B,) per-row group vector rides
      as a tiny int32 operand and every row gathers its activation
      params in VMEM (one-hot dot against the (G, ...) stacks), so ALL
      slots share ONE launch and the weights stream exactly once per
      dispatch, independent of the slot count.

    Activation in/out bytes are identical on both paths; per-group
    metadata vectors are not charged, following this file's convention
    (they are noise next to the weight stream).
    """
    if bits == 4:
        w = traffic_int4_linear(M_per_slot, K, N, group_k)["int4_weight"]
    else:
        w = K * N * 1
    act = n_slots * M_per_slot * (K * 4 + N * 4)
    return {"weight_bytes_per_dispatch": w,
            "per_slot": n_slots * w + act,
            "vector": w + act}


def traffic_attention_flash_packed(BH: int, S: int, D: int,
                                   bm: int | None = None) -> dict:
    """Flash attention kv stream: unpacked fp32 vs 4-bit nibble-packed.

    unpacked — k/v are fetched in fp32 once per q-tile:
      ``BH*S*D * (8 + 8*n_qtiles)`` (q read + out write, then 2x4B per
      kv element per q-tile).
    packed — ONE fp32 read of k/v to quantize + nibble-pack them
      (2x4B), one packed write (2x0.5B), then each q-tile streams the
      packed codes (2x0.5B each):
      ``BH*S*D * (8 + 8 + 1 + n_qtiles)``.

    The trade is honest: packing costs an extra 9B/elt up front, so it
    WINS only when the kv stream is re-fetched — n_qtiles >= 2 (e.g.
    S = 512 with the default bm = 256).  At n_qtiles = 1 the unpacked
    path is strictly cheaper and ``ops.flash_attention`` still uses the
    packed path for 4-bit packs only because the code path must match
    the pack bits, not for traffic."""
    from repro.kernels.flash_attn_mrq import DEFAULT_BM
    bm = DEFAULT_BM if bm is None else bm
    n_qtiles = -(-S // bm)
    return {"unpacked": BH * S * D * (8 + 8 * n_qtiles),
            "packed": BH * S * D * (8 + 8 + 1 + n_qtiles),
            "n_qtiles": n_qtiles}


def traffic_attention_probs(BH: int, S: int, D: int) -> dict:
    """Attention softmax->P·V tail for BH (batch*heads) matrices of
    (S, S) scores against (S, D) values.

    unfused — fp probs round-trip (the pre-int8-attention serving path):
      softmax(+qdq): read f32 scores (4B/elt) + WRITE f32 probs (4B),
      P·V:           READ f32 probs (4B) + read f32 v (4B),
                     write f32 out (4B).
    fused — softmax_mrq_codes + int8_bmm_pv: the probs tensor moves as
      int8 codes (1B write + 1B read); v is read once in fp and
      quantized in VMEM; out written once.

    probs_unfused/probs_fused isolate the probs-tensor bytes — the
    quadratic term the codes path shrinks 4x.
    """
    probs_unfused = BH * S * S * (4 + 4)          # f32 write + f32 read
    probs_fused = BH * S * S * (1 + 1)            # int8 codes write + read
    rest = BH * S * S * 4 + BH * S * D * 4 + BH * S * D * 4
    return {
        "probs_unfused": probs_unfused,
        "probs_fused": probs_fused,
        "unfused": probs_unfused + rest,
        "fused": probs_fused + rest,
    }


def traffic_attention_qk(BH: int, S: int, D: int) -> dict:
    """QK^T: the int8 path reads q/k once in fp (quantized in VMEM) and
    writes f32 scores once; the unfused int8 chain would pay a separate
    quantize pass (f32 read + int8 write) per operand."""
    quant_pass = 2 * BH * S * D * (4 + 1)
    matmul = 2 * BH * S * D * 1 + BH * S * S * 4
    return {"unfused": quant_pass + matmul,
            "fused": 2 * BH * S * D * 4 + BH * S * S * 4}


def traffic_attention_flash(BH: int, S: int, D: int,
                            bm: int | None = None) -> dict:
    """Whole-attention HBM bytes: composed three-kernel int8 path vs the
    flash-style fused kernel (``kernels.flash_attn_mrq``).

    composed — ``int8_bmm_qk`` -> ``softmax_mrq_codes`` -> ``int8_bmm_pv``
      still round-trips the quadratic (S, S) tensors through HBM:
      f32 scores write (4B) + read (4B), int8 prob-code write (1B) +
      read (1B) — 10 bytes per score element — on top of the f32 q/k/v
      reads and the output write.
    flash — q is read once and the output written once in f32; the K/V
      stream is charged HONESTLY at one fetch per q-tile
      (``ceil(S/bm)`` reads each — the kernel's kv BlockSpec index maps
      revisit every kv tile for every q-tile, so Pallas cannot elide the
      re-fetch). With the kernel's default ``bm = 256`` that is exactly
      ONE fetch at DiT-serving lengths. Scores, running softmax state
      and prob codes never leave VMEM: the (S, S) round-trip is
      ELIMINATED — ``scores_codes_eliminated`` counts those bytes.

    At DiT-XL/2 attention shape (S = 256, hd = 72) the cut is >= 3x
    (asserted in ``tests/test_flash_attn.py``).
    """
    from repro.kernels.flash_attn_mrq import DEFAULT_BM
    bm = DEFAULT_BM if bm is None else bm
    n_qtiles = -(-S // bm)
    flash = BH * S * D * 4 * (2 + 2 * n_qtiles)  # q+out once, k/v per q-tile
    scores_codes = BH * S * S * (4 + 4 + 1 + 1)
    composed = 4 * BH * S * D * 4 + scores_codes
    return {"composed": composed,
            "flash": flash,
            "scores_codes_eliminated": scores_codes}


def _attention_rows(rows, flash_only: bool = False) -> None:
    key = jax.random.PRNGKey(7)
    # DiT-XL/2 attention shape: 256 tokens, 16 heads, head dim 72 — and a
    # ragged case to exercise padding (and, for flash, the NEG_INF lane
    # masking ahead of the online max).
    for (BH, S, D) in [(16, 256, 72), (3, 130, 17)]:
        k1, k2, k3 = jax.random.split(key, 3)
        q = jax.random.normal(k1, (BH, S, D)) * 2
        k = jax.random.normal(k2, (BH, S, D)) * 2
        v = jax.random.normal(k3, (BH, S, D))
        s_q = jnp.full((1, 1), 0.03, jnp.float32)
        s_k = jnp.full((1, 1), 0.04, jnp.float32)
        scale = s_q * s_k * (D ** -0.5)
        scores = int8_bmm_qk(q, k, s_q, s_k, scale, interpret=True)
        s1 = jnp.full((1, 1), 2e-3, jnp.float32)
        codes = softmax_mrq_codes(scores, s1, interpret=True)
        s_v = jnp.full((1, 1), 0.05, jnp.float32)
        out = int8_bmm_pv(codes, v, s_v, s1 * s_v, (1.0 / 128) * s_v,
                          interpret=True)
        t = traffic_attention_qk(BH, S, D)
        tp = traffic_attention_probs(BH, S, D)
        if not flash_only:
            want = ref.int8_bmm_qk_ref(q, k, s_q, s_k, scale)
            err = float(jnp.max(jnp.abs(scores - want)))
            rows.append(("int8_bmm_qk", f"{BH}x{S}x{D}", f"{err:.1e}",
                         t["unfused"], t["fused"],
                         round(t["unfused"] / t["fused"], 2)))

            cerr = int(jnp.max(jnp.abs(
                codes.astype(jnp.int32)
                - ref.softmax_mrq_codes_ref(scores, s1).astype(jnp.int32))))
            rows.append(("softmax_mrq_codes", f"{BH}x{S}x{S}", f"{cerr:d}",
                         tp["probs_unfused"], tp["probs_fused"],
                         round(tp["probs_unfused"] / tp["probs_fused"], 2)))

            pwant = ref.int8_bmm_pv_ref(codes, v, s_v, s1 * s_v,
                                        (1.0 / 128) * s_v)
            perr = float(jnp.max(jnp.abs(out - pwant)))
            rows.append(("int8_bmm_pv", f"{BH}x{S}x{D}", f"{perr:.1e}",
                         tp["unfused"], tp["fused"],
                         round(tp["unfused"] / tp["fused"], 2)))

        # flash-style fused kernel: whole block in one launch, (S,S)
        # scores/codes never in HBM. max_err is vs the COMPOSED output
        # above (the exactness oracle; documented tolerance contract in
        # kernels/ref.py::flash_vs_composed_atol), traffic vs composed.
        fout = flash_attn_mrq(
            q, k, v, s_q, s_k, scale, s1, s_v, s1 * s_v,
            (1.0 / 128) * s_v, interpret=True)
        ferr = float(jnp.max(jnp.abs(fout - out)))
        tf = traffic_attention_flash(BH, S, D)
        rows.append(("flash_attn_mrq", f"{BH}x{S}x{D}", f"{ferr:.1e}",
                     tf["composed"], tf["flash"],
                     round(tf["composed"] / tf["flash"], 2)))


def _int4_rows(rows) -> None:
    """Packed-int4 linear family + packed-kv flash: correctness vs the
    ref.py oracles through the REAL pack builders, and the weight-stream
    traffic cut (asserted >= 1.8x at DiT linear shapes — the CI gate for
    ``make bench-int4``)."""
    from repro.core.quantizers import (ChannelQ, MRQSignedQ, TGQ, UniformQ,
                                       channel_scale_from_absmax,
                                       weight_absmax)
    from repro.kernels import ops

    G = 3
    for (M, K, N) in [(256, 2048, 2048), (256, 4608, 1152)]:
        kx, kw = jax.random.split(jax.random.PRNGKey(11 + K), 2)
        w = jax.random.normal(kw, (K, N)) * 0.05

        x = jax.random.normal(kx, (M, K)) * 2.0
        qp = {"x": TGQ(UniformQ(scale=jnp.linspace(0.01, 0.05, G),
                                zero=jnp.round(jnp.linspace(5.6, 9.4, G)),
                                bits=4)),
              "w": ChannelQ(channel_scale_from_absmax(weight_absmax(w), 4),
                            4)}
        pack = ops.pack_int4_linear(qp, np.asarray(w))
        out = ops.int4_linear(x, pack, tgroup=1)
        want = ref.int4_matmul_fq_ref(
            x, pack["wp"], pack["sx"], pack["zx"], pack["scale"],
            pack["corr"], g=1, group_k=pack["group_k"])
        err = float(jnp.max(jnp.abs(out - want)))
        t = traffic_int4_linear(M, K, N, group_k=pack["group_k"])
        cut = t["int8_weight"] / t["int4_weight"]
        assert cut >= 1.8, (
            f"int4 weight-traffic cut {cut:.2f}x < 1.8x at {M}x{K}x{N}")
        rows.append(("int4_matmul_fq", f"{M}x{K}x{N}", f"{err:.1e}",
                     t["int8_weight"], t["int4_weight"], round(cut, 2)))

        xg = jax.nn.gelu(jax.random.normal(kx, (M, K)) * 1.5)
        qpm = {"x": TGQ(MRQSignedQ(s_neg=jnp.geomspace(1e-4, 2e-3, G),
                                   s_pos=jnp.geomspace(1e-3, 2e-2, G),
                                   bits=4)),
               "w": ChannelQ(channel_scale_from_absmax(weight_absmax(w), 4),
                             4)}
        packm = ops.pack_int4_mrq_linear(qpm, np.asarray(w))
        outm = ops.int4_linear_mrq(xg, packm, tgroup=1)
        wantm = ref.int4_matmul_mrq_fq_ref(
            xg, packm["wp"], packm["s_neg"], packm["s_pos"],
            packm["scale_neg"], packm["scale_pos"], g=1,
            group_k=packm["group_k"])
        errm = float(jnp.max(jnp.abs(outm - wantm)))
        tm = traffic_int4_mrq_linear(M, K, N, group_k=packm["group_k"])
        cutm = tm["int8_weight"] / tm["int4_weight"]
        assert cutm >= 1.8, (
            f"int4 MRQ weight-traffic cut {cutm:.2f}x < 1.8x at {M}x{K}x{N}")
        rows.append(("int4_matmul_mrq_fq", f"{M}x{K}x{N}", f"{errm:.1e}",
                     tm["int8_weight"], tm["int4_weight"], round(cutm, 2)))

    # packed-kv flash: packed vs unpacked 4-bit kv stream is BIT-identical
    # (same codes either way); traffic quoted at the multi-q-tile shape
    # where packing actually wins (S = 512 > bm = 256 -> n_qtiles = 2).
    BH, S, D, bn = 3, 130, 17, 64
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(23), 3)
    q = jax.random.normal(k1, (BH, S, D)) * 2
    k = jax.random.normal(k2, (BH, S, D)) * 2
    v = jax.random.normal(k3, (BH, S, D))
    s_q = jnp.full((1, 1), 0.03, jnp.float32)
    s_k = jnp.full((1, 1), 0.04, jnp.float32)
    scale = s_q * s_k * (D ** -0.5)
    s1 = jnp.full((1, 1), 2e-3, jnp.float32)
    s_v = jnp.full((1, 1), 0.05, jnp.float32)
    kwargs = dict(bits=4, bn=bn, interpret=True)
    f_packed = flash_attn_mrq(q, k, v, s_q, s_k, scale, s1, s_v, s1 * s_v,
                              (1.0 / 8) * s_v, packed_kv=True, **kwargs)
    f_plain = flash_attn_mrq(q, k, v, s_q, s_k, scale, s1, s_v, s1 * s_v,
                             (1.0 / 8) * s_v, packed_kv=False, **kwargs)
    ferr = float(jnp.max(jnp.abs(f_packed - f_plain)))
    tf = traffic_attention_flash_packed(16, 512, 72)
    assert tf["n_qtiles"] >= 2
    rows.append(("flash_attn_mrq[packed_kv]", "16x512x72", f"{ferr:.1e}",
                 tf["unpacked"], tf["packed"],
                 round(tf["unpacked"] / tf["packed"], 2)))


def _vector_tgq_rows(rows) -> None:
    """Vector-tgroup rows (``--vector-tgq``): correctness of the per-row
    gather kernels at a MIXED group vector (vs the per-row oracles,
    through the real pack builders) plus the dispatch traffic model for
    a mixed-timestep slot batch. ASSERTS the one-weight-read contract:
    modeled weight bytes per dispatch do not depend on the number of
    active slots."""
    from repro.core.quantizers import (ChannelQ, MRQSoftmaxQ, SymQ, TGQ,
                                       UniformQ, channel_scale_from_absmax,
                                       weight_absmax)
    from repro.kernels import ops
    from repro.kernels.flash_attn_mrq import flash_attn_mrq_vec

    G = 4
    M, K, N = 64, 256, 128
    kx, kw = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(kx, (M, K)) * 2.0
    w = jax.random.normal(kw, (K, N)) * 0.05
    gv = jnp.asarray(np.arange(M) % G, jnp.int32)
    for bits, name in ((8, "int8_matmul_fq_vec"), (4, "int4_matmul_fq_vec")):
        half = 2 ** (bits - 1)
        qp = {"x": TGQ(UniformQ(scale=jnp.linspace(0.01, 0.05, G),
                                zero=jnp.round(jnp.linspace(
                                    0.7 * half, 1.17 * half, G)),
                                bits=bits)),
              "w": ChannelQ(channel_scale_from_absmax(weight_absmax(w),
                                                      bits), bits)}
        if bits == 4:
            pack = ops.pack_int4_linear(qp, np.asarray(w))
            out = ops.int4_linear(x, pack, tgroup=gv)
            want = ref.int4_matmul_fq_vec_ref(
                x, pack["wp"], pack["sx"], pack["zx"], pack["scale"],
                pack["corr"], gv=gv, group_k=pack["group_k"])
        else:
            pack = ops.pack_int8_linear(qp, np.asarray(w))
            out = ops.int8_linear(x, pack, tgroup=gv)
            want = ref.int8_matmul_fq_vec_ref(
                x, pack["wq"], pack["sx"], pack["zx"], pack["scale"],
                pack["corr"], gv=gv)
        err = float(jnp.max(jnp.abs(out - want)))
        t = traffic_vector_tgq_linear(M, K, N, G, bits=bits)
        rows.append((name, f"{M}x{K}x{N}[mixed,G={G}]", f"{err:.1e}",
                     t["per_slot"], t["vector"],
                     round(t["per_slot"] / t["vector"], 2)))

    # flash with a per-batch-row group vector: a constant vector must be
    # BIT-identical to the scalar-prefetch kernel (asserted)
    B, S, D = 3, 16, 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(k1, (B, S, D)) * 2
    k = jax.random.normal(k2, (B, S, D)) * 2
    v = jax.random.normal(k3, (B, S, D))
    qk_pack = ops.pack_int8_qk(
        {"x": TGQ(SymQ(scale=jnp.linspace(0.01, 0.05, G))),
         "b": TGQ(SymQ(scale=jnp.linspace(0.02, 0.06, G)))})
    pv_pack = ops.pack_int8_pv(
        {"x": TGQ(MRQSoftmaxQ(s1=jnp.geomspace(3e-4, 6e-3, G))),
         "b": TGQ(SymQ(scale=jnp.linspace(0.01, 0.04, G)))})
    scale = D ** -0.5
    args = (q, k, v, qk_pack["s_q"], qk_pack["s_k"],
            qk_pack["scale"] * scale, pv_pack["s1"], pv_pack["s_v"],
            pv_pack["scale1"], pv_pack["scale2"])
    got = flash_attn_mrq_vec(*args, g_qk=jnp.full((B,), 2, jnp.int32),
                             g_pv=jnp.full((B,), 2, jnp.int32),
                             interpret=True)
    want = flash_attn_mrq(*args, g_qk=2, g_pv=2, interpret=True)
    ferr = float(jnp.max(jnp.abs(got - want)))
    assert ferr == 0.0, (
        f"constant group vector diverged from scalar prefetch: {ferr}")
    rows.append(("flash_attn_mrq_vec", f"{B}x{S}x{D}[const==scalar]",
                 f"{ferr:.1e}", "-", "-", "-"))

    # one-weight-read contract at the DiT-XL/2 fc1 shape: one chunk-step
    # dispatch over n active mixed-timestep slots (CFG-paired, 2*256
    # token rows per slot) streams the weights ONCE
    T, d, f = 256, 1152, 4608
    base = None
    for n_slots in (1, 2, 4, 8):
        t = traffic_vector_tgq_linear(2 * T, d, f, n_slots)
        if base is None:
            base = t["weight_bytes_per_dispatch"]
        assert t["weight_bytes_per_dispatch"] == base, (
            "vector-tgq dispatch weight bytes must not scale with the "
            f"active-slot count ({t['weight_bytes_per_dispatch']} != "
            f"{base} at {n_slots} slots)")
        rows.append(("vector_tgq_dispatch", f"xl2_fc1[{n_slots}_slots]",
                     "-", t["per_slot"], t["vector"],
                     round(t["per_slot"] / t["vector"], 2)))


def _residue_rows(rows) -> None:
    """Fusion-residue audit (``--residue``): correctness of the fully
    fused kernel (norm-modulate prologue + gate+residual epilogue in one
    launch, vs the jitted ``*_fused_ref`` oracle), then the XL/2 block
    traffic table. ASSERTS zero uncharged adaLN/residual fp bytes and a
    >= 1.15x modeled block-aggregate traffic win over the PR-8 baseline
    (fused linears, chains still in XLA) — the CI gate for
    ``make bench-residue``."""
    # correctness probe: all three fusions live in one int8 launch
    M, K, N, B, G = 64, 96, 80, 4, 3
    kx, kw, kf = jax.random.split(jax.random.PRNGKey(41), 3)
    x = jax.random.normal(kx, (M, K)) * 2
    wq = jax.random.randint(kw, (K, N), -128, 128, jnp.int32).astype(
        jnp.int8)
    sx = (jax.random.uniform(kf, (G, 1)) * 0.04 + 0.01).astype(jnp.float32)
    zx = jnp.round(jax.random.uniform(kx, (G, 1)) * 200.0)
    scale = (jax.random.uniform(kw, (G, N)) * 1e-3 + 1e-5).astype(
        jnp.float32)
    corr = (jnp.round(zx).astype(jnp.int32) - 128) * jnp.sum(
        wq.astype(jnp.int32), axis=0)[None, :]
    bias = jax.random.normal(kf, (N,))
    ks = jax.random.split(kf, 5)
    ps = jnp.exp(jax.random.uniform(ks[0], (K,), minval=-1.0, maxval=1.0))
    nm = (jax.random.normal(ks[1], (B, K)) * 0.5,
          jax.random.normal(ks[2], (B, K)) * 0.2)
    gr = (jax.random.normal(ks[3], (B, N)) * 0.8,
          jax.random.normal(ks[4], (M, N)))
    bv = jnp.repeat(jnp.arange(B, dtype=jnp.int32), M // B)
    out = int8_matmul_fq(x, wq, sx, zx, scale, corr, bias, g=1, ps=ps,
                         nm=nm, gr=gr, rows_per_batch=M // B, interpret=True)
    want = jax.jit(lambda *a: ref.int8_matmul_fq_fused_ref(
        *a, bias, g=1, ps=ps, nm=nm, gr=gr, bv=bv))(x, wq, sx, zx, scale,
                                                    corr)
    err = float(jnp.max(jnp.abs(out - want)))
    rows.append(("int8_matmul_fq[nm+ps+gr]", f"{M}x{K}x{N}", f"{err:.1e}",
                 "-", "-", "-"))

    # XL/2 block traffic: PR-8 baseline vs fused prologues/epilogues
    t = fused_block_traffic()
    for name, fusion, ts in t["sites"]:
        rows.append((f"linear[{fusion or 'plain'}]", name,
                     f"residue={ts['residue_bytes']}", ts["unfused"],
                     ts["fused"],
                     round(ts["unfused"] / ts["fused"], 3)))
    assert t["residue_adaln_residual"] == 0, (
        "uncharged adaLN/residual fp bytes remain: "
        f"{t['residue_adaln_residual']}")
    win = t["unfused"] / t["fused"]
    assert win >= 1.15, (
        f"fused block traffic win {win:.3f}x < 1.15x vs the PR-8 baseline")
    rows.append(("dit_block_aggregate", "xl2[4x256tok]", "residue=0",
                 t["unfused"], t["fused"], round(win, 3)))
    # the one elementwise fp island left between the linears — charged on
    # neither path, excluded from the residue contract
    rows.append(("post_gelu_island", "xl2_fc1->fc2",
                 f"bytes={t['gelu_island_bytes']}", "-", "-", "-"))


def main(attn_only: bool = False, flash_only: bool = False,
         int4_only: bool = False, vector_tgq_only: bool = False,
         residue_only: bool = False) -> None:
    rows = [("kernel", "case", "max_err", "hbm_bytes_unfused",
             "hbm_bytes_fused", "traffic_saving")]
    if residue_only:
        _residue_rows(rows)
        for r in rows:
            print(",".join(str(x) for x in r), flush=True)
        C.emit("kernel_micro_residue", rows)
        return
    if vector_tgq_only:
        _vector_tgq_rows(rows)
        C.emit("kernel_micro_vector_tgq", rows)
        return
    if int4_only:
        _int4_rows(rows)
        C.emit("kernel_micro_int4", rows)
        return
    if flash_only:
        _attention_rows(rows, flash_only=True)
        for r in rows:
            print(",".join(str(x) for x in r), flush=True)
        C.emit("kernel_micro_flash", rows)
        return
    if attn_only:
        _attention_rows(rows)
        for r in rows:
            print(",".join(str(x) for x in r), flush=True)
        C.emit("kernel_micro_attn", rows)
        return

    key = jax.random.PRNGKey(0)
    # --- fused-quantize int8 matmul: M,K,N sweep ------------------------------
    for (M, K, N) in [(256, 2048, 2048), (512, 4096, 1024)]:
        k1, k2 = jax.random.split(key)
        x = jax.random.normal(k1, (M, K)) * 2
        wq = jax.random.randint(k2, (K, N), -128, 128,
                                jnp.int32).astype(jnp.int8)
        sx = jnp.full((1, 1), 0.02, jnp.float32)
        zx = jnp.full((1, 1), 110.0, jnp.float32)
        sw = jax.random.uniform(k1, (N,)) * 1e-3
        corr = (jnp.round(zx).astype(jnp.int32) - 128) * jnp.sum(
            wq.astype(jnp.int32), axis=0)[None, :]
        scale = sx * sw[None, :]
        out = int8_matmul_fq(x, wq, sx, zx, scale, corr, interpret=True)
        want = ref.int8_matmul_fq_ref(x, wq, sx, zx, scale, corr)
        err = float(jnp.max(jnp.abs(out - want)))
        t = traffic_int8_linear(M, K, N)
        rows.append(("int8_matmul_fq", f"{M}x{K}x{N}", f"{err:.1e}",
                     t["unfused"], t["fused"],
                     round(t["unfused"] / t["fused"], 2)))

    # --- single-pass MRQ matmul (fc2-shaped cases) ----------------------------
    for (M, K, N) in [(256, 4608, 1152), (512, 4096, 1024)]:
        k1, k2 = jax.random.split(key)
        x = jax.nn.gelu(jax.random.normal(k1, (M, K)) * 1.5)
        wq = jax.random.randint(k2, (K, N), -128, 128,
                                jnp.int32).astype(jnp.int8)
        s_neg = jnp.full((1, 1), 1.5e-3, jnp.float32)
        s_pos = jnp.full((1, 1), 2.5e-2, jnp.float32)
        sw = jax.random.uniform(k1, (N,)) * 1e-3
        out = int8_matmul_mrq_fq(x, wq, s_neg, s_pos, s_neg * sw[None, :],
                                 s_pos * sw[None, :], interpret=True)
        want = ref.int8_matmul_mrq_fq_ref(x, wq, s_neg, s_pos,
                                          s_neg * sw[None, :],
                                          s_pos * sw[None, :])
        err = float(jnp.max(jnp.abs(out - want)))
        t = traffic_mrq_linear(M, K, N)
        rows.append(("int8_matmul_mrq_fq", f"{M}x{K}x{N}", f"{err:.1e}",
                     t["unfused"], t["fused"],
                     round(t["unfused"] / t["fused"], 2)))

    # --- pre-quantized-codes matmul (einsum-style operands keep it) -----------
    for (M, K, N) in [(256, 2048, 2048)]:
        k1, k2 = jax.random.split(key)
        xq = jax.random.randint(k1, (M, K), -128, 128,
                                jnp.int32).astype(jnp.int8)
        wq = jax.random.randint(k2, (K, N), -128, 128,
                                jnp.int32).astype(jnp.int8)
        scale = jax.random.uniform(k1, (N,)) * 1e-3
        corr = jnp.sum(wq.astype(jnp.int32), axis=0) * 3
        out = int8_matmul(xq, wq, scale, corr, interpret=True)
        want = ref.int8_matmul_ref(xq, wq, scale, corr)
        err = float(jnp.max(jnp.abs(out - want)))
        # epilogue fusion only: saves the s32 round trip of the output
        unfused = M * K + K * N + M * N * (4 + 4 + 4)
        fused = M * K + K * N + M * N * 4
        rows.append(("int8_matmul", f"{M}x{K}x{N}", f"{err:.1e}", unfused,
                     fused, round(unfused / fused, 2)))

    # --- softmax_mrq ------------------------------------------------------------
    for (R, Cc) in [(1024, 1024), (4096, 512)]:
        s = jax.random.normal(key, (R, Cc)) * 4
        out = softmax_mrq(s, 0.3 / 128, bits=8, interpret=True)
        want = ref.softmax_mrq_ref(s, 0.3 / 128, 8)
        err = float(jnp.max(jnp.abs(out - want)))
        unfused = R * Cc * (4 + 4 + 4 + 4)   # probs write+read, q write+read
        fused = R * Cc * (4 + 4)             # scores in, quantized out
        rows.append(("softmax_mrq", f"{R}x{Cc}", f"{err:.1e}", unfused,
                     fused, round(unfused / fused, 2)))

    # --- act_mrq ----------------------------------------------------------------
    for (T, F) in [(2048, 4096)]:
        x = jax.random.normal(key, (T, F)) * 2
        out = act_mrq(x, 0.004, 0.03, bits=8, kind="gelu", interpret=True)
        want = ref.act_mrq_ref(x, 0.004, 0.03, 8, "gelu")
        err = float(jnp.max(jnp.abs(out - want)))
        unfused = T * F * (4 + 4 + 4 + 4)
        fused = T * F * (4 + 4)
        rows.append(("act_mrq", f"{T}x{F}", f"{err:.1e}", unfused, fused,
                     round(unfused / fused, 2)))

    # --- int8 attention (QK^T / softmax codes / P·V) --------------------------
    _attention_rows(rows)

    for r in rows:
        print(",".join(str(x) for x in r), flush=True)
    C.emit("kernel_micro", rows)


if __name__ == "__main__":
    main(attn_only="--attn" in sys.argv[1:],
         flash_only="--flash" in sys.argv[1:],
         int4_only="--int4" in sys.argv[1:],
         vector_tgq_only="--vector-tgq" in sys.argv[1:],
         residue_only="--residue" in sys.argv[1:])
