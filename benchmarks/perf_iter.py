import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# Performance hillclimbing harness (EXPERIMENTS.md section "Perf").
#
# Three cells chosen from the 34-cell baseline:
#   qwen2.5-14b x train_4k   — worst roofline fraction (0.01)
#   kimi-k2-1t-a32b x train_4k — most collective-bound in absolute terms
#   dit-xl-2 x sample_128    — the paper's own serving workload
#
# Each named variant is hypothesis -> change -> re-lower -> re-analyse;
# results append to experiments/perf.json.
#
# Run: PYTHONPATH=src python -m benchmarks.perf_iter --exp <name>

import argparse
import json
import time

import jax
import numpy as np


def measure_variant(arch, shape_id, overrides=None, mesh_shape=None,
                    quantized_weights=False, replicate_params=False):
    """Like benchmarks.roofline.measure but with config overrides and an
    optional custom layout of the same 256 chips."""
    from repro.launch.steps import build_cell
    from repro.launch.hlo_stats import collective_stats
    from benchmarks.roofline import analyse

    if mesh_shape is not None:
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(*mesh_shape)
        tp = mesh_shape[1]
    else:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=False)
        tp = 16
    if replicate_params:
        tp = 1

    rec = {}
    for L in (1, 2):
        over = {"n_layers": L, "scan_layers": False, "remat": False,
                "grad_accum": 1}
        if arch == "whisper-tiny":
            over["n_enc_layers"] = L
        if arch == "hymba-1.5b":
            over["global_layers"] = ()
        over.update(overrides or {})
        cell = build_cell(arch, shape_id, mesh, cfg_overrides=over,
                          force_micro=1, replicate_params=replicate_params)
        with mesh:
            compiled = jax.jit(
                cell["fn"], in_shardings=cell["in_shardings"],
                donate_argnums=cell["donate_argnums"]).lower(
                *cell["args"]).compile()
        cost = compiled.cost_analysis()
        colls = collective_stats(compiled.as_text())
        rec[L] = {"flops": float(cost.get("flops", 0.0)),
                  "bytes": float(cost.get("bytes accessed", 0.0)),
                  "coll": float(sum(v["bytes"] for v in colls.values())),
                  "meta": cell["meta"]}
    r = analyse(arch, shape_id, rec, tp=tp)
    if quantized_weights:
        # int8 weights: halve the analytic weight-read traffic and the
        # MXU compute time (2x int8 peak) — the paper's deployment effect
        # on the roofline terms (weight bytes dominate decode/serve).
        r["t_memory_s"] = r["t_memory_s"] / 2
        r["t_compute_s"] = r["t_compute_s"] / 2
        _rebottleneck(r)
        r["note"] = "int8-weight terms (W8A8 serve)"
    return r


def _rebottleneck(r):
    dom = max(("compute", r["t_compute_s"]), ("memory", r["t_memory_s"]),
              ("collective", r["t_collective_s"]), key=lambda kv: kv[1])
    r["bottleneck"] = dom[0]
    r["roofline_frac"] = r["t_compute_s"] / dom[1] if dom[1] else 1.0


def dit_fused_serving_factor(d: int = 1152, T: int = 256) -> float:
    """Memory-term factor for the fused single-pass int8 serving kernels
    vs the unfused int8 path, from the per-block DiT traffic model
    (consistent with benchmarks/kernel_micro.py's per-op models).

    Weights: qkv 3d^2 + proj d^2 + fc1 4d^2 + fc2 4d^2 = 12d^2 int8 bytes;
    the UNFUSED two-matmul MRQ path reads fc2's 4d^2 TWICE -> 16d^2.
    Activation input traffic per element: UNFUSED pays the standalone
    quantize pass (4B fp32 read + 1B code write) plus the matmul's 1B code
    read = 6B; FUSED reads the fp32 tile once in-kernel = 4B. Linear
    inputs per block: qkv/proj/fc1 (T,d) + fc2 (T,4d) = 7*T*d elements.
    Both paths write the fp32 outputs once (3d+d+4d+d per token = 36*T*d
    bytes).
    """
    unfused = 16 * d * d + 6 * 7 * T * d + 36 * T * d
    fused = 12 * d * d + 4 * 7 * T * d + 36 * T * d
    return fused / unfused


def log(exp, hypothesis, variant, r):
    path = "experiments/perf.json"
    data = json.load(open(path)) if os.path.exists(path) else []
    entry = {"exp": exp, "variant": variant, "hypothesis": hypothesis,
             "t_compute_ms": round(r["t_compute_s"] * 1e3, 3),
             "t_memory_ms": round(r["t_memory_s"] * 1e3, 3),
             "t_collective_ms": round(r["t_collective_s"] * 1e3, 3),
             "bottleneck": r["bottleneck"],
             "roofline_frac": round(r["roofline_frac"], 3)}
    data.append(entry)
    os.makedirs("experiments", exist_ok=True)
    json.dump(data, open(path, "w"), indent=1)
    print(f"[perf] {exp} / {variant}: comp={entry['t_compute_ms']}ms "
          f"mem={entry['t_memory_ms']}ms coll={entry['t_collective_ms']}ms "
          f"-> {entry['bottleneck']} frac={entry['roofline_frac']}",
          flush=True)
    return entry


SP = (("data",), "model")


def exp_qwen14b():
    arch, shape = "qwen2.5-14b", "train_4k"
    r = measure_variant(arch, shape)
    log(arch, "baseline (head-sharded attention; 40 heads % 16 != 0 makes "
        "GSPMD all-reduce the (S,S) scores)", "baseline", r)
    r = measure_variant(arch, shape, overrides={"attn_sp": SP})
    log(arch, "SP attention: shard q/scores/probs on seq over the model "
        "axis -> no quadratic-tensor collectives; predicted coll "
        "~100x down", "sp_attn", r)
    r = measure_variant(arch, shape, overrides={"attn_sp": SP,
                                                "q_chunk": 2048,
                                                "attn_impl": "qchunk"})
    log(arch, "SP + q-chunked attention: bound transient scores "
        "(memory-side insurance; collective term should hold)",
        "sp_attn+qchunk", r)


def exp_kimi():
    arch, shape = "kimi-k2-1t-a32b", "train_4k"
    r = measure_variant(arch, shape)
    log(arch, "baseline (FSDP expert tables re-gathered per layer; GQA "
        "kv=8 heads also hit the scores all-reduce)", "baseline", r)
    r = measure_variant(arch, shape, overrides={"attn_sp": SP})
    log(arch, "SP attention first (same fix as qwen2.5-14b)", "sp_attn", r)
    r = measure_variant(arch, shape, overrides={"attn_sp": SP,
                                                "moe_groups": 16})
    log(arch, "MoE dispatch groups = dp size: dispatch per data shard -> "
        "smaller expert all-gathers / token all-to-alls", "sp+moe_groups", r)


def exp_dit():
    arch, shape = "dit-xl-2", "sample_128"
    r = measure_variant(arch, shape)
    log(arch, "baseline TP16xDP16: per-device compute 0.6ms vs 37ms "
        "residual all-reduces — TP is wasted on a 675M model at serve",
        "baseline", r)
    r = measure_variant(arch, shape, mesh_shape=(128, 2))
    log(arch, "relayout the same 256 chips as DP128 x TP2: TP all-reduce "
        "bytes fall 8x per device; predicted collective ~50x down, "
        "memory(weights)-bound at ~0.8ms", "dp128_tp2", r)
    r = measure_variant(arch, shape, mesh_shape=(128, 2),
                        replicate_params=True)
    log(arch, "pure DP serving (params replicated, 675M bf16 = 1.35GB "
        "fits easily): ZERO per-layer collectives; each device does the "
        "full model at batch 1 -> weight-read bound", "dp_replicated", r)
    r = measure_variant(arch, shape, mesh_shape=(128, 2),
                        replicate_params=True, quantized_weights=True)
    log(arch, "the paper's W8A8 on top: int8 weights halve the weight-read "
        "term AND the MXU time (2x int8 peak) -> balanced compute/memory",
        "dp_replicated+w8a8", r)
    # fused single-pass serving kernels on top of the int8 layout: the
    # in-VMEM quantize prologue removes the standalone activation quantize
    # pass and the single-pass MRQ kernel reads fc2 weights once instead
    # of twice (see dit_fused_serving_factor for the per-block model).
    f = dit_fused_serving_factor()
    r = dict(r)
    r["t_memory_s"] = r["t_memory_s"] * f
    _rebottleneck(r)
    log(arch, f"fused int8 serving kernels (int8_matmul_fq + single-pass "
        f"MRQ): no standalone quantize pass, one fc2 weight read -> "
        f"memory term x{f:.2f} on the weight/activation traffic model",
        "dp_replicated+w8a8+fused", r)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", default="all",
                    choices=("all", "qwen14b", "kimi", "dit"))
    args = ap.parse_args()
    if args.exp in ("all", "qwen14b"):
        exp_qwen14b()
    if args.exp in ("all", "kimi"):
        exp_kimi()
    if args.exp in ("all", "dit"):
        exp_dit()


if __name__ == "__main__":
    main()


def exp_qwen14b_round2():
    """Round 2 after profiling the SP-attention HLO: the residual monster
    was the CE path — take_along_axis over vocab-sharded logits forced a
    37 GiB/device all-gather of the f32 logits. ce_loss was rewritten to
    the vocab-parallel form (iota-mask reduction + sharded logsumexp)."""
    arch, shape = "qwen2.5-14b", "train_4k"
    r = measure_variant(arch, shape, overrides={"attn_sp": SP})
    log(arch, "vocab-parallel CE (iota-mask reduction; no logits gather) "
        "+ SP attention; predicted collective ~50x down from baseline",
        "sp_attn+vp_ce", r)
    r = measure_variant(arch, shape)
    log(arch, "vocab-parallel CE alone (no SP attention) — isolate the "
        "contribution of each change", "vp_ce_only", r)


def exp_qwen14b_round3():
    """Round 3: after the head/embed FSDP-contraction fix (37 GiB logits
    all-reduce eliminated at the sharding-rule level), the remaining
    per-layer cost is the standard TP activation all-reduce, which scales
    with per-device batch. At fixed 256 chips, shrinking TP shrinks
    B_loc and the AR bytes 1:1 — and 40 heads divide TP=4/8, so the
    score-sharding problem vanishes without SP."""
    arch, shape = "qwen2.5-14b", "train_4k"
    r = measure_variant(arch, shape)
    log(arch, "fixed head/embed sharding rules (vocab-only, no fsdp on the "
        "contraction dim) — no SP needed", "headfix_tp16", r)
    r = measure_variant(arch, shape, overrides={"attn_sp": SP})
    log(arch, "head fix + SP attention (40 heads % 16 != 0 still pays "
        "score resharding at TP16)", "headfix_tp16_sp", r)
    r = measure_variant(arch, shape, mesh_shape=(32, 8))
    log(arch, "relayout 256 chips as DP32 x TP8: heads divide 8 -> clean "
        "head-sharded attention; AR bytes halve with B_loc", "dp32_tp8", r)
    r = measure_variant(arch, shape, mesh_shape=(64, 4))
    log(arch, "DP64 x TP4: AR bytes 4x down vs TP16; FSDP gather cost "
        "rises only ~2x (net win predicted ~3x)", "dp64_tp4", r)


def exp_qwen14b_round4():
    arch, shape = "qwen2.5-14b", "train_4k"
    r = measure_variant(arch, shape, mesh_shape=(128, 2))
    log(arch, "DP128 x TP2: AR bytes halve again; FSDP gather ~2x up; "
        "predicted coll ~1.9s vs compute 2.0s -> frac ~0.9", "dp128_tp2", r)


def exp_kimi_round2():
    """Round 2 after diagnosing the HLO: the monsters were (a) gate/up
    expert weights FSDP-sharded on their CONTRACTION dim d -> partial-sum
    all-reduces of the giant (E,C,f) tensors over "data", and (b) the
    global sort-based dispatch materializing the (NK,d) slot tensor
    cross-device. Fixed the expert sharding rules (f-dim FSDP) and added
    the EP dispatch pin (groups=dp, buffers G@data x E@model)."""
    arch, shape = "kimi-k2-1t-a32b", "train_4k"
    r = measure_variant(arch, shape)
    log(arch, "expert-FSDP rule fix alone (gate/up f-dim, down d-dim; no "
        "contraction dims)", "expert_fsdp_fix", r)
    r = measure_variant(arch, shape,
                        overrides={"moe_groups": 16,
                                   "moe_shard": (("data",), "model")})
    log(arch, "+ EP dispatch pin: local per-data-shard sort, buffers "
        "G@data x E@model (token all-to-all layout)", "ep_dispatch_pin", r)


def exp_kimi_round3():
    """Round 3: revert to the original expert rules (round 2 refuted both
    alternatives — recorded); remeasure the kimi baseline with only the
    head/embed fix, then try the one remaining safe lever: smaller TP
    (kv=8 heads divide TP=8, B_loc and AR bytes shrink)."""
    arch, shape = "kimi-k2-1t-a32b", "train_4k"
    r = measure_variant(arch, shape)
    log(arch, "reverted expert rules + head/embed fix only", "headfix", r)
    r = measure_variant(arch, shape, mesh_shape=(32, 8))
    log(arch, "DP32 x TP8: kv heads divide 8; EP=8 (48 experts/shard); "
        "B_loc halves -> activation ARs halve", "dp32_tp8", r)


def exp_kimi_round4():
    """Round 4: TP shrink refuted (dispatch cost is invariant to B_loc —
    the GLOBAL argsort keeps the slot tensors unsharded). Retry local
    dispatch (groups = dp) with the ORIGINAL expert rules, with and
    without the buffer pin."""
    arch, shape = "kimi-k2-1t-a32b", "train_4k"
    r = measure_variant(arch, shape, overrides={"moe_groups": 16})
    log(arch, "local dispatch: moe_groups=16 (argsort within each data "
        "shard; no sharding pins)", "moe_groups16", r)
    r = measure_variant(arch, shape,
                        overrides={"moe_groups": 16,
                                   "moe_shard": (("data",), "model")})
    log(arch, "local dispatch + buffer pin G@data x E@model",
        "moe_groups16_pin", r)
