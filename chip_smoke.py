"""On-chip smoke test of the served path: DiT-XL/2 at W8A8 on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # data-parallel slot pool, 4 chips

One chip: builds DiT-XL/2 at its published widths (28 layers, d_model
1152, 16 heads of 72, 256 tokens, 1000 classes, bf16) from ``dit_init``
with random weights drawn from a fixed seed, quantizes it with
``QuantRecipe(bits="w8a8", method="range")`` and serves mixed-label,
CFG-guided requests through ``AsyncServeEngine.from_artifact`` with every
quantized op on a compiled Pallas kernel. It then compares one
``dit_apply`` forward per TGQ group (and one with mixed groups per row)
on the kernels against the artifact's fake-quant context, run at the
highest matmul precision.

Four chips: serves the same requests on a slot pool sharded over a
4-device ``make_serving_mesh(4)`` and on a one-device pool with as many
slots as each of the four devices holds, in the same process, and
requires bit-identical samples with every device holding its own shard
of the pool.

Any failed check exits non-zero before the last line. On success the
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times and memory printed on earlier lines are single-run bring-up
numbers, not measurements.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
STEPS = 8                 # one step bucket
CHUNK = 4                 # divides STEPS: two dispatches per request
CFG_SCALE = 1.5
N_REQUESTS = 8
MICROBATCH = 8            # slots; divisible by the four-chip mesh
EPS_RTOL = 5e-2           # kernel vs fake-quant eps, relative L2


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu():
    """Fail unless JAX's first device is a TPU and the Pallas kernels
    compile for it (not interpret mode). Returns the device description."""
    import jax
    from repro.kernels import ops
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    check(d.platform == "tpu",
          f"no TPU: JAX's first device is on {d.platform!r}")
    check(not ops.INTERPRET, "Pallas kernels would run in interpret mode")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def build_params(cfg, seed: int):
    """``dit_init`` with every leaf then perturbed by N(0, 0.02²) noise.

    adaLN-Zero initializes the block gates and the final layer to zero,
    which makes a freshly initialized DiT the identity map with a zero
    output; the perturbation gives every layer real work."""
    import jax
    import jax.numpy as jnp
    from repro.models import dit_init

    def make():
        params = dit_init(jax.random.PRNGKey(seed), cfg)
        leaves, tree = jax.tree.flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
        return jax.tree.unflatten(tree, [
            a + (0.02 * jax.random.normal(k, a.shape, jnp.float32)
                 ).astype(a.dtype) for a, k in zip(leaves, keys)])
    return jax.jit(make)()


def quantize_w8a8(params, cfg, dif):
    """``quantize()`` at W8A8 range calibration; every quantized op must
    carry a kernel pack."""
    from repro.quant import QuantRecipe, quantize
    t0 = time.perf_counter()
    art = quantize(params, cfg, dif, QuantRecipe(bits="w8a8", method="range"),
                   provenance={"weights": f"random, seed {SEED}"})
    print(f"quantized: {art.summary()} in {time.perf_counter() - t0:.1f}s",
          flush=True)
    check(art.has_kernel_packs, "artifact carries no kernel packs")
    fb = art.fallback_ops()
    check(fb == [], f"ops without a kernel pack: {fb}")
    return art


def requests(cfg, first_id=0, seed0=1000, n=N_REQUESTS):
    from repro.serving import GenRequest
    return [GenRequest(request_id=first_id + i,
                       label=(seed0 - 1000 + i * 137 + 11) % cfg.n_classes,
                       steps=STEPS, cfg_scale=CFG_SCALE, seed=seed0 + i)
            for i in range(n)]


def engine(params, art, mesh=None, microbatch=MICROBATCH):
    """``AsyncServeEngine.from_artifact`` on the flash kernel path."""
    from repro.serving import AsyncServeEngine
    eng = AsyncServeEngine.from_artifact(
        params, art, mesh=mesh, microbatch=microbatch, step_buckets=(STEPS,),
        chunk=CHUNK)
    check(eng.ctx.kernel and eng.ctx.attn_impl == "flash",
          f"engine context is not the flash kernel path: {eng.ctx!r:.200}")
    return eng


def serve(eng, reqs):
    """Serve ``reqs`` on ``eng`` and check that the kernel path stayed in
    place. Returns (samples in request order, wall s)."""
    import numpy as np
    t0 = time.perf_counter()
    out = eng.serve(reqs)
    wall = time.perf_counter() - t0
    st = eng.stats
    bad = {rid: (o.status, o.error) for rid, o in out.items()
           if o.status != "OK"}
    check(not bad, f"requests not OK: {bad}")
    check(st["chunk_traces"] == 1,
          f"chunk executable traced {st['chunk_traces']} times")
    check(st["degradations"] == [],
          f"engine degraded: {[d['reason'] for d in st['degradations']]}")
    check(eng.ctx.kernel and eng.ctx.attn_impl == "flash",
          "engine left the flash kernel path")
    samples = np.stack([out[r.request_id].sample for r in reqs])
    check(bool(np.all(np.isfinite(samples))), "non-finite samples")
    return samples, wall


def neighbours_check(eng, cfg, reqs, samples) -> None:
    """Serve half of ``reqs`` again on the same pool, each in another slot
    next to a request it has not met: a request's sample must not depend
    on what occupies the other slots."""
    import dataclasses
    import numpy as np
    half = len(reqs) // 2
    again = [dataclasses.replace(r, request_id=2 * N_REQUESTS + i)
             for i, r in enumerate(reqs[:half])]
    others = requests(cfg, first_id=3 * N_REQUESTS, seed0=5000, n=half)
    mixed = [r for pair in zip(others, again) for r in pair]
    got, _ = serve(eng, mixed)
    same = bool(np.array_equal(got[1::2], samples[:half]))
    print(f"{half} requests served again next to other requests, in other "
          f"slots of the same pool: bit-identical {same}", flush=True)
    check(same, "a request's sample changed with its neighbours: max |diff| "
          f"{float(np.max(np.abs(got[1::2] - samples[:half])))}")


def eps_errors(params, cfg, art):
    """Relative L2 error of eps, kernels vs fake-quant at the highest
    matmul precision: one forward per TGQ group, then one forward whose
    rows sit in different groups (the vector-tgroup kernels)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.diffusion import tgroup_of
    from repro.models import dit_apply

    dif = art.dif_cfg()
    G, T = dif.tgq_groups, dif.T
    kq, kctx = art.context(kernel=True).split_arrays()
    rq, rctx = art.context(kernel=False).split_arrays()
    fk = jax.jit(lambda p, q, x, t, y, g: dit_apply(
        p, cfg, x, t, y, ctx=kctx(q).with_tgroup(g)))
    fr = jax.jit(lambda p, q, x, t, y, g: dit_apply(
        p, cfg, x, t, y, ctx=rctx(q).with_tgroup(g)))
    B = 4
    kx, ky = jax.random.split(jax.random.PRNGKey(SEED + 2))
    x = jax.random.normal(kx, (B, cfg.img_size, cfg.img_size, cfg.in_ch))
    y = jax.random.randint(ky, (B,), 0, cfg.n_classes)

    def rel(t, g):
        ek = np.asarray(fk(params, kq, x, t, y, g), np.float32)
        with jax.default_matmul_precision("highest"):
            er = np.asarray(fr(params, rq, x, t, y, g), np.float32)
        check(bool(np.all(np.isfinite(ek))), "non-finite kernel eps")
        return float(np.linalg.norm(ek - er) / np.linalg.norm(er))

    errs = {}
    for g in range(G):
        t = jnp.full((B,), (2 * g + 1) * T // (2 * G), jnp.int32)
        errs[str(g)] = rel(t, jnp.int32(g))
    t = jnp.asarray([(2 * g + 1) * T // (2 * G)
                     for g in np.linspace(0, G - 1, B).astype(int)],
                    jnp.int32)
    errs["mixed"] = rel(t, tgroup_of(t, T, G))
    return errs


def peak_hbm(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def one_chip(cfg) -> None:
    import jax
    from repro.diffusion import DiffusionCfg
    t0 = time.perf_counter()
    params = build_params(cfg, SEED)
    jax.block_until_ready(params)
    print(f"built {cfg.n_layers}-layer d_model={cfg.d_model} DiT in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    art = quantize_w8a8(params, cfg, DiffusionCfg())
    reqs = requests(cfg)
    eng = engine(params, art)
    samples, wall = serve(eng, reqs)
    compile_s = eng.stats["compile_s"]
    print(f"bring-up (single run, not a measurement): served "
          f"{len(reqs)} requests x {STEPS} steps, chunk executable compile "
          f"{compile_s:.1f}s, serve {wall - compile_s:.1f}s, "
          f"{eng.stats['dispatches']} dispatches", flush=True)
    print(f"bring-up (single run, not a measurement): peak HBM bytes "
          f"through serving {peak_hbm(jax.devices()[0])}", flush=True)
    neighbours_check(eng, cfg, reqs, samples)
    errs = eps_errors(params, cfg, art)
    print("eps relative L2 error, kernels vs fake-quant: "
          + json.dumps({k: float(f"{v:.3e}") for k, v in errs.items()}),
          flush=True)
    worst = max(errs.values())
    check(worst <= EPS_RTOL,
          f"eps relative error {worst:.3e} exceeds {EPS_RTOL}")


def four_chips(cfg) -> None:
    import jax
    import numpy as np
    from repro.diffusion import DiffusionCfg
    from repro.launch.mesh import make_serving_mesh
    check(jax.device_count() >= 4,
          f"--four-chips needs 4 devices, JAX sees {jax.device_count()}")
    params = build_params(cfg, SEED)
    art = quantize_w8a8(params, cfg, DiffusionCfg())
    reqs = requests(cfg)
    mesh = make_serving_mesh(4)
    eng4 = engine(params, art, mesh=mesh)
    s4, wall4 = serve(eng4, reqs)
    pool = eng4._x
    held = sorted(d.id for d in pool.sharding.device_set)
    check(held == sorted(d.id for d in mesh.devices.flat),
          f"slot pool lives on devices {held}, not the whole mesh")
    shards = {s.device.id: s.data.shape[0] for s in pool.addressable_shards}
    check(len(shards) == 4 and set(shards.values()) == {MICROBATCH // 4},
          f"slot pool shards per device: {shards}")
    print(f"four-chip pool: {MICROBATCH} slots as {shards} "
          f"(device id: slots); served in {wall4:.1f}s incl. "
          f"{eng4.stats['compile_s']:.1f}s compile "
          "(bring-up, not a measurement)", flush=True)
    # the reference pool has the four-chip pool's slots per device: on a
    # TPU the forward's rounding depends on the rows per dispatch (one
    # chip, 8 slots vs 2: samples 2e-2 apart), which sharding must not
    # change
    s1, wall1 = serve(engine(params, art, microbatch=MICROBATCH // 4), reqs)
    same = bool(np.array_equal(s4, s1))
    print(f"one-device pool of {MICROBATCH // 4} slots served in "
          f"{wall1:.1f}s; 4-device samples bit-identical to 1-device: "
          f"{same}", flush=True)
    check(same, "4-device samples differ from 1-device samples: max |diff| "
          f"{float(np.max(np.abs(s4 - s1)))}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device data-parallel pool phase")
    args = ap.parse_args(argv)
    try:
        from repro.configs.dit_xl_2 import full
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repo's code: {e}",
              file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    try:
        device = require_tpu()
        print(f"compile cache: {enable_compile_cache()}", flush=True)
        cfg = full()
        (four_chips if args.four_chips else one_chip)(cfg)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(f"chip_smoke passed in {time.perf_counter() - t0:.0f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
