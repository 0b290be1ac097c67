"""Compile each configuration's chunk executable for a described TPU v5e.

    JAX_PLATFORMS=cpu python bench/tests/compile_v5e.py [config ...]

A rehearsal that needs no chip: the executable the window dispatches
(``AsyncServeEngine``'s chunk of ``ddpm_chunk_slots`` over the whole
quantized DiT) is lowered for one chip of a described ``v5e:2x2`` and
compiled by the TPU's compiler, which refuses what the chip would.
Shapes come from ``jax.eval_shape``; the quantizer packs from a 2-layer
calibration at the configuration's widths and tokens, repeated over its
depth (their shapes do not depend on the layer). Prints, per
configuration, the number of Pallas calls in the compiled program and
its ``memory_analysis()``.
"""
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def compile_config(c: dict, one_chip) -> dict:
    import jax
    from harness import cell, weights
    from repro.diffusion import DiffusionCfg
    from repro.kernels import ops as kops
    from repro.quant import QuantArtifact, QuantRecipe, quantize
    from repro.serving import AsyncServeEngine

    dif = DiffusionCfg(tgq_groups=c["tgq_groups"])
    recipe = QuantRecipe(bits=c["quant"]["bits"], method=c["quant"]["method"])
    small = dict(c, depth=2)
    art2 = quantize(weights.make(small), cell.model_cfg(small), dif, recipe)
    qp = {}
    for name, v in art2.qparams.items():
        if name.startswith("blk"):
            rest = name.split("/", 1)[1]
            for layer in range(int(name[3:name.index("/")]), c["depth"], 2):
                qp[f"blk{layer}/{rest}"] = v
        else:
            qp[name] = v
    art = QuantArtifact(qparams=qp, recipe=art2.recipe, meta=art2.meta)
    e = c["engine"]
    kops.INTERPRET = False
    eng = AsyncServeEngine(
        None, cell.model_cfg(c), dif, ctx=art.context(kernel=True),
        microbatch=int(e["slots"]), step_buckets=tuple(e["step_buckets"]),
        chunk=int(e["chunk"]), pipeline=int(e["pipeline"]))

    def abstract(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(abstract, jax.eval_shape(lambda: weights.make(c)))
    args = jax.tree.map(abstract, eng._chunk_args(eng._x, eng._pos))
    args = (params,) + tuple(args[1:])
    t = time.perf_counter()
    compiled = eng._chunk_fn.lower(*args).compile()
    secs = time.perf_counter() - t
    ma = compiled.memory_analysis()
    return {"config": c["name"], "compile_s": round(secs, 1),
            "pallas_calls": compiled.as_text().count('custom_call_target='
                                                     '"tpu_custom_call"'),
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes}


def main(names) -> int:
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    if not names:
        with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
            names = [x["name"] for x in json.load(f)["configs"]]
    for name in names:
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            print(json.dumps(compile_config(json.load(f), one_chip)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
