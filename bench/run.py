"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration
(``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<traffic>.json``) are found by name from
``BENCHMARK.json``; each metric is read by ``bench/metrics/<name>.py``.
The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared for ``correct``
beside its limit). Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.

``--control 4`` puts the reference, fake-quantized at 4 bits, in the
program's place in the comparison: the lower-precision control, which
has to read not correct. The benchmark's own runs never pass it.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def load_cell(name: str, bench_file: str):
    with open(bench_file) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file} "
                       f"(known: {sorted(cells)})")
    cell = cells[name]
    with open(os.path.join(BENCH, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        tr = json.load(f)
    return bench, cell, config, tr


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, default=None, metavar="BITS",
                    help="judge the reference fake-quantized at BITS in the "
                         "program's place (the control; not a benchmark run)")
    args = ap.parse_args(argv)
    try:
        bench, cell, config, tr = load_cell(
            args.workload, os.path.join(ROOT, "BENCHMARK.json"))
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot load the cell: {e}")

    # the benchmark's own compile cache, at a fixed path in the checkout;
    # the TPU runtime's log files would go to a fixed path under /tmp
    cache = os.path.join(BENCH, ".cache", "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        import repro  # the system under test, from this checkout
        from harness import cell as harness
    except ImportError as e:
        return fail(f"cannot import the system under test: {e}")
    where = [os.path.abspath(p) for p in repro.__path__]
    if not all(p.startswith(os.path.join(ROOT, "src") + os.sep)
               for p in where):
        return fail(f"the system under test is not this checkout's: {where}")

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"no TPU: JAX's first device is on {devs[0].platform!r}")
    if len(devs) < int(cell["chips"]):
        return fail(f"the cell asks for {cell['chips']} chips, JAX sees "
                    f"{len(devs)}")
    print(f"bench: device platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", file=sys.stderr,
          flush=True)
    out = harness.run_cell(bench, cell, config, tr, args.seed, args.seconds,
                           bool(args.trace), T_START,
                           control=args.control)
    print(f"bench: correct = {out['correct']}", file=sys.stderr, flush=True)
    for name, c in out["checks"].items():
        print(f"bench: check {name} = {c['value']!r} (limit {c['limit']!r}, "
              f"{c['requests']} requests)", file=sys.stderr, flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
