"""CPU tests of the harness's parts: the traffic generator, the trace
reduction, the operation counts, the peaks table and how cells, configs,
traffic mixes and metrics are found."""
import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import ops, peaks, trace, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
BIG = 2 ** 33 + 12345          # seeds are larger than 32 bits


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# --- traffic -----------------------------------------------------------------
TR = {"loop": "open", "rate_per_s": 2.5, "steps": 100, "guidance": 1.5}


def head(seed, n=50):
    return list(itertools.islice(traffic.stream(TR, 1000, seed), n))


def test_same_seed_same_requests():
    assert head(BIG) == head(BIG)
    a, b = head(BIG), head(BIG + 1)
    assert [r.label for r in a] != [r.label for r in b]
    assert [r.noise_seed for r in a] != [r.noise_seed for r in b]
    assert all(0 <= r.label < 1000 and 0 <= r.noise_seed < 2 ** 32
               for r in a)


def test_same_seed_same_arrivals_and_every_seed_same_work():
    a = traffic.arrival_offsets(TR, BIG, 51.0, before=5, after=10)
    assert a == traffic.arrival_offsets(TR, BIG, 51.0, before=5, after=10)
    b = traffic.arrival_offsets(TR, BIG + 7, 51.0, before=5, after=10)
    assert a != b
    win = lambda xs: sorted(x for x in xs if 0 <= x < 51.0)
    # the window holds the same number of arrivals whatever the seed
    assert len(win(a)) == len(win(b)) == round(2.5 * 51)
    assert np.all(np.diff(a) >= 0)
    assert a[0] == pytest.approx(-5.0)


# --- trace reduction ---------------------------------------------------------
def test_trace_reduction_on_a_synthetic_trace():
    ops_ = [("%while.1 = (...) while(...)", 100, 900),     # container
            ("%int8_matmul_fq_vec.3 = bf16[8,8] custom-call()", 100, 400),
            ("%fusion.7 = f32[8] fusion()", 400, 500),
            ("%flash_attn_mrq_vec.2 = bf16[8,8] custom-call()", 500, 900),
            ("%int8_matmul_fq_vec.4 = bf16[8,8] custom-call()", 1500, 1700)]
    assert trace.union(ops_) == [(100, 900), (1500, 1700)]
    assert trace.busy_ns(ops_, 0, 2000) == 1000
    assert trace.busy_ns(ops_, 200, 1600) == 800      # clipped
    assert trace.gaps(ops_, 0, 2000) == [(0, 100), (900, 1500),
                                         (1700, 2000)]
    host = [("bench.pump", 0, 1200), ("bench.sample_pull", 950, 1100)]
    by = trace.attribute(trace.gaps(ops_, 0, 2000), host, min_ns=50)
    assert by == {"bench.pump": 100 + 50 + 100, "bench.sample_pull": 150,
                  "no span": 300 + 300}
    assert trace.attribute([(0, 10)], host, min_ns=50) == \
        {"gaps under 0 us": 10}
    kinds = trace.kind_time(ops_, 0, 1000)
    assert kinds["int8_matmul_fq_vec"] == (1, 300)
    assert kinds["flash_attn_mrq_vec"] == (1, 400)
    assert "int8_matmul_fq_vec" in dict(trace.top_ops(ops_, 0, 2000))
    assert "while" not in dict(trace.top_ops(ops_, 0, 2000))
    assert trace.op_kind("%custom-call.12 = s8[4] custom-call()") == \
        "custom-call"


# --- operations, bytes, peaks -----------------------------------------------
@pytest.mark.parametrize("name,gmacs", [("dit-xl2-256-w8a8", 118.6),
                                        ("dit-xl2-512-w8a8", 524.6)])
def test_forward_matches_published_gmacs(name, gmacs):
    got = ops.forward_macs(config(name)) / 1e9
    assert got == pytest.approx(gmacs, rel=1e-3)


def test_linear_calls_are_the_served_packs_and_bytes_add_up():
    c = config("dit-xl2-256-w8a8")
    calls = ops.linear_calls(c, rows=16)
    assert len(calls) == 28 * 5 + 5            # 145 int8 linear packs
    fc1 = [x for x in calls if x.site == "fc1"][0]
    assert (fc1.M, fc1.K, fc1.N) == (16 * 256, 1152, 4608)
    plain = ops.linear_bytes(fc1._replace(fusion=""), 16, 2)
    assert plain == 4096 * 1152 * 2 + 1152 * 4608 + 4096 * 4608 * 2 + 4608 * 2
    assert ops.linear_bytes(fc1, 16, 2) > plain
    assert ops.roofline_s(10, 1, 5.0, 1.0) == 2.0
    assert ops.roofline_s(10, 100, 5.0, 1.0) == 100.0


def test_peaks_table_refuses_an_unknown_device():
    p = peaks.peaks("TPU v5 lite")
    assert p["int8_ops"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


# --- found by name -----------------------------------------------------------
def test_every_name_in_the_benchmark_is_a_file():
    b = bench_json()
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert config(c["name"])["name"] == c["name"]
    for w in b["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    from harness import cell
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(cell.load_reader(m["name"]))


def test_load_cell_finds_config_and_traffic():
    sys.path.insert(0, BENCH)
    import run as bench_run
    bench, cell, cfg, tr = bench_run.load_cell(
        "xl2-256-w8a8.saturated", os.path.join(ROOT, "BENCHMARK.json"))
    assert cfg["input_size"] == 32 and tr["loop"] == "closed"
    with pytest.raises(KeyError):
        bench_run.load_cell("no-such-cell",
                            os.path.join(ROOT, "BENCHMARK.json"))


def test_without_a_tpu_the_run_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "xl2-256-w8a8.saturated", "--seed", str(BIG),
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_compile_counter_sees_a_compile_and_nothing_after():
    import jax
    from harness.cell import CompileCounter
    f = jax.jit(lambda x: x * 3 + 1)
    with CompileCounter() as c:
        f(1.0)
    assert c.n >= 1
    with CompileCounter() as c:
        f(2.0)
    assert c.n == 0
