"""The served kernels compile for a TPU v5e at DiT-XL/2 widths.

Interpret mode (every other kernel test) runs the kernel bodies on the
CPU and cannot see what the chip's compiler (Mosaic) refuses: MXU
operand types, block shapes the tiling does not admit, vector ops the
VPU lacks. These tests compile for a v5e chip that is described, not
attached, so they need no TPU.

The topology is described inside a fixture and nowhere at import: only
one process at a time may load the TPU library, and every pytest worker
imports every test file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attn_mrq import flash_attn_mrq, flash_attn_mrq_vec
from repro.kernels.int4_packed import int4_matmul_fq_vec
from repro.kernels.int8_fused import (
    int8_matmul_fq, int8_matmul_fq_vec, int8_matmul_mrq_fq,
    int8_matmul_mrq_fq_vec,
)

# DiT-XL/2: d_model 1152, mlp 4608, 16 heads of 72, 256 tokens; a pool of
# 4 slots runs 8 CFG rows.
D, FF, HEADS, HD, TOK = 1152, 4608, 16, 72, 256
ROWS = 8
M = ROWS * TOK
f32, bf16, i32, i8 = jnp.float32, jnp.bfloat16, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 — any failure means: no TPU lib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    a compile for a chip that is not attached is written to the cache
    but cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *args, **kwargs):
    """Compile ``fn`` for the described chip; python-int keyword
    arguments are static, the rest abstract operands. The kernel's own
    ``jax.jit`` is unwrapped, so the Pallas call's instruction name must
    come from its ``name=`` and not from the wrapper (the benchmark tells
    kernels apart by that name in the trace). Returns the instruction
    names of the compiled program's Pallas calls, without their ids."""
    static = {k: v for k, v in kwargs.items() if isinstance(v, int)}
    operands = {k: v for k, v in kwargs.items() if k not in static}
    compiled = jax.jit(functools.partial(fn.__wrapped__, out_dtype=bf16,
                                         interpret=False, **static)
                       ).lower(*args, **operands).compile()
    return set(re.findall(r"%([\w\-]+?)(?:\.\d+)? = [^\n]*"
                          r'custom_call_target="tpu_custom_call"',
                          compiled.as_text()))


# (kernel, G, K, N, fusion, rows) — the linears of one DiT block: qkv and
# fc1 take the adaLN norm-modulate prologue, proj and fc2 (MRQ input) the
# gate+residual epilogue; ada runs on one row per batch entry, unfused.
# A batch entry owns TOK rows, so each row tile lies in one entry;
# "nm-rows" gives an entry 64 rows, which makes the kernel select each
# row's entry in VMEM instead. The served pool (8 slots x 2 CFG halves)
# runs 4,096 token rows and 16 per-sample rows, at the planned tiles;
# x_proj (K = 16) and final (N = 16) are its narrowest linears.
SERVED = 2 * M
LINEARS = [
    ("fq", 1, D, 3 * D, "nm", M),
    ("fq", 10, D, 3 * D, "nm", M),
    ("fq", 10, D, 6 * D, None, ROWS),
    ("mrq", 10, FF, D, "gr", M),
    ("fq_vec", 10, D, FF, "nm", M),
    ("fq_vec", 10, D, FF, "nm-rows", M),
    ("fq_vec", 10, D, D, "gr", M),
    ("mrq_vec", 10, FF, D, "gr", M),
    ("fq_vec", 10, D, 3 * D, "nm", SERVED),
    ("fq_vec", 10, D, D, "gr", SERVED),
    ("fq_vec", 10, D, FF, "nm", SERVED),
    ("mrq_vec", 10, FF, D, "gr", SERVED),
    ("fq_vec", 10, D, 6 * D, None, 16),
    ("fq_vec", 10, 16, D, None, SERVED),
    ("fq_vec", 10, D, 16, "nm", SERVED),
]
KERNELS = {"fq": int8_matmul_fq, "mrq": int8_matmul_mrq_fq,
           "fq_vec": int8_matmul_fq_vec, "mrq_vec": int8_matmul_mrq_fq_vec}
NAMES = {"fq": "int8_matmul_fq", "mrq": "int8_matmul_mrq_fq",
         "fq_vec": "int8_matmul_fq_vec", "mrq_vec": "int8_matmul_mrq_fq_vec"}


def _linear_id(kind, G, K, N, fusion, n):
    base = f"{kind}-G{G}-{N}-{fusion}"
    if n == (M if fusion else ROWS):
        return base
    return base + ("" if K == D else f"-K{K}") + f"-{n}rows"


@pytest.mark.parametrize("kind,G,K,N,fusion,n", LINEARS,
                         ids=[_linear_id(*c) for c in LINEARS])
def test_int8_linear_compiles_for_v5e(one_chip, kind, G, K, N, fusion, n):
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    corr = sds((G, N), f32 if kind.startswith("mrq") else i32)
    args = (sds((n, K), bf16), sds((K, N), i8), sds((G, 1), f32),
            sds((G, 1), f32), sds((G, N), f32), corr, sds((N,), f32))
    kw = ({"gv": sds((n,), i32)} if kind.endswith("_vec")
          else {"g": sds((), i32)})
    per_batch = 64 if fusion == "nm-rows" else TOK
    if fusion in ("nm", "nm-rows"):
        kw["nm"] = (sds((n // per_batch, K), f32),
                    sds((n // per_batch, K), f32))
    elif fusion == "gr":
        kw["gr"] = (sds((n // per_batch, N), f32), sds((n, N), f32))
    if fusion:
        kw["rows_per_batch"] = per_batch
    assert _compile(KERNELS[kind], *args, **kw) == {NAMES[kind]}


def test_int4_linear_vec_compiles_for_v5e(one_chip):
    """The nibble unpack (packed int4 weights widened to s8 in VMEM)."""
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    G, nk, N = 10, 5, 3 * D                    # K = 1152 pads to 5 x 256
    names = _compile(int4_matmul_fq_vec, sds((M, D), bf16),
                     sds((nk * 128, N), i8), sds((G, 1), f32),
                     sds((G, 1), f32), sds((G, nk, N), f32),
                     sds((G, nk, N), i32), sds((N,), f32), gv=sds((M,), i32))
    assert names == {"int4_matmul_fq_vec"}


# (vec, tokens, rows): the 256-token pool, and DiT-XL/2 at 512x512 (1,024
# tokens; 2 slots run 4 CFG rows), where one kv tile spans every key.
@pytest.mark.parametrize("vec,S,rows", [(False, TOK, ROWS), (True, TOK, ROWS),
                                        (False, 1024, 4), (True, 1024, 4)],
                         ids=["scalar", "vec", "scalar-1k", "vec-1k"])
def test_flash_attention_compiles_for_v5e(one_chip, vec, S, rows):
    """head_dim 72 (not a lane multiple), S = 256 and 1,024, G = 10."""
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    BH, G = rows * HEADS, 10
    qkv = [sds((BH, S, HD), bf16) for _ in range(3)]
    params = [sds((G, 1), f32) for _ in range(7)]
    g = sds((BH,), i32) if vec else sds((), i32)
    names = _compile(flash_attn_mrq_vec if vec else flash_attn_mrq, *qkv,
                     *params, g_qk=g, g_pv=g)
    assert names == {"flash_attn_mrq_vec" if vec else "flash_attn_mrq"}


def _unfused_instructions(text):
    """(name, type) of every instruction of the compiled program outside
    fusion bodies: what the program writes to memory."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    out, keep = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) ", line)
        if head and not line.startswith(" "):
            keep = head.group(1) not in fused
        elif keep:
            m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (\(?\w+\[[\d,]*\])", line)
            if m:
                out.append(m.groups())
    return out


@pytest.mark.parametrize("S,rows", [(TOK, ROWS), (1024, 4)],
                         ids=["256", "1k"])
def test_served_attention_writes_no_f32_qkv(one_chip, monkeypatch, S, rows):
    """The served attention, from the qkv linear's output to the flash
    call: q/k/v become s8 codes in the pass that splits the heads, so no
    instruction writes them in f32 (once, every grid step quantized a
    transposed f32 copy)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "INTERPRET", False)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    G = 10

    def attn(qkv, sq, sk, sc, s1, sv, sc1, sc2, gv):
        q, k, v = jnp.split(qkv.reshape(rows, S, 3, HEADS, HD), 3, axis=2)
        qk = {"s_q": sq, "s_k": sk, "scale": sc, "bits": 8, "groups": G}
        pv = {"s1": s1, "s_v": sv, "scale1": sc1, "scale2": sc2, "bits": 8,
              "groups": G}
        return ops.flash_attention(q[:, :, 0, :, None], k[:, :, 0],
                                   v[:, :, 0], qk, pv, scale=HD ** -0.5,
                                   tgroup=gv)

    text = jax.jit(attn).lower(sds((rows, S, 3 * D), bf16),
                               *[sds((G, 1), f32)] * 7,
                               sds((rows,), i32)).compile().as_text()
    written = _unfused_instructions(text)
    assert any(n.startswith("flash_attn_mrq_vec") for n, _ in written)
    qkv_f32 = f"f32[{rows},{HEADS}"
    assert not [w for w in written if qkv_f32 in w[1]], written
