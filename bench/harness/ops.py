"""Operations and bytes of the served DiT, from its published shapes.

Counts are of useful work: one multiply-add is two operations, a
linear needs ``2 M K N`` of them and an attention head ``2 S^2 D`` for
QK^T plus as many for P.V. A kernel that does more than that (the MRQ
kernels accumulate the two regions of their input in two passes over
the MXU) spends the rest as overhead, which lowers its roofline share.

Bytes are the least that each Pallas call has to move through HBM:
activations in and out at the served dtype, int8 weight codes, and the
per-row adaLN operands a fused prologue or epilogue reads. Per-group
quantizer parameters (a few vectors of length N per TGQ group) are left
out; at DiT widths they are under 0.3% of a call's bytes.

Checked against DiT's published compute (Peebles & Xie 2023, Table 4):
118.6 GFLOPs (multiply-adds, as fvcore counts them) per DiT-XL/2 forward
at 256x256 and 524.6 at 512x512.
"""
from __future__ import annotations

from typing import List, NamedTuple


class Call(NamedTuple):
    site: str        # linear site, e.g. "qkv"
    M: int           # rows
    K: int
    N: int
    fusion: str      # "" | "norm_mod" | "gate_residual"


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    tokens = (c["input_size"] // c["patch_size"]) ** 2
    return {"d": d, "f": int(d * c["mlp_ratio"]), "tokens": tokens,
            "heads": c["num_heads"], "head_dim": d // c["num_heads"],
            "patch_dim": c["patch_size"] ** 2 * c["in_channels"],
            "layers": c["depth"]}


def linear_calls(c: dict, rows: int) -> List[Call]:
    """Every linear of one forward over ``rows`` samples (both CFG halves
    of every slot), in program order."""
    k = dims(c)
    d, f, T = k["d"], k["f"], k["tokens"]
    M = rows * T
    calls = [Call("x_proj", M, k["patch_dim"], d, ""),
             Call("t_mlp1", rows, 256, d, ""),
             Call("t_mlp2", rows, d, d, "")]
    for _ in range(k["layers"]):
        calls += [Call("ada", rows, d, 6 * d, ""),
                  Call("qkv", M, d, 3 * d, "norm_mod"),
                  Call("proj", M, d, d, "gate_residual"),
                  Call("fc1", M, d, f, "norm_mod"),
                  Call("fc2", M, f, d, "gate_residual")]
    calls += [Call("final_ada", rows, d, 2 * d, ""),
              Call("final", M, d, k["patch_dim"], "norm_mod")]
    return calls


def linear_ops(call: Call) -> int:
    return 2 * call.M * call.K * call.N


def linear_bytes(call: Call, rows: int, act_bytes: int) -> int:
    """x in, int8 W in, y out; a norm-modulate prologue adds the row
    statistics (two f32 per row) and the per-sample shift and scale rows;
    a gate-residual epilogue adds the residual tile and the gate rows."""
    M, K, N = call.M, call.K, call.N
    b = M * K * act_bytes + K * N + M * N * act_bytes + N * act_bytes
    if call.fusion == "norm_mod":
        b += M * 8 + 2 * rows * K * act_bytes
    elif call.fusion == "gate_residual":
        b += M * N * act_bytes + rows * N * act_bytes
    return b


def attention_ops(c: dict, rows: int) -> int:
    """QK^T and P.V of one layer's attention over ``rows`` samples."""
    k = dims(c)
    return 2 * 2 * rows * k["heads"] * k["tokens"] ** 2 * k["head_dim"]


def attention_bytes(c: dict, rows: int, act_bytes: int) -> int:
    """q, k and v in, the output out, at the served dtype; scores and
    probability codes stay in VMEM."""
    k = dims(c)
    return 4 * rows * k["tokens"] * k["heads"] * k["head_dim"] * act_bytes


def forward_macs(c: dict) -> int:
    """Multiply-adds of one forward of one sample: every linear plus
    attention's two products (what DiT's published GFLOPs count)."""
    lin = sum(linear_ops(x) for x in linear_calls(c, 1)) // 2
    return lin + c["depth"] * attention_ops(c, 1) // 2


def slot_step_ops(c: dict) -> int:
    """Operations of one denoising step of one request: a forward of the
    conditional and of the unconditional half."""
    return 2 * 2 * forward_macs(c)


def roofline_s(ops: int, nbytes: int, peak_ops: float, bw: float) -> float:
    """The least time the chip can take: the larger of the compute bound
    and the memory bound."""
    return max(ops / peak_ops, nbytes / bw)
