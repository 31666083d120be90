"""Least time the chip could take over the delta rule's chunk-local work of
a train step, over the device time of the kernels that do it.

The floor is reckoned kernel by kernel, forward and backward, once a linear
layer: the larger of the operations the chunk's
algebra needs over the peak and of the bytes of the arrays the kernel is
handed and hands back over the bandwidth (``chunk_local_cost`` below: what
the algebra needs, whatever a kernel pads, rebuilds or multiplies twice). A
forward kernel run a second time under ``remat`` is time and no further
work, and a kernel cannot finish before its operands have crossed HBM once,
so the share cannot pass 100 %. Both kernels are bound by their bytes: 647
MB a layer forward and 932 MB backward at the hybrid cell's shape, 0.79 and
1.14 ms, beside 0.08 and 0.22 ms of operations."""

from benchmark.metrics import gdn_chunk_local_ms

CHUNK = 64


def chunk_local_cost(cfg: dict, traffic: dict, itemsize: int = 2) -> dict:
    """``{kernel: {"flops", "bytes"}}`` for one linear layer and one step.

    Forward: reads ``q, k`` (float32), ``v`` (``itemsize``) and the two
    gates; writes ``w, q_in, k_out, attn`` (``itemsize``) and ``u0``
    (float32); four products a chunk (``K K^T``, ``Q K^T``, ``W``,
    ``U_0``) and the triangular inverse at ``C^3 / 3`` multiply-adds.
    Backward: reads the forward's inputs and the cotangents of its five
    outputs, writes the cotangents of its five inputs; the forward's
    ``K K^T``, ``Q K^T`` and inverse once more, ``d_inv`` (two products),
    the inverse's own derivative (two of ``C^3``), ``d_kb`` and ``d_vb``,
    and ``d_q``, ``d_k`` (one and three)."""
    B, T = traffic["batch"], traffic["seq_len"]
    H, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    C = CHUNK
    tokens = B * H * (-(-T // C) * C)       # whole chunks, every head
    inputs = tokens * (2 * dk * 4 + dv * itemsize + 2 * 4)
    outputs = tokens * (3 * dk * itemsize + dv * 4 + C * itemsize)
    wide = 2.0 * tokens * C             # one product of [C, C] by [C, d], per d
    inverse = wide * C / 3
    return {
        "gdn_chunk_local_fwd": {
            "bytes": inputs + outputs,
            "flops": wide * (3 * dk + dv) + inverse},
        "gdn_chunk_local_bwd": {
            "bytes": 2 * inputs + outputs,
            "flops": wide * (2 * dk + 2 * (dk + dv) + 4 * dk) + inverse
            + 2 * wide * C},
    }


def read(run):
    ms = gdn_chunk_local_ms.read(run)
    if not ms:
        return None
    layers = run.reference.layer_kinds(run.cfg).count("linear_attention")
    least = layers * sum(
        max(cost["flops"] / run.peaks["flops_per_s"],
            cost["bytes"] / run.peaks["bytes_per_s"])
        for cost in chunk_local_cost(run.cfg, run.mix).values())
    return 100.0 * least * 1e3 / ms
