"""Operations and bytes from shapes, for the per-layer metrics that divide
a least time by a measured one.

Counts are fixed by the model (live heads, the work the algorithm needs),
not by how the program computes it, so a faster program raises the share
and a share above 100% means a count is wrong. A multiply-add is 2
operations.
"""
from __future__ import annotations

BF16 = 2


def _dims(arch: dict):
    d, h = arch["d_model"], arch["n_heads"]
    hd = arch.get("head_dim") or d // h
    return d, h, arch["n_kv_heads"], hd, arch["d_ff"], arch["vocab_size"], \
        arch["n_layers"]


def matmul_params(arch: dict) -> int:
    """Weights that take part in a matrix product for every token: the
    live q/k/v/o projections, the MLP, and the unembedding (the
    embedding's row gather is no product)."""
    d, h, hkv, hd, f, v, L = _dims(arch)
    gated = arch.get("activation", "silu") == "silu"
    per_layer = 2 * d * h * hd + 2 * d * hkv * hd + (3 if gated else 2) * d * f
    return L * per_layer + d * v


def weight_bytes_per_token_step(arch: dict, n_tokens: int) -> int:
    """Bytes of weights one decode step must read at bfloat16: every
    matrix-product weight, the norm gains, and the embedding rows of the
    step's tokens (an untied embedding is otherwise not read)."""
    d, L = arch["d_model"], arch["n_layers"]
    gathered = 0 if arch.get("tie_embeddings") else n_tokens * d
    return BF16 * (matmul_params(arch) + (2 * L + 1) * d + gathered)


def decode_step_least_s(arch: dict, lengths, peaks: dict) -> float:
    """Least time of one decode step whose active slots hold ``lengths``
    tokens each (the new token included): the larger of operations over
    peak and bytes over bandwidth. Bytes are the weights and each slot's
    K/V rows, all at bfloat16."""
    d, h, hkv, hd, f, v, L = _dims(arch)
    n = len(lengths)
    if n == 0:
        return 0.0
    total_len = sum(int(x) for x in lengths)
    flops = 2 * matmul_params(arch) * n + L * 4 * h * hd * total_len
    kv_bytes = L * 2 * hkv * hd * BF16 * total_len
    bytes_ = weight_bytes_per_token_step(arch, n) + kv_bytes
    return max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"])


def attention_flops_per_token(arch: dict, seq: int) -> float:
    """Causal self-attention forward operations per token, averaged over
    the positions of a ``seq``-token row: QK^T and PV over the (t+1)
    visible keys of position t."""
    d, h, hkv, hd, f, v, L = _dims(arch)
    return L * 4 * h * hd * (seq + 1) / 2


def train_flops_per_token(arch: dict, seq: int) -> float:
    """Forward and backward operations per trained token: 6 per
    matrix-product weight plus three times the causal attention forward.
    Recomputation is not counted."""
    return 6 * matmul_params(arch) + 3 * attention_flops_per_token(arch, seq)


def flash_least_s(kind: str, groups: int, seq: int, head_dim: int,
                  peaks: dict, itemsize: int = BF16) -> float:
    """Least time of one causal flash-attention call over ``groups``
    (batch x live heads) rows of ``seq`` x ``head_dim``.

    ``fwd``: QK^T and PV over the causal half; reads Q, K, V, writes O
    and the per-row log-sum-exp. ``bwd``: the two backward kernels
    together: the scores once more, dP = dO V^T, dV, dQ, dK (five
    products over the causal half); reads Q, K, V, O, dO, lse and delta,
    writes dQ, dK, dV.
    """
    pairs = seq * (seq + 1) / 2
    rows = groups * seq * head_dim * itemsize
    stats = groups * seq * 4
    if kind == "fwd":
        flops = 2 * 2 * head_dim * pairs * groups
        bytes_ = 4 * rows + stats
    elif kind == "bwd":
        flops = 5 * 2 * head_dim * pairs * groups
        bytes_ = 8 * rows + 2 * stats
    else:
        raise ValueError(kind)
    return max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"])
