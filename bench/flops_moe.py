"""Operations and bytes from shapes for training cells of mixture-of-experts
models with sliding-window and full attention layers (the configurations
of ``refs/moe_window.py``), for the per-layer metrics that divide a least
time by a measured one.

As in ``flops.py``: counts are fixed by the model and the chip's share of
it (live heads, the experts held here, the sliced vocabulary), not by how
the program computes it; a multiply-add is 2 operations; recomputation is
not counted.
"""
from __future__ import annotations

BF16 = 2


def _dims(arch: dict):
    d, h = arch["d_model"], arch["n_heads"]
    moe = arch["moe"]
    return dict(d=d, h=h, hkv=arch["n_kv_heads"],
                hd=arch.get("head_dim") or d // h, v=arch["vocab_size"],
                L=arch["n_layers"], held=moe["n_experts"],
                E=moe.get("n_routed_experts") or moe["n_experts"],
                k=moe["top_k"], f=moe["expert_d_ff"],
                kinds=tuple(arch.get("layer_kinds") or ("full",)),
                window=arch.get("sliding_window", 0))


def visible_keys(kind: str, seq: int, window: int) -> float:
    """Keys a query sees, averaged over the positions of a ``seq``-token
    row: t + 1 at position t on a full layer, min(t + 1, window) on a
    sliding one."""
    if kind != "sliding" or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def train_flops_per_token(arch: dict, seq: int) -> float:
    """Forward and backward operations per trained token: 6 per weight
    that a token meets in a matrix product (the q/k/v/o projections, the
    whole router, top_k x held / width expected expert pairs of three
    matrices each, the sliced head) plus three times the attention
    forward, over the band on sliding layers and the causal prefix on
    full ones."""
    m = _dims(arch)
    d, hd = m["d"], m["hd"]
    proj = 2 * d * m["h"] * hd + 2 * d * m["hkv"] * hd
    experts = m["k"] * m["held"] / m["E"] * 3 * d * m["f"]
    weights = m["L"] * (proj + d * m["E"] + experts) + d * m["v"]
    kinds = m["kinds"]
    attn = sum(4 * m["h"] * hd * visible_keys(kinds[i % len(kinds)], seq,
                                               m["window"])
               for i in range(m["L"]))
    return 6 * weights + 3 * attn


def flash_least_s(kind: str, groups: int, seq: int, head_dim: int,
                  window: int, layer_kinds, peaks: dict,
                  itemsize: int = BF16) -> float:
    """Least time of one flash-attention call over ``groups`` (batch x
    heads) rows of ``seq`` x ``head_dim``, averaged over the period's
    layer kinds (every step calls each layer's kernels equally often).

    ``fwd``: QK^T and PV over the visible pairs; reads Q, K, V, writes O
    and the per-row log-sum-exp. ``bwd``: the dQ and dK/dV kernels
    together: the scores again, dP, dV, dQ, dK (five products over the
    visible pairs); reads Q, K, V, O, dO, lse and delta, writes dQ, dK,
    dV."""
    rows = groups * seq * head_dim * itemsize
    stats = groups * seq * 4
    products, bytes_ = {"fwd": (2, 4 * rows + stats),
                        "bwd": (5, 8 * rows + 2 * stats)}[kind]
    total = 0.0
    for k in layer_kinds:
        pairs = visible_keys(k, seq, window) * seq
        flops = products * 2 * head_dim * pairs * groups
        total += max(flops / peaks["bf16_flops"],
                     bytes_ / peaks["hbm_bytes_per_s"])
    return total / len(layer_kinds)


def expert_gmm_least_s(arch: dict, pairs: float, peaks: dict,
                       itemsize: int = BF16) -> float:
    """Least time of one grouped product of the held experts (a forward
    ``gmm``, or a backward ``gmm`` or ``tgmm``) over ``pairs`` held
    (token, expert) pairs: 2 x pairs x d x f operations, reading or
    writing one pairs x d and one pairs x f operand and one held x d x f
    weight block, at bfloat16."""
    m = _dims(arch)
    flops = 2 * pairs * m["d"] * m["f"]
    bytes_ = itemsize * (pairs * m["d"] + pairs * m["f"] + m["held"] * m["d"]
                         * m["f"])
    return max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"])
