"""Plain reference for mixture-of-experts decoders with sliding-window and
full attention layers (Mellum2), and their weights.

Written from the configuration's equations, not from the program's code.
Per layer: an RMSNorm; grouped-query attention with rotary embedding over
the whole head and a softmax over the keys each query sees, which is the
causal prefix on a "full" layer and the band ``0 <= q - k < window`` on a
"sliding" one; an RMSNorm; and the experts held here. The layers repeat
the period ``layer_kinds``. A full layer's rotary embedding is YaRN
(arXiv:2309.00071), computed below from the formula as Hugging Face's
``_compute_yarn_parameters`` states it: frequencies ramp from divided by
``factor`` to unchanged over the correction range that ``beta_fast`` and
``beta_slow`` rotations give at ``original_max_position_embeddings``
(floored and ceiled), and cos and sin are multiplied by
``attention_factor``. A sliding layer's is plain RoPE at ``rope_theta``.

The experts: a softmax router over all ``n_routed_experts``, in float32;
the top ``top_k`` with gates renormalized over them; of those, the
experts this chip holds, ``[0, n_experts)``, each a gated SiLU MLP
computed for every token and weighted by the token's gate for it (0 where
the token did not choose it). What experts held elsewhere add is left out
here as in the program: that is this chip's share of the layer. The loss
is the mean next-token cross-entropy plus 0.01 times the Switch
load-balancing term of every layer, ``E * sum_e f_e P_e`` over the whole
router (f: share of the token's choices, P: mean router probability).

Everything is float32 with every matrix product at ``Precision.HIGHEST``;
attention runs in query blocks so that an 8192-token row fits the chip.
Departures, as in the configuration's ``departures``: none from the
published equations beyond the cut (held experts, sliced vocabulary,
depth); the layer's input to the router is the normed hidden state as
the published model has it.

``quant=True`` computes every matrix product, the router's included, from
operands rounded to int8 with one symmetric scale per tensor (forward and
backward): the control, one precision step below the configuration's
bfloat16. The AdamW step and the int8 products are the dense reference's.

Weights are made here, from the seed, in the program's parameter layout
(``param_shapes``), on the device in one jitted call.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_ref_moe_window_dense",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "dense.py"))
dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dense)

dot, rms_norm, _nest = dense.dot, dense.rms_norm, dense._nest
adamw_init, adamw_step = dense.adamw_init, dense.adamw_step
AUX_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# Layout and weights
# ---------------------------------------------------------------------------


def dims(arch: dict) -> dict:
    d, h = arch["d_model"], arch["n_heads"]
    moe = arch["moe"]
    return dict(d=d, h=h, hkv=arch["n_kv_heads"],
                hd=arch.get("head_dim") or d // h, v=arch["vocab_size"],
                L=arch["n_layers"], eps=float(arch.get("norm_eps", 1e-5)),
                theta=float(arch.get("rope_theta", 10000.0)),
                held=moe["n_experts"],
                E=moe.get("n_routed_experts") or moe["n_experts"],
                k=moe["top_k"], f=moe["expert_d_ff"],
                kinds=tuple(arch.get("layer_kinds") or ("full",)),
                window=arch.get("sliding_window", 0), yarn=arch.get("yarn"))


def param_shapes(arch: dict) -> dict:
    """``{path: (shape, std)}``; std 0 means ones (a norm gain).
    Projections take std 1/sqrt(contracted size)."""
    m = dims(arch)
    d, h, hkv, hd, v, L = (m[k] for k in ("d", "h", "hkv", "hd", "v", "L"))
    held, E, f = m["held"], m["E"], m["f"]
    return {
        "embed": ((v, d), 0.02),
        "unembed": ((d, v), 0.02),
        "final_norm": ((d,), 0.0),
        "layers/ln1": ((L, d), 0.0),
        "layers/ln2": ((L, d), 0.0),
        "layers/attn/wq": ((L, d, h, hd), d ** -0.5),
        "layers/attn/wk": ((L, d, hkv, hd), d ** -0.5),
        "layers/attn/wv": ((L, d, hkv, hd), d ** -0.5),
        "layers/attn/wo": ((L, h, hd, d), (h * hd) ** -0.5),
        "layers/moe/router": ((L, d, E), d ** -0.5),
        "layers/moe/w_gate": ((L, held, d, f), d ** -0.5),
        "layers/moe/w_up": ((L, held, d, f), d ** -0.5),
        "layers/moe/w_down": ((L, held, f, d), f ** -0.5),
    }


def make_params(arch: dict, seed: int):
    """The whole parameter tree, float32, on the default device, from one
    jitted call."""
    shapes = param_shapes(arch)

    def build(key):
        flat = {}
        for i, (path, (shape, std)) in enumerate(sorted(shapes.items())):
            if std == 0.0:
                flat[path] = jnp.ones(shape, jnp.float32)
            else:
                flat[path] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return _nest(flat)

    return jax.jit(build)(jax.random.PRNGKey(seed))


def check_layout(arch: dict, program_shapes: dict) -> Optional[str]:
    """None when the program's parameter tree has exactly the shapes this
    reference assumes; otherwise what differs."""
    want = {k: tuple(s) for k, (s, _) in param_shapes(arch).items()}
    got = {k: tuple(s) for k, s in program_shapes.items()}
    if want != got:
        return (f"layout differs: reference-only {sorted(set(want) - set(got))}"
                f", program-only {sorted(set(got) - set(want))}, shapes "
                f"{[(k, want[k], got[k]) for k in want if k in got and want[k] != got[k]]}")
    return None


# ---------------------------------------------------------------------------
# Rotary embedding: plain, and YaRN from its formula
# ---------------------------------------------------------------------------


def inv_freq(hd: int, theta: float, yarn: Optional[dict]) -> np.ndarray:
    """Inverse frequencies of the hd/2 rotated pairs (float64)."""
    base = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    extra = 1.0 / base
    if not yarn:
        return extra
    inter = 1.0 / (yarn["factor"] * base)

    def correction_dim(rotations):
        return (hd * math.log(yarn["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi))) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extra_share = 1.0 - ramp
    return inter * (1.0 - extra_share) + extra * extra_share


def rope(x, positions, freqs, scale):
    """x (B,S,H,D): rotate the two halves of each head by position; cos
    and sin times ``scale``."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _attention(q, k, v, hkv, quant, q_block, window):
    """Softmax attention over the keys each query sees: k <= q, and with
    a ``window`` also q - k < window; query rows in blocks of
    ``q_block``, each recomputed in the backward pass. q (B,S,H,D), k/v
    (B,S,Hkv,D)."""
    b, s, h, d = q.shape
    rep = h // hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    nb = s // q_block
    qb = q.reshape(b, nb, q_block, h, d).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def block(args):
        i, qi = args
        sc = dot("bqhd,bkhd->bhqk", qi, k, quant) / math.sqrt(d)
        rel = (i * q_block + jnp.arange(q_block))[:, None] \
            - jnp.arange(s)[None, :]
        mask = rel >= 0
        if window:
            mask = mask & (rel < window)
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        return dot("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v, quant)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


def _experts(m, p, x, quant):
    """The held experts' part of the layer for x (T,d) -> (T,d), and the
    layer's load-balancing term over the whole router."""
    probs = jax.nn.softmax(dot("td,de->te", x, p["router"], quant), axis=-1)
    gates, ids = jax.lax.top_k(probs, m["k"])
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(ids, m["E"], dtype=jnp.float32)      # (T,k,E)
    f = jnp.mean(jnp.sum(chosen, axis=1), axis=0) / m["k"]
    aux = m["E"] * jnp.sum(f * jnp.mean(probs, axis=0))
    weight = jnp.einsum("tk,tke->te", gates, chosen)            # (T,E)

    y = jnp.zeros_like(x)
    for e in range(m["held"]):
        g = dot("td,df->tf", x, p["w_gate"][e], quant)
        u = dot("td,df->tf", x, p["w_up"][e], quant)
        out = dot("tf,fd->td", g * jax.nn.sigmoid(g) * u, p["w_down"][e],
                  quant)
        y = y + weight[:, e:e + 1] * out
    return y, aux


def _layer(m, kind, quant, q_block, x, p):
    b, s, d = x.shape
    positions = jnp.arange(s)
    sliding = kind == "sliding"
    freqs = inv_freq(m["hd"], m["theta"], None if sliding else m["yarn"])
    scale = 1.0 if sliding or not m["yarn"] else \
        m["yarn"]["attention_factor"]
    h = rms_norm(x, p["ln1"], m["eps"])
    q = rope(dot("bsd,dhk->bshk", h, p["attn"]["wq"], quant), positions,
             freqs, scale)
    k = rope(dot("bsd,dhk->bshk", h, p["attn"]["wk"], quant), positions,
             freqs, scale)
    v = dot("bsd,dhk->bshk", h, p["attn"]["wv"], quant)
    att = _attention(q, k, v, m["hkv"], quant, q_block,
                     m["window"] if sliding else 0)
    x = x + dot("bshk,hkd->bsd", att, p["attn"]["wo"], quant)
    y, aux = _experts(m, p["moe"], rms_norm(x, p["ln2"], m["eps"])
                      .reshape(b * s, d), quant)
    return x + y.reshape(b, s, d), aux


def _trunk(arch: dict, params, tokens, quant: bool, q_block: int):
    """Final-normed hidden states (B,S,d) and the summed load-balancing
    terms; each layer recomputed in the backward pass."""
    m = dims(arch)
    x = params["embed"][tokens]
    q_block = math.gcd(tokens.shape[1], q_block)
    aux = 0.0
    for i in range(m["L"]):
        kind = m["kinds"][i % len(m["kinds"])]
        layer = jax.checkpoint(functools.partial(_layer, m, kind, quant,
                                                 q_block))
        x, a = layer(x, jax.tree_util.tree_map(lambda w: w[i],
                                               params["layers"]))
        aux = aux + a
    return rms_norm(x, params["final_norm"], m["eps"]), aux


def hidden(arch: dict, params, tokens, *, quant: bool = False,
           q_block: int = 256):
    """Final-normed hidden states (B,S,d) for tokens (B,S)."""
    return _trunk(arch, params, tokens, quant, q_block)[0]


def logits(arch: dict, params, h, *, quant: bool = False):
    return dot("...d,dv->...v", h, params["unembed"], quant)


def loss(arch: dict, params, tokens, labels, *, quant: bool = False,
         chunk: int = 1024):
    """Mean next-token cross-entropy over every position plus 0.01 x the
    layers' load-balancing terms; logits made ``chunk`` positions at a
    time and recomputed in the backward pass."""
    h, aux = _trunk(arch, params, tokens, quant, 256)
    b, s, d = h.shape
    chunk = math.gcd(s, chunk)
    hs = h.reshape(b, s // chunk, chunk, d).transpose(1, 0, 2, 3)
    ys = labels.reshape(b, s // chunk, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def part(args):
        hc, yc = args
        lg = logits(arch, params, hc, quant=quant)
        gold = jnp.take_along_axis(lg, yc[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    ce = jnp.sum(jax.lax.map(part, (hs, ys))) / (b * s)
    return ce + AUX_WEIGHT * aux


def loss_and_grad(arch: dict, params, tokens, labels, *, quant=False):
    """Loss and gradient of the batch mean."""
    return _loss_and_grad_fn(json.dumps(arch, sort_keys=True), quant)(
        params, jnp.asarray(tokens), jnp.asarray(labels))


@functools.lru_cache(maxsize=None)
def _loss_and_grad_fn(arch_key: str, quant: bool):
    arch = json.loads(arch_key)
    return jax.jit(jax.value_and_grad(
        lambda p, t, y: loss(arch, p, t, y, quant=quant)))
