"""Plain reference for the dense decoder configurations, and their weights.

Written from the configuration's equations, not from the program's code:
token embedding; per layer an RMSNorm, grouped-query attention with
rotary embedding over the whole head and a causal softmax, and a gated
SiLU or tanh-GELU MLP, either after the attention (sequential block) or
beside it on the same normed input (parallel block); a final RMSNorm and
the unembedding, tied to the embedding or not. Everything is float32
with every matrix product at ``Precision.HIGHEST``.

The program stores query heads padded to ``pad_heads_to`` with dead heads
whose outputs it masks. The reference computes only the live heads: in
the padded layout, kv group ``g`` holds its live heads first
(``g * Hp/Hkv + r`` for ``r < H/Hkv``).

``quant=True`` computes every matrix product from operands rounded to
int8 with one symmetric scale per tensor (forward and backward): the
control, one precision step below the bfloat16 compute the
configurations state.

Weights are made here, from the seed, in the program's parameter layout
(``param_shapes``), on the device in one jitted call.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Layout and weights
# ---------------------------------------------------------------------------


def dims(arch: dict) -> dict:
    d = arch["d_model"]
    h = arch["n_heads"]
    hd = arch.get("head_dim") or d // h
    return dict(d=d, h=h, hp=max(arch.get("pad_heads_to", 0), h),
                hkv=arch["n_kv_heads"], hd=hd, f=arch["d_ff"],
                v=arch["vocab_size"], L=arch["n_layers"],
                gated=arch.get("activation", "silu") == "silu",
                tied=bool(arch.get("tie_embeddings", False)),
                parallel=bool(arch.get("parallel_block", False)),
                eps=float(arch.get("norm_eps", 1e-5)),
                theta=float(arch.get("rope_theta", 10000.0)))


def param_shapes(arch: dict) -> dict:
    """``{path: (shape, std)}``; std 0 means ones (a norm gain).
    Projections take std 1/sqrt(contracted size) over the live model."""
    m = dims(arch)
    d, hp, hkv, hd, f, v, L = (m[k] for k in
                               ("d", "hp", "hkv", "hd", "f", "v", "L"))
    out = {
        "embed": ((v, d), 0.02),
        "final_norm": ((d,), 0.0),
        "layers/ln1": ((L, d), 0.0),
        "layers/ln2": ((L, d), 0.0),
        "layers/attn/wq": ((L, d, hp, hd), d ** -0.5),
        "layers/attn/wk": ((L, d, hkv, hd), d ** -0.5),
        "layers/attn/wv": ((L, d, hkv, hd), d ** -0.5),
        "layers/attn/wo": ((L, hp, hd, d), (m["h"] * hd) ** -0.5),
        "layers/mlp/w_up": ((L, d, f), d ** -0.5),
        "layers/mlp/w_down": ((L, f, d), f ** -0.5),
    }
    if m["gated"]:
        out["layers/mlp/w_gate"] = ((L, d, f), d ** -0.5)
    if not m["tied"]:
        out["unembed"] = ((d, v), 0.02)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = leaf
    return tree


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


def live_heads(arch: dict) -> np.ndarray:
    m = dims(arch)
    per_pad, per_live = m["hp"] // m["hkv"], m["h"] // m["hkv"]
    return np.array([g * per_pad + r for g in range(m["hkv"])
                     for r in range(per_live)], np.int32)


# ---------------------------------------------------------------------------
# Matrix products: exact, or from int8-rounded operands (the control)
# ---------------------------------------------------------------------------


def _int8_round(x):
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _exact(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _make_qdot(spec):
    @jax.custom_vjp
    def qdot(a, b):
        return _exact(spec, _int8_round(a), _int8_round(b))

    def fwd(a, b):
        qa, qb = _int8_round(a), _int8_round(b)
        return _exact(spec, qa, qb), (qa, qb)

    def bwd(res, g):
        qa, qb = res
        _, vjp = jax.vjp(lambda x, y: _exact(spec, x, y), qa, qb)
        return vjp(_int8_round(g))

    qdot.defvjp(fwd, bwd)
    return qdot


_QDOTS: dict = {}


def dot(spec: str, a, b, quant: bool):
    if not quant:
        return _exact(spec, a, b)
    if spec not in _QDOTS:
        _QDOTS[spec] = _make_qdot(spec)
    return _QDOTS[spec](a, b)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, positions, theta):
    """x (B,S,H,D): rotate the two halves of each head by position."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, hkv, quant, q_block, remat):
    """Causal softmax attention, query rows in blocks of ``q_block``
    (each block recomputed in the backward pass with ``remat``).
    q (B,S,H,D), k/v (B,S,Hkv,D)."""
    b, s, h, d = q.shape
    rep = h // hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    nb = s // q_block
    qb = q.reshape(b, nb, q_block, h, d).transpose(1, 0, 2, 3, 4)

    def block(args):
        i, qi = args
        sc = dot("bqhd,bkhd->bhqk", qi, k, quant) / math.sqrt(d)
        qpos = i * q_block + jnp.arange(q_block)
        mask = jnp.arange(s)[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return dot("bhqk,bkhd->bqhd", p, v, quant)

    if remat:
        block = jax.checkpoint(block)
    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


def _layer(m, live, quant, q_block, remat, x, p):
    positions = jnp.arange(x.shape[1])
    h = rms_norm(x, p["ln1"], m["eps"])
    wq = p["attn"]["wq"][:, live]
    wo = p["attn"]["wo"][live]
    q = rope(dot("bsd,dhk->bshk", h, wq, quant), positions, m["theta"])
    k = rope(dot("bsd,dhk->bshk", h, p["attn"]["wk"], quant), positions,
             m["theta"])
    v = dot("bsd,dhk->bshk", h, p["attn"]["wv"], quant)
    a = dot("bshk,hkd->bsd",
            _attention(q, k, v, m["hkv"], quant, q_block, remat), wo, quant)

    def mlp(u):
        up = dot("bsd,df->bsf", u, p["mlp"]["w_up"], quant)
        if m["gated"]:
            g = dot("bsd,df->bsf", u, p["mlp"]["w_gate"], quant)
            act = g * jax.nn.sigmoid(g) * up
        else:
            act = gelu_tanh(up)
        return dot("bsf,fd->bsd", act, p["mlp"]["w_down"], quant)

    if m["parallel"]:
        return x + a + mlp(h)
    x = x + a
    return x + mlp(rms_norm(x, p["ln2"], m["eps"]))


def hidden(arch: dict, params, tokens, *, quant: bool = False,
           q_block: int = 512, remat: bool = False):
    """Final-normed hidden states (B,S,d) for tokens (B,S)."""
    m = dims(arch)
    live = live_heads(arch)
    x = params["embed"][tokens]
    s = tokens.shape[1]
    q_block = math.gcd(s, q_block)
    layer = lambda x, p: (  # noqa: E731
        _layer(m, live, quant, q_block, remat, x, p), None)
    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["final_norm"], m["eps"])


def unembed(arch: dict, params):
    return params["embed"].T if dims(arch)["tied"] else params["unembed"]


def logits(arch: dict, params, h, *, quant: bool = False):
    return dot("...d,dv->...v", h, unembed(arch, params), quant)


def loss(arch: dict, params, tokens, labels, *, quant: bool = False,
         chunk: int = 1024):
    """Mean next-token cross-entropy over every position; the logits are
    made ``chunk`` positions at a time and recomputed in the backward pass,
    so a 4096-token batch's vocabulary rows never all exist at once."""
    h = hidden(arch, params, tokens, quant=quant, remat=True)
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

    return jnp.sum(jax.lax.map(part, (hs, ys))) / (b * s)


def loss_and_grad(arch: dict, params, tokens, labels, *, quant=False):
    """Loss and gradient of the batch mean."""
    return _loss_and_grad_fn(json.dumps(arch, sort_keys=True), quant)(
        params, jnp.asarray(tokens), jnp.asarray(labels))


@functools.lru_cache(maxsize=None)
def _loss_and_grad_fn(arch_key: str, quant: bool):
    arch = json.loads(arch_key)
    return jax.jit(jax.value_and_grad(
        lambda p, t, y: loss(arch, p, t, y, quant=quant)))


# ---------------------------------------------------------------------------
# AdamW (the optimizer settings the cell states)
# ---------------------------------------------------------------------------


def adamw_init(params):
    z = lambda p: jnp.zeros_like(p)  # noqa: E731
    return {"m": jax.tree_util.tree_map(z, params),
            "v": jax.tree_util.tree_map(z, params), "step": 0}


def adamw_lr(opt: dict, step: int) -> float:
    warm, decay = opt["warmup_steps"], opt["decay_steps"]
    if step < warm:
        return opt["peak_lr"] * step / max(warm, 1)
    prog = min(max((step - warm) / max(decay - warm, 1), 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return opt["peak_lr"] * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * prog)))


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam_leaf(p, g, m, v, clip, hyper):
    lr, b1, b2, bc1, bc2, eps, wd = (hyper[i] for i in range(7))
    g = g * clip
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if p.ndim >= 2:
        delta = delta + wd * p
    return p - lr * delta, m, v, jnp.sqrt(jnp.sum(g * g))


def adamw_step(opt: dict, params, grads, state):
    """One AdamW step: global-norm clipping, bias-corrected moments, and
    decoupled weight decay on every leaf of two or more dimensions. The
    parameters and moments passed in are donated, one leaf at a time, so
    that the step needs no second copy of the state. Returns (params,
    state, norm of every leaf's clipped gradient)."""
    step = state["step"] + 1
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = opt["b1"], opt["b2"]
    hyper = jnp.asarray([adamw_lr(opt, step), b1, b2, 1 - b1 ** step,
                         1 - b2 ** step, opt["eps"], opt["weight_decay"]],
                        jnp.float32)
    tree = jax.tree_util.tree_structure(params)
    out = []
    for p, g, m, v in zip(jax.tree_util.tree_leaves(params), leaves,
                          jax.tree_util.tree_leaves(state["m"]),
                          jax.tree_util.tree_leaves(state["v"])):
        out.append(_adam_leaf(p, g, m, v, clip, hyper))
    unf = lambda i: jax.tree_util.tree_unflatten(tree, [o[i] for o in out])  # noqa: E731
    return unf(0), {"m": unf(1), "v": unf(2), "step": step}, \
        np.asarray(jnp.stack([o[3] for o in out]))


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
