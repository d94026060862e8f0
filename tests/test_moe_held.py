"""The held-expert MoE layer (``models.moe.moe_held``): a chip that holds
experts [0, n_experts) of a wider router computes their part of the layer
through grouped matmuls, drops no token however the router skews, and the
shares of every rank of an expert-parallel deployment add up to the
uncut layer; on a mesh (``moe_held_mesh``, a shard_map) it gives the
one-device layer's values and gradients. Also: nested config groups given
as dicts, and the layer's counters in the train step's metrics and on the
program's spans."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_devices
from repro.configs import ARCH_NAMES, get_config
from repro.configs.base import ArchConfig, MoEConfig
from repro.models.layers import init_params
from repro.models.moe import COUNTERS, _route, moe_held, moe_template

D, FF = 32, 16


def _cfg(held, width, top_k):
    return ArchConfig(name="t", family="moe", n_layers=1, d_model=D,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                      compute_dtype="float32",
                      moe=MoEConfig(n_experts=held, n_routed_experts=width,
                                    top_k=top_k, expert_d_ff=FF))


def _params(cfg, seed=0):
    return init_params(moe_template(cfg), jax.random.PRNGKey(seed))


def _oracle(cfg, p, x):
    """Each token's held choices, one expert at a time, in float64."""
    m = cfg.moe
    gates, ids, _ = _route(x, p["router"], m.top_k, m.router_width)
    x, gates, ids = (np.asarray(a, np.float64) if a.dtype != jnp.int32
                     else np.asarray(a) for a in (x, gates, ids))
    w = {k: np.asarray(p[k], np.float64) for k in ("w_gate", "w_up",
                                                   "w_down")}
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        for j in range(m.top_k):
            e = int(ids[t, j])
            if e < m.n_experts:
                g = x[t] @ w["w_gate"][e]
                h = g / (1 + np.exp(-g)) * (x[t] @ w["w_up"][e])
                y[t] += gates[t, j] * (h @ w["w_down"][e])
    return y


@pytest.mark.parametrize("held,width,top_k", [
    (8, 0, 2),        # every expert held, the router no wider
    (4, 16, 4),       # one rank's share of a wider router
    (2, 16, 8),       # fewer held experts than choices per token
])
def test_held_layer_matches_per_token_oracle(held, width, top_k):
    cfg = _cfg(held, width, top_k)
    p = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (48, D))
    y, aux, counters = moe_held(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y), _oracle(cfg, p, x),
                               rtol=2e-4, atol=2e-5)
    _, ids, want_aux = _route(x, p["router"], top_k, cfg.moe.router_width)
    assert float(aux) == pytest.approx(float(want_aux), rel=1e-6)
    assert float(counters["moe_held_pairs"]) == int(jnp.sum(ids < held))
    assert float(counters["moe_dropped"]) == 0.0


def test_rank_shares_sum_to_the_uncut_layer():
    """Eight ranks of 2 experts each over a 16-wide router: rank r holds
    experts 2r, 2r+1, which it numbers from 0 (its router's columns
    reordered to put them first, which changes no routing). The eight
    partial results add up to the layer with every expert held, and
    every rank reads the same load-balancing term."""
    width, per_rank, top_k = 16, 2, 4
    whole = _cfg(width, 0, top_k)
    p = _params(whole, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (40, D))
    want, want_aux, _ = moe_held(whole, p, x)
    share = _cfg(per_rank, width, top_k)
    total = jnp.zeros_like(want)
    for r in range(width // per_rank):
        mine = np.arange(r * per_rank, (r + 1) * per_rank)
        perm = np.concatenate([mine, np.setdiff1d(np.arange(width), mine)])
        pr = {"router": p["router"][:, perm],
              **{k: p[k][mine] for k in ("w_gate", "w_up", "w_down")}}
        y, aux, _ = moe_held(share, pr, x)
        total = total + y
        assert float(aux) == pytest.approx(float(want_aux), rel=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("held,top_k", [(4, 2), (8, 8)])
def test_no_token_dropped_under_skew(held, top_k):
    """The router sends every token's choices to held experts, all of
    them to expert 0 first: the busiest group holds every token, and
    with top_k == held every one of the T*k pairs is held, the buffer
    full. Nothing is dropped and the layer is exact."""
    cfg = _cfg(held, 16, top_k)
    p = _params(cfg, seed=5)
    bias = np.zeros((D, 16), np.float32)
    bias[0, :held] = np.linspace(40.0, 30.0, held)   # feature 0 picks them
    p["router"] = p["router"] * 0.01 + bias
    x = jax.random.normal(jax.random.PRNGKey(6), (64, D))
    x = x.at[:, 0].set(1.0)
    y, _, counters = moe_held(cfg, p, x)
    _, ids, _ = _route(x, p["router"], top_k, 16)
    assert bool(jnp.all(ids[:, 0] == 0)) and bool(jnp.all(ids < held))
    assert float(counters["moe_held_pairs"]) == 64 * top_k
    assert float(counters["moe_dropped"]) == 0.0
    assert float(counters["moe_load_max_over_mean"]) > 1.0 \
        or top_k == held
    np.testing.assert_allclose(np.asarray(y), _oracle(cfg, p, x),
                               rtol=2e-4, atol=2e-5)


def test_dropped_counts_tokens_whose_rows_came_back_empty(monkeypatch):
    """``moe_dropped`` reads what a lost row would cost: with grouped
    products that return nothing, every token with a held choice counts."""
    import repro.models.moe as moe_mod
    cfg = _cfg(4, 16, 4)
    p = _params(cfg, seed=9)
    x = jax.random.normal(jax.random.PRNGKey(10), (32, D))
    monkeypatch.setattr(moe_mod, "_grouped",
                        lambda lhs, rhs, *a: jnp.zeros(
                            (lhs.shape[0], rhs.shape[-1]), lhs.dtype))
    _, _, counters = moe_held(cfg, p, x)
    _, ids, _ = _route(x, p["router"], 4, 16)
    assert float(counters["moe_dropped"]) == int(jnp.sum(
        jnp.any(ids < 4, axis=-1))) > 0


_MESH_CHECK = """
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ArchConfig, MoEConfig
from repro.launch.mesh import make_mesh
from repro.models.layers import init_params
from repro.models.moe import (held_lane_split, moe_held, moe_held_mesh,
                              moe_template)
from repro.models.sharding import MeshCtx

cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=32, n_heads=4,
                 n_kv_heads=2, d_ff=64, vocab_size=64,
                 compute_dtype="float32", moe=MoEConfig(**{moe}))
mesh = make_mesh(2, 2)
ctx = MeshCtx(mesh=mesh, batch_axes=("data",))
assert held_lane_split(cfg, 2) == {split!r}, held_lane_split(cfg, 2)
p = init_params(moe_template(cfg), jax.random.PRNGKey(0))
for t in (64, 3):      # tokens split over the data axis, and not
    x = jax.random.normal(jax.random.PRNGKey(1), (t, 32))

    def loss(fn):
        def f(p, x):
            y, aux, c = fn(p, x)
            return jnp.sum(y * jnp.cos(x)) + aux, (y, aux, c)
        return jax.value_and_grad(f, (0, 1), has_aux=True)

    with mesh:
        (_, got), g_got = jax.jit(loss(
            lambda p, x: moe_held_mesh(cfg, p, x, ctx)))(p, x)
    (_, want), g_want = loss(lambda p, x: moe_held(cfg, p, x))(p, x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    assert abs(float(got[1]) - float(want[1])) < 1e-5
    assert {{k: float(v) for k, v in got[2].items()}} == \
        {{k: float(v) for k, v in want[2].items()}}
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
print("MESH_OK")
"""


@pytest.mark.parametrize("split,moe", [
    ("experts", dict(n_experts=8, top_k=2, expert_d_ff=FF)),
    ("experts", dict(n_experts=4, n_routed_experts=16, top_k=4,
                     expert_d_ff=FF)),
    ("ffn", dict(n_experts=6, top_k=2, expert_d_ff=FF,
                 expert_parallel=False)),
    (None, dict(n_experts=5, top_k=2, expert_d_ff=FF)),
], ids=["experts", "held-of-16", "ffn", "replicated"])
def test_held_layer_on_a_mesh_matches_one_device(split, moe):
    """On a 2 x 2 mesh the held layer runs as a shard_map, the lanes
    splitting the held experts, their hidden width, or (where neither
    divides) the tokens; values, aux term, counters and gradients match
    the one-device layer."""
    code = _MESH_CHECK.format(moe=moe, split=split)
    assert "MESH_OK" in run_devices(code, n_devices=4)


def test_held_layer_gradients_match_dense_mixture():
    """The grouped products' VJP against the same layer written as a
    dense per-expert mixture."""
    cfg = _cfg(4, 16, 4)
    p = _params(cfg, seed=7)
    x = jax.random.normal(jax.random.PRNGKey(8), (32, D))

    def dense(p, x):
        gates, ids, aux = _route(x, p["router"], 4, 16)
        weight = jnp.einsum("tk,tke->te", gates,
                            jax.nn.one_hot(ids, 16))[:, :4]
        g = jnp.einsum("td,edf->etf", x, p["w_gate"])
        u = jnp.einsum("td,edf->etf", x, p["w_up"])
        out = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, p["w_down"])
        return jnp.einsum("te,etd->td", weight, out), aux

    def loss(fn):
        def f(p, x):
            y, aux = fn(p, x)[:2]
            return jnp.sum(jnp.sin(y)) + aux
        return jax.grad(f, argnums=(0, 1))(p, x)

    with jax.default_matmul_precision("highest"):
        got, want = loss(lambda p, x: moe_held(cfg, p, x)), loss(dense)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("family", ["moe", "dense"])
def test_counters_reach_the_train_step_metrics(family):
    """``Trainer.step_fn`` returns the layer's counters in the step's
    metrics and files the held pairs on an ``obs`` span
    ``train.step.dispatch``; a model without experts files none."""
    from repro import obs
    from repro.data.pipeline import DataConfig
    from repro.optim.adamw import OptConfig
    from repro.train.trainer import Trainer, TrainerConfig
    cfg = dataclasses.replace(_cfg(4, 16, 4), family=family, n_layers=4,
                              layer_kinds=("sliding", "full"),
                              sliding_window=4, head_dim=8)
    trainer = Trainer(cfg, OptConfig(), DataConfig(16, 2, 64),
                      TrainerConfig())
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    obs.reset()
    _, metrics = trainer.step_fn(trainer.init_state(),
                                 {"tokens": toks, "labels": toks})
    filed = obs.spans(name="train.step.dispatch")
    obs.reset()
    if family == "dense":
        assert not set(COUNTERS) & set(metrics) and not filed
        return
    assert set(COUNTERS) <= set(metrics)
    # 4 layers x 32 tokens x 4 choices, a quarter of them held on average
    assert 0 < float(metrics["moe_held_pairs"]) <= 4 * 32 * 4
    assert float(metrics["moe_dropped"]) == 0.0
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    assert len(filed) == 1
    assert float(filed[0].attrs["moe_held_pairs"]) == \
        float(metrics["moe_held_pairs"])


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_registry_configs_survive_asdict_round_trip(name):
    """A config written out as plain data (a JSON file's nested dicts)
    builds the same ArchConfig: ``moe``, ``mla``, ``ssm`` and ``yarn``
    groups come back as their dataclasses."""
    cfg = get_config(name)
    assert ArchConfig(**dataclasses.asdict(cfg)) == cfg


def test_nested_dicts_and_kinds_from_json_data():
    cfg = ArchConfig(name="t", family="moe", n_layers=4, d_model=8,
                     n_heads=2, n_kv_heads=1, d_ff=8, vocab_size=8,
                     moe={"n_experts": 2, "n_routed_experts": 8,
                          "top_k": 2, "expert_d_ff": 4},
                     layer_kinds=["sliding", "full"], sliding_window=4,
                     yarn={"factor": 4.0,
                           "original_max_position_embeddings": 64})
    assert cfg.moe.router_width == 8 and cfg.moe.n_experts == 2
    assert cfg.layer_kinds == ("sliding", "full")
    assert cfg.yarn.factor == 4.0
    hash(cfg)
