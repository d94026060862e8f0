"""MoE dispatch: the EP shard_map path against the held-expert path
(subprocess with fake devices), and the router. The held-expert path's
own tests are in ``test_moe_held.py``."""
import dataclasses

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.models.layers import init_params
from repro.models.moe import moe_template, _route
from conftest import run_devices


def _moe_cfg(n_experts=8, top_k=2, d=32, ff=16):
    cfg = reduced(get_config("deepseek-v3-671b"), mtp_depth=0)
    return dataclasses.replace(
        cfg, d_model=d,
        moe=dataclasses.replace(cfg.moe, n_experts=n_experts, top_k=top_k,
                                expert_d_ff=ff, n_shared_experts=0,
                                n_dense_layers=0))


def _params(cfg, seed=0):
    return init_params(moe_template(cfg), jax.random.PRNGKey(seed))


def test_ep_shard_map_matches_dense():
    """EP all_to_all path == the held-expert path (every expert held) on
    an 8-device mesh."""
    code = """
import dataclasses, jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.models.layers import init_params
from repro.models.moe import moe_ep_shard_map, moe_held, moe_template
import repro.models.moe as moe_mod
from repro.models.sharding import MeshCtx
from repro.launch.mesh import make_mesh

cfg = reduced(get_config("deepseek-v3-671b"), mtp_depth=0)
cfg = dataclasses.replace(cfg, d_model=32,
    moe=dataclasses.replace(cfg.moe, n_experts=8, top_k=2, expert_d_ff=16,
                            capacity_factor=16.0, n_shared_experts=0,
                            n_dense_layers=0))
p = init_params(moe_template(cfg), jax.random.PRNGKey(0))
mesh = make_mesh(2, 4)
ctx = MeshCtx(mesh=mesh, batch_axes=("data",))
x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
moe_mod.EP_CHUNK_TOKENS = 4   # force strip-mining through multiple chunks
with mesh:
    y_ep, aux_ep = jax.jit(lambda xx: moe_ep_shard_map(cfg, p, xx, ctx))(x)
y_dense, aux_d, _ = moe_held(cfg, p, x)
err = np.abs(np.asarray(y_ep) - np.asarray(y_dense)).max()
scale = np.abs(np.asarray(y_dense)).max()
assert err < 1e-3 * scale + 1e-4, (err, scale)
assert abs(float(aux_ep) - float(aux_d)) < 0.3, (float(aux_ep), float(aux_d))
print("EP_OK")
"""
    assert "EP_OK" in run_devices(code, n_devices=8)


def test_router_gates_normalized():
    cfg = _moe_cfg()
    p = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (16, cfg.d_model))
    gates, ids, aux = _route(x, p["router"], cfg.moe.top_k, cfg.moe.n_experts)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    assert ids.shape == (16, cfg.moe.top_k)
    assert float(aux) >= 1.0 - 1e-3  # >= 1 at perfect balance


def test_ep_padded_experts_matches_dense():
    """EP with a 40->48 padded expert table == held path on 40 experts
    (granite hillclimb path; dead experts must contribute nothing)."""
    code = """
import dataclasses, jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.models.layers import init_params
from repro.models.moe import moe_ep_shard_map, moe_held, moe_template
import repro.models.moe as moe_mod
from repro.models.sharding import MeshCtx
from repro.launch.mesh import make_mesh

cfg = reduced(get_config("granite-moe-3b-a800m"))
cfg = dataclasses.replace(cfg, d_model=32,
    moe=dataclasses.replace(cfg.moe, n_experts=5, top_k=2, expert_d_ff=16,
                            capacity_factor=16.0, pad_experts_to=8))
p = init_params(moe_template(cfg), jax.random.PRNGKey(0))
assert p["w_gate"].shape[0] == 8
mesh = make_mesh(2, 4)
ctx = MeshCtx(mesh=mesh, batch_axes=("data",))
x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
moe_mod.EP_CHUNK_TOKENS = 8
with mesh:
    y_ep, _ = jax.jit(lambda xx: moe_ep_shard_map(cfg, p, xx, ctx))(x)
y_dense, _, _ = moe_held(cfg, p, x)
err = np.abs(np.asarray(y_ep) - np.asarray(y_dense)).max()
scale = np.abs(np.asarray(y_dense)).max()
assert err < 1e-3 * scale + 1e-4, (err, scale)
print("EP_PAD_OK")
"""
    assert "EP_PAD_OK" in run_devices(code, n_devices=8)
