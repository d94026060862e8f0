"""Substrate tests: optimizer, data pipeline, checkpoint, fault tolerance,
strip-mining, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import ckpt
from repro.core import stripmine
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.ft.elastic import (HeartbeatTracker, StragglerMonitor,
                              plan_remesh)
from repro.optim import adamw


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_converges_quadratic():
    cfg = adamw.OptConfig(peak_lr=0.1, warmup_steps=5, decay_steps=200,
                          weight_decay=0.0)
    params = {"w": jnp.asarray([4.0, -3.0])}
    state = adamw.init(cfg, params)

    @jax.jit
    def step(params, state):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        return adamw.update(cfg, grads, state, params)

    for _ in range(200):
        params, state, m = step(params, state)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_schedule_shape():
    cfg = adamw.OptConfig(peak_lr=1e-3, warmup_steps=10, decay_steps=100,
                          min_lr_ratio=0.1)
    lrs = [float(adamw.schedule(cfg, jnp.asarray(s))) for s in
           (0, 5, 10, 50, 100, 1000)]
    assert lrs[0] == 0 and lrs[1] == pytest.approx(5e-4)
    assert lrs[2] == pytest.approx(1e-3)
    assert lrs[3] < lrs[2] and lrs[4] == pytest.approx(1e-4, rel=1e-3)


def test_grad_clipping():
    cfg = adamw.OptConfig(clip_norm=1.0)
    params = {"w": jnp.zeros(3)}
    state = adamw.init(cfg, params)
    _, _, m = adamw.update(cfg, {"w": jnp.asarray([100.0, 0, 0])}, state,
                           params)
    assert float(m["grad_norm"]) == pytest.approx(100.0)


def test_moment_dtype_bf16():
    cfg = adamw.OptConfig(moment_dtype="bfloat16")
    params = {"w": jnp.zeros(3)}
    st_ = adamw.init(cfg, params)
    assert st_["m"]["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# strip-mining
# ---------------------------------------------------------------------------


def test_stripmined_grads_equal_full():
    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        l = jnp.mean((pred - batch["y"]) ** 2)
        return l, {"l": l}

    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(4, 2), jnp.float32)}
    batch = {"x": jnp.asarray(rng.randn(8, 4), jnp.float32),
             "y": jnp.asarray(rng.randn(8, 2), jnp.float32)}
    (l1, _), g1 = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
    (l2, _), g2 = stripmine.stripmined_grads(loss_fn, params, batch, 4)
    assert float(jnp.abs(l1 - l2)) < 1e-6
    np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g2["w"]),
                               rtol=1e-5, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(strips=st.sampled_from([1, 2, 4, 8]), seed=st.integers(0, 99))
def test_stripmine_map_property(strips, seed):
    r = np.random.RandomState(seed)
    xs = jnp.asarray(r.randn(8, 3), jnp.float32)
    got = stripmine.stripmine_map(lambda x: x * 2 + 1, xs, 8 // strips)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xs) * 2 + 1,
                               rtol=1e-6)


def test_fuse_steps_equivalence():
    def step(state, batch):
        return state + batch["x"], {"s": state}

    fused = stripmine.fuse_steps(step, 4)
    batches = {"x": jnp.arange(4.0)}
    s1 = jnp.float32(0)
    for i in range(4):
        s1, _ = step(s1, {"x": batches["x"][i]})
    s2, ms = fused(jnp.float32(0), batches)
    assert float(s1) == float(s2)
    assert ms["s"].shape == (4,)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_synthetic_deterministic_and_shaped():
    cfg = DataConfig(seq_len=32, global_batch=4, vocab_size=128, seed=7)
    src = SyntheticLM(cfg)
    b1, b2 = src.batch(3), src.batch(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (4, 32)
    assert b1["labels"].shape == (4, 32)
    # next-token alignment
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    assert b1["tokens"].max() < 128
    b3 = src.batch(4)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_synthetic_has_structure():
    """Bigram stickiness -> repeated-context prediction beats chance."""
    cfg = DataConfig(seq_len=512, global_batch=8, vocab_size=64, seed=0)
    src = SyntheticLM(cfg)
    b = src.batch(0)
    toks, labels = b["tokens"], b["labels"]
    # P(label | token) concentrated: most common successor share > 1/64
    t0 = toks[toks < 64]
    from collections import Counter, defaultdict
    succ = defaultdict(Counter)
    for t, l in zip(toks.ravel(), labels.ravel()):
        succ[int(t)][int(l)] += 1
    shares = [c.most_common(1)[0][1] / sum(c.values())
              for c in succ.values() if sum(c.values()) > 20]
    assert np.mean(shares) > 0.15


def test_prefetcher():
    cfg = DataConfig(seq_len=8, global_batch=2, vocab_size=32)
    pf = Prefetcher(SyntheticLM(cfg), depth=2)
    it = iter(pf)
    s0, b0 = next(it)
    s1, b1 = next(it)
    assert s1 == s0 + 1 and b0["tokens"].shape == (2, 8)
    pf.close()


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def _tree(seed=0):
    r = np.random.RandomState(seed)
    return {"params": {"w": jnp.asarray(r.randn(4, 4), jnp.float32),
                       "b": jnp.asarray(r.randn(4), jnp.float32)},
            "opt": {"step": jnp.asarray(7, jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 10, t)
    step, got = ckpt.restore(str(tmp_path))
    assert step == 10
    np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                  np.asarray(t["params"]["w"]))
    assert int(got["opt"]["step"]) == 7


def test_checkpoint_keep_gc(tmp_path):
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, _tree(), keep=2)
    assert ckpt.latest_steps(str(tmp_path)) == [4, 5]


def test_checkpoint_corruption_detected(tmp_path):
    ckpt.save(str(tmp_path), 1, _tree())
    d = os.path.join(tmp_path, "step_00000001")
    fn = [f for f in os.listdir(d) if f.endswith(".npy")][0]
    arr = np.load(os.path.join(d, fn))
    np.save(os.path.join(d, fn), arr + 1)
    with pytest.raises(IOError, match="corruption"):
        ckpt.restore(str(tmp_path))


def test_incomplete_checkpoint_ignored(tmp_path):
    ckpt.save(str(tmp_path), 1, _tree())
    # a crashed save: tmp dir without manifest
    os.makedirs(os.path.join(tmp_path, "step_00000002.tmp"))
    assert ckpt.latest_steps(str(tmp_path)) == [1]


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 99))
def test_checkpoint_property_roundtrip(tmp_path_factory, seed):
    d = tmp_path_factory.mktemp("ck")
    t = _tree(seed)
    ckpt.save(str(d), seed, t)
    _, got = ckpt.restore(str(d))
    for p, leaf in [(("params", "w"), t["params"]["w"]),
                    (("params", "b"), t["params"]["b"])]:
        node = got
        for k in p:
            node = node[k]
        np.testing.assert_array_equal(np.asarray(node), np.asarray(leaf))


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------


def test_straggler_monitor_flags_outlier():
    m = StragglerMonitor(min_steps=5, k_mad=5.0)
    for _ in range(20):
        assert not m.observe(0.100 + np.random.RandomState(1).rand() * 1e-3)
    assert m.observe(0.5)
    assert len(m.flagged) == 1


def test_heartbeat_tracker():
    hb = HeartbeatTracker(4, timeout_s=10.0)
    now = 100.0
    for h in range(4):
        hb.beat(h, t=now)
    assert hb.dead_hosts(now=105.0) == []
    hb.beat(0, t=120.0)
    hb.beat(1, t=120.0)
    hb.beat(2, t=120.0)
    assert hb.dead_hosts(now=121.0) == [3]


def test_plan_remesh():
    p = plan_remesh(n_surviving=192, model=16, old_global_batch=256)
    assert p.mesh_shape == (12, 16) and p.n_devices == 192
    assert p.global_batch % p.data == 0
    with pytest.raises(ValueError):
        plan_remesh(n_surviving=8, model=16, old_global_batch=256)


def test_elastic_restore_between_meshes(tmp_path):
    """Save sharded on a 4x2 mesh, restore onto 2x2 (subprocess)."""
    from conftest import run_devices
    code = f"""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS
from repro.checkpoint import ckpt
from repro.launch.mesh import make_mesh
d = r"{tmp_path}"
mesh_a = make_mesh(4, 2)
w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
sh_a = NamedSharding(mesh_a, PS("data", "model"))
tree = {{"w": jax.device_put(w, sh_a)}}
ckpt.save(d, 1, tree)
mesh_b = make_mesh(2, 2, devices=jax.devices()[:4])
sh_b = {{"w": NamedSharding(mesh_b, PS("model", "data"))}}
step, got = ckpt.restore(d, shardings=sh_b)
assert step == 1
np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(w))
assert got["w"].sharding.mesh.shape["data"] == 2
print("ELASTIC_OK")
"""
    assert "ELASTIC_OK" in run_devices(code, n_devices=8)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


def test_compressed_psum_subprocess():
    from conftest import run_devices
    code = """
import jax, numpy as np, jax.numpy as jnp, functools
from jax.sharding import PartitionSpec as PS
from repro.launch.mesh import make_mesh
from repro.optim.compression import compressed_psum, init_residuals
mesh = make_mesh(4, 1)
rng = np.random.RandomState(0)
g_global = rng.randn(4, 16).astype(np.float32)

def device_fn(g_loc, r_loc):
    (mean_g,), (new_r,) = compressed_psum((g_loc,), (r_loc,), mesh, ("data",))
    return mean_g, new_r

from jax import shard_map
fn = shard_map(device_fn, mesh=mesh,
               in_specs=(PS("data"), PS("data")),
               out_specs=(PS(None), PS("data")), check_vma=False)
g = jnp.asarray(g_global)
r = jnp.zeros_like(g)
mean_g, new_r = fn(g, r)
true_mean = g_global.mean(axis=0)
err = np.abs(np.asarray(mean_g)[0] - true_mean).max()
scale = np.abs(true_mean).max()
assert err < 0.05 * scale + 0.05, (err, scale)
# error feedback: residual equals quantization error, bounded by scale/127
assert np.abs(np.asarray(new_r)).max() < np.abs(g_global).max() / 100
print("COMP_OK")
"""
    assert "COMP_OK" in run_devices(code, n_devices=4)
