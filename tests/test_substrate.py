"""Optimizer, data pipeline, checkpointing, compression, straggler monitor."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import PipelineConfig, TokenPipeline
from repro.optim import adamw
from repro.optim.compress import dequantize_int8, quantize_int8
from repro.runtime.elastic import plan_rescale, rescale_batch_boundaries
from repro.runtime.straggler import StragglerConfig, StragglerMonitor


# ---------------------------------------------------------------- optimizer
def test_adamw_converges_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw.init(params, cfg)

    def loss(p):
        return jnp.sum((p["w"] - jnp.array([1.0, 2.0])) ** 2)

    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state, _ = adamw.update(g, state, params, cfg)
    np.testing.assert_allclose(np.asarray(params["w"]), [1.0, 2.0], atol=1e-2)


def test_adamw_clipping_and_metrics():
    cfg = adamw.AdamWConfig(lr=1e-3, clip_norm=1.0)
    params = {"w": jnp.ones((4,))}
    state = adamw.init(params, cfg)
    g = {"w": jnp.full((4,), 100.0)}
    new_params, state, m = adamw.update(g, state, params, cfg)
    assert float(m["grad_norm"]) == pytest.approx(200.0)
    delta = np.abs(np.asarray(new_params["w"] - params["w"])).max()
    assert delta < 0.01  # clipped step is tiny


def test_adamw_bf16_params_master_fp32():
    cfg = adamw.AdamWConfig(lr=0.05, weight_decay=0.0)
    params = {"w": jnp.ones((8,), jnp.bfloat16)}
    state = adamw.init(params, cfg)
    for i in range(20):
        g = {"w": jnp.full((8,), 1e-3, jnp.bfloat16)}
        params, state, _ = adamw.update(g, state, params, cfg)
    assert params["w"].dtype == jnp.bfloat16
    assert state.master["w"].dtype == jnp.float32
    # master accumulates updates below bf16 resolution
    assert float(state.master["w"][0]) != 1.0


def test_cosine_schedule():
    s = adamw.cosine_schedule(jnp.arange(0, 1000), warmup=100, total=1000)
    s = np.asarray(s)
    assert s[0] == 0.0 and abs(s[100] - 1.0) < 0.02
    assert s[-1] <= s[200]


# ------------------------------------------------------------- compression
def test_int8_roundtrip():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1000,)) * 3.0
    q, scale = quantize_int8(x)
    y = dequantize_int8(q, scale, x.shape, jnp.float32)
    err = np.abs(np.asarray(x - y)).max()
    assert err < 3.0 * 2 / 127  # block max / 127 quantization step


def test_compressed_psum_error_feedback(subproc):
    out = subproc(r"""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from functools import partial
from repro.optim.compress import compressed_psum

mesh = Mesh(np.array(jax.devices()), ("d",))
x = jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64) / 100.0

def f(xs):
    s, r = compressed_psum(xs[0], "d")
    return s[None], r[None]

g = shard_map(f, mesh=mesh, in_specs=P("d", None), out_specs=P("d", None))
s, resid = g(x)
ref = np.asarray(x).sum(0)
got = np.asarray(s)[0]
np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.05)
print("PSUM_OK")
""", devices=8)
    assert "PSUM_OK" in out


# ---------------------------------------------------------------- pipeline
def test_pipeline_deterministic_and_restartable():
    cfg = PipelineConfig(vocab_size=1000, global_batch=8, seq_len=32)
    p1 = TokenPipeline(cfg)
    b5a = p1.batch_at(5)
    p2 = TokenPipeline(cfg)
    b5b = p2.batch_at(5)
    np.testing.assert_array_equal(b5a["tokens"], b5b["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b5a["tokens"][:, 1:], b5a["labels"][:, :-1])


def test_pipeline_host_sharding_partition():
    rows = []
    for host in range(4):
        cfg = PipelineConfig(vocab_size=100, global_batch=16, seq_len=8,
                             num_hosts=4, host_id=host)
        p = TokenPipeline(cfg)
        lo, hi = p.host_rows()
        rows.extend(range(lo, hi + 1))
        b = p.batch_at(0)
        assert b["tokens"].shape[0] == hi - lo + 1
    assert sorted(rows) == list(range(16))


def test_pipeline_prefetch_iterator():
    cfg = PipelineConfig(vocab_size=100, global_batch=4, seq_len=8, prefetch=2)
    p = TokenPipeline(cfg).start(step=3)
    b = next(p)
    ref = p.batch_at(3)
    np.testing.assert_array_equal(b["tokens"], ref["tokens"])
    p.stop()


# -------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_keep(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, async_save=False)
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones((4,))}}
    for step in [10, 20, 30]:
        ck.save(step, jax.tree.map(lambda t, s=step: t + s, tree), {"note": step})
    assert ck.all_steps() == [20, 30]  # keep=2
    restored, meta, step = ck.restore(tree)
    assert step == 30 and meta["note"] == 30
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.asarray(tree["a"] + 30))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save(1, {"a": jnp.ones((4,))})
    with pytest.raises(ValueError):
        ck.restore({"a": jnp.ones((5,))})


def test_checkpoint_atomicity(tmp_path):
    """A leftover .tmp dir is never listed as a valid step."""
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save(7, {"a": jnp.ones(2)})
    os.makedirs(os.path.join(str(tmp_path), "step_00000099.tmp"))
    assert ck.all_steps() == [7]
    assert ck.latest_step() == 7


# ----------------------------------------------------------------- elastic
def test_elastic_plan():
    plan = plan_rescale(512, model_parallel=16, pods=2)
    assert plan.mesh_shape == (2, 16, 16)
    plan2 = plan_rescale(256, model_parallel=16)
    assert plan2.mesh_shape == (16, 16)
    with pytest.raises(ValueError):
        plan_rescale(100, model_parallel=16)
    assert rescale_batch_boundaries(16, 4)[-1] == (12, 15)


# --------------------------------------------------------------- straggler
def test_straggler_monitor_rebalances():
    mon = StragglerMonitor(4, 64, StragglerConfig(cooldown_steps=2,
                                                  trigger_imbalance=0.1))
    new = None
    for _ in range(12):
        new = mon.observe([1.0, 1.0, 1.0, 3.0]) or new
    assert new is not None
    sizes = [hi - lo + 1 for lo, hi in new]
    assert sizes[3] < 16  # the slow host got fewer rows
    assert sum(sizes) == 64
    assert new[0][0] == 0 and new[-1][1] == 63


def test_straggler_monitor_stable_when_balanced():
    mon = StragglerMonitor(4, 64, StragglerConfig(cooldown_steps=2))
    for _ in range(10):
        assert mon.observe([1.0, 1.01, 0.99, 1.0]) is None


def test_grad_accum_matches_single_step():
    """grad_accum=k averages microbatch grads — numerically identical step."""
    from repro.configs import get_smoke_config
    from repro.launch.steps import make_train_step
    from repro.models import lm

    cfg = get_smoke_config("internlm2-20b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (4, 64), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (4, 64), 0, cfg.vocab_size)}
    p1, o1, m1 = jax.jit(make_train_step(cfg))(params, opt, batch)
    p2, o2, m2 = jax.jit(make_train_step(cfg, grad_accum=2))(params, opt, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)
