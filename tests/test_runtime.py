"""Runtime substrate: optimizer, checkpointing, fault tolerance, serving."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update, lr_at
from repro.optim.compression import (dequantize_int8, ef_compress,
                                     ef_compress_tree, ef_decompress_tree,
                                     init_residuals, quantize_int8)
from repro.runtime.checkpoint import (latest_step, restore_checkpoint,
                                      save_checkpoint)
from repro.runtime.fault_tolerance import (StragglerMonitor, plan_mesh,
                                           simulate_failure, with_retries)
from repro.runtime.serving import OffloadServingPool, Replica
from repro.runtime.train_loop import (TrainLoopConfig, make_train_step,
                                      train)


# -- optimizer ---------------------------------------------------------------

def quad_loss(params, batch):
    err = params["w"] - batch["target"]
    return jnp.sum(err * err), {"dummy": jnp.zeros(())}


def test_adamw_converges():
    cfg = AdamWConfig(peak_lr=0.1, warmup_steps=5, total_steps=200,
                      weight_decay=0.0)
    params = {"w": jnp.zeros(8, jnp.float32)}
    target = jnp.arange(8, dtype=jnp.float32) / 8.0
    st = adamw_init(params)
    for _ in range(200):
        g = jax.grad(lambda p: quad_loss(p, {"target": target})[0])(params)
        params, st, info = adamw_update(cfg, g, st, params)
    assert float(jnp.abs(params["w"] - target).max()) < 0.05


def test_lr_schedule_shape():
    cfg = AdamWConfig(peak_lr=1.0, warmup_steps=10, total_steps=100,
                      end_lr_frac=0.1)
    assert float(lr_at(cfg, jnp.asarray(0))) == 0.0
    assert np.isclose(float(lr_at(cfg, jnp.asarray(10))), 1.0)
    assert float(lr_at(cfg, jnp.asarray(100))) <= 0.11
    assert float(lr_at(cfg, jnp.asarray(55))) < 1.0


def test_adamw_bf16_params_fp32_moments():
    cfg = AdamWConfig(peak_lr=1e-2)
    params = {"w": jnp.ones(4, jnp.bfloat16)}
    st = adamw_init(params)
    assert st["m"]["w"].dtype == jnp.float32
    g = {"w": jnp.ones(4, jnp.bfloat16)}
    p2, st2, _ = adamw_update(cfg, g, st, params)
    assert p2["w"].dtype == jnp.bfloat16
    assert st2["step"] == 1


# -- compression --------------------------------------------------------------

def test_quantize_roundtrip_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3, 1000), jnp.float32)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x).max()
    assert float(err) <= float(s) * 0.5 + 1e-6


def test_error_feedback_accumulates():
    """With EF, the *running sum* of compressed grads tracks the true sum."""
    rng = np.random.default_rng(1)
    res = jnp.zeros(64, jnp.float32)
    true_sum = np.zeros(64)
    comp_sum = np.zeros(64)
    for i in range(50):
        g = jnp.asarray(rng.normal(0, 1, 64), jnp.float32)
        q, s, res = ef_compress(g, res)
        comp_sum += np.asarray(dequantize_int8(q, s))
        true_sum += np.asarray(g)
    # residual bounds the drift
    drift = np.abs(comp_sum + np.asarray(res) - true_sum).max()
    assert drift < 1e-3


def test_ef_tree_roundtrip():
    params = {"a": jnp.ones(8), "b": {"c": jnp.ones((2, 2))}}
    res = init_residuals(params)
    grads = jax.tree.map(lambda p: p * 0.37, params)
    q, s, res2 = ef_compress_tree(grads, res)
    deq = ef_decompress_tree(q, s)
    err = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), grads, deq)
    assert max(jax.tree.leaves(err)) < 0.01


# -- checkpointing -------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    state = {"params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
             "opt": {"step": jnp.asarray(7, jnp.int32)}}
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 7, state)
    save_checkpoint(d, 9, jax.tree.map(lambda x: x + 1, state))
    assert latest_step(d) == 9
    step, restored = restore_checkpoint(d, state)
    assert step == 9
    assert np.allclose(restored["params"]["w"],
                       np.asarray(state["params"]["w"]) + 1)


def test_checkpoint_retention(tmp_path):
    d = str(tmp_path / "ckpt")
    state = {"x": jnp.zeros(2)}
    for s in range(6):
        save_checkpoint(d, s, state, keep_last=2)
    kept = sorted(p for p in os.listdir(d) if p.startswith("step_"))
    assert len(kept) == 2 and kept[-1] == "step_00000005"


def test_checkpoint_shape_mismatch_raises(tmp_path):
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, {"w": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        restore_checkpoint(d, {"w": jnp.zeros((3, 3))})


# -- fault tolerance -----------------------------------------------------------

def test_retries():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert with_retries(flaky, n_retries=3)() == "ok"
    assert calls["n"] == 3
    with pytest.raises(ZeroDivisionError):
        with_retries(lambda: 1 / 0, n_retries=1)()


def test_straggler_monitor():
    m = StragglerMonitor(factor=3.0)
    flagged = [m.observe(i, 0.1) for i in range(10)]
    assert not any(flagged)
    assert m.observe(10, 1.0)       # 10x the EWMA
    assert m.flagged_steps == [10]
    assert not m.observe(11, 0.1)   # EWMA not poisoned


def test_plan_mesh_elastic():
    assert plan_mesh(512, 16, pod_axis=2) == (2, 16, 16)
    assert plan_mesh(256, 16) == (16, 16)
    # lose a pod: 256 devices left, single-pod layout
    assert plan_mesh(256, 16, pod_axis=1) == (16, 16)
    # lose 3 rows: 208 devices -> 13 data rows
    assert plan_mesh(208, 16) == (13, 16)
    with pytest.raises(ValueError):
        plan_mesh(8, 16)
    devs = list(range(512))
    assert len(simulate_failure(devs, 256)) == 256


def test_elastic_restore_across_meshes(tmp_path):
    """Save under one sharding layout, restore under another (1-device CPU:
    layouts differ logically; correctness = values survive)."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    d = str(tmp_path / "ckpt")
    state = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    save_checkpoint(d, 1, state)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    step, restored = restore_checkpoint(d, state, shardings=sh)
    assert np.allclose(restored["w"], np.asarray(state["w"]))
    assert restored["w"].sharding == sh["w"]


# -- train loop -----------------------------------------------------------------

def batches(target):
    while True:
        yield {"target": target}


def test_train_loop_runs_and_checkpoints(tmp_path):
    target = jnp.arange(8, dtype=jnp.float32)
    params = {"w": jnp.zeros(8, jnp.float32)}
    loop = TrainLoopConfig(total_steps=30, log_every=10, ckpt_every=10,
                           ckpt_dir=str(tmp_path / "ck"))
    opt = AdamWConfig(peak_lr=0.2, warmup_steps=2, total_steps=30,
                      weight_decay=0.0)
    res = train(quad_loss, params, batches(target), opt, loop,
                log=lambda *a: None)
    assert latest_step(str(tmp_path / "ck")) == 30
    l0 = float(quad_loss(params, {"target": target})[0])
    l1 = float(quad_loss(res.params, {"target": target})[0])
    assert l1 < l0 * 0.5

    # resume continues from the checkpoint
    res2 = train(quad_loss, params, batches(target), opt,
                 TrainLoopConfig(total_steps=35, ckpt_every=10,
                                 ckpt_dir=str(tmp_path / "ck")),
                 log=lambda *a: None)
    assert res2.resumed_from == 30


def test_microbatch_accumulation_matches_large_batch():
    opt = AdamWConfig(peak_lr=0.1, warmup_steps=0, weight_decay=0.0)
    params = {"w": jnp.zeros(4, jnp.float32)}
    st = adamw_init(params)
    big = {"target": jnp.stack([jnp.ones(4), 3 * jnp.ones(4)])}

    def loss_mean(p, b):
        err = p["w"][None, :] - b["target"]
        return jnp.mean(jnp.sum(err * err, -1)), {}

    step1 = make_train_step(loss_mean, opt, microbatches=1)
    p1, _, m1 = jax.jit(step1)(params, st, big)

    def loss_micro(p, b):
        err = p["w"] - b["target"]
        return jnp.sum(err * err), {}

    step2 = make_train_step(loss_micro, opt, microbatches=2)
    micro = {"target": big["target"][:, None, :][:, 0, :]}  # [2, 4]
    p2, _, m2 = jax.jit(step2)(params, st, micro)
    assert np.allclose(p1["w"], p2["w"], atol=1e-6)


# -- offload serving --------------------------------------------------------------

def test_offload_serving_pool():
    replicas = [
        Replica(0, classes={0, 1}, cycles_per_s=2e8, link_bps=75e6,
                runner=lambda xs: [("edge0", x) for x in xs]),
        Replica(1, classes={1, 2}, cycles_per_s=2e8, link_bps=75e6,
                runner=lambda xs: [("edge1", x) for x in xs]),
    ]
    pool = OffloadServingPool(replicas,
                              cloud_runner=lambda xs: [("cloud", x)
                                                       for x in xs])
    rng = np.random.default_rng(0)
    reqs = [{"class_id": int(rng.integers(4)),
             "cycles": float(rng.uniform(1e6, 1e8)),
             "result_bits": float(rng.uniform(1e5, 1e7)),
             "payload": i} for i in range(12)]
    out = pool.admit(reqs, policy="bnb")
    assert len(out.responses) == 12
    for i, (where, payload) in enumerate(out.responses):
        assert payload == i
        j = out.assignments[i]
        if j >= 0:
            assert reqs[i]["class_id"] in replicas[j].classes
            assert where == f"edge{j}"
        else:
            assert where == "cloud"
    # class 3 requests can only go to the cloud
    for i, r in enumerate(reqs):
        if r["class_id"] == 3:
            assert out.assignments[i] == -1


def test_checkpoint_bf16_roundtrip(tmp_path):
    """bf16 params survive npz (stored as raw bits; caught by train_lm)."""
    d = str(tmp_path / "ckpt")
    state = {"w": jnp.arange(8, dtype=jnp.bfloat16) / 8.0,
             "m": jnp.ones(4, jnp.float32)}
    save_checkpoint(d, 1, state)
    step, restored = restore_checkpoint(d, state)
    assert restored["w"].dtype == jnp.bfloat16
    assert np.allclose(np.asarray(restored["w"], np.float32),
                       np.asarray(state["w"], np.float32))
