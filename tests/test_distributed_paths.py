"""Explicit-SPMD (shard_map) paths == local paths, on a 1x1 mesh.

The dry-run exercises these paths at 512 devices compile-only; here we run
them numerically on a trivial mesh and assert equality with the mesh-free
implementations (same math, different schedule).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.data.graphs import molecule_batch
from repro.models.common import AxisRules
from repro.models.gnn import GNNConfig, gnn_init, gnn_loss, mp_aggregate
from repro.models.transformer import LMConfig, init_lm_params, lm_loss


@pytest.fixture(scope="module")
def mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def test_moe_shardmap_matches_local(mesh11):
    cfg = LMConfig(name="m", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
                   d_head=8, d_ff=16, vocab=211, n_experts=4, top_k=2,
                   capacity_factor=2.0)
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    local_rules = AxisRules(batch=(), fsdp=None, tp=None)
    loss_local, _ = jax.jit(
        lambda p, t: lm_loss(cfg, p, t, local_rules))(params, toks)
    dist_rules = AxisRules.for_mesh(mesh11)
    with mesh11:
        loss_dist, _ = jax.jit(
            lambda p, t: lm_loss(cfg, p, t, dist_rules))(params, toks)
    assert np.isclose(float(loss_local), float(loss_dist), rtol=2e-3), \
        (float(loss_local), float(loss_dist))


def test_mp_aggregate_shardmap_matches_local(mesh11):
    rng = np.random.default_rng(0)
    E, N, D = 128, 32, 8
    msg = jnp.asarray(rng.normal(0, 1, (E, D)), jnp.float32)
    dst = jnp.asarray(rng.integers(0, N, E), jnp.int32)
    local = mp_aggregate(msg, dst, N, AxisRules(batch=(), mesh=None))
    rules = AxisRules.for_mesh(mesh11)
    with mesh11:
        dist = jax.jit(lambda m, d: mp_aggregate(m, d, N, rules))(msg, dst)
        dist_max = jax.jit(
            lambda m, d: mp_aggregate(m, d, N, rules, op="max"))(msg, dst)
    np.testing.assert_allclose(np.asarray(local), np.asarray(dist),
                               rtol=1e-6)
    local_max = mp_aggregate(msg, dst, N, AxisRules(batch=(), mesh=None),
                             op="max")
    np.testing.assert_allclose(np.asarray(local_max), np.asarray(dist_max),
                               rtol=1e-6)


@pytest.mark.parametrize("model", ["nequip", "egnn", "pna", "gcn"])
def test_gnn_dist_matches_local(mesh11, model):
    cfg = GNNConfig(name=model, model=model, n_layers=2, d_hidden=8,
                    n_species=8, n_classes=4, d_feat=16)
    params = gnn_init(cfg, jax.random.PRNGKey(0))
    if model in ("gcn", "pna"):
        from repro.data.graphs import cora_like
        data = cora_like(n_nodes=64, n_edges=256, d_feat=16, n_classes=4,
                         seed=2)
    else:
        data = molecule_batch(batch=4, n_nodes=16, n_edges=32, seed=2)
    batch = {k: jnp.asarray(v) for k, v in data.items()}
    loss_local, _ = jax.jit(lambda p, b: gnn_loss(
        cfg, p, b, AxisRules(batch=(), mesh=None)))(params, batch)
    rules = AxisRules.for_mesh(mesh11)
    with mesh11:
        loss_dist, _ = jax.jit(
            lambda p, b: gnn_loss(cfg, p, b, rules))(params, batch)
        # grads flow through the shard_map/custom-vjp paths
        g = jax.jit(jax.grad(
            lambda p: gnn_loss(cfg, p, batch, rules)[0]))(params)
    gn = jax.tree.reduce(lambda a, b: a + float(jnp.sum(jnp.abs(b))), g, 0.0)
    assert np.isclose(float(loss_local), float(loss_dist), rtol=1e-4), model
    assert np.isfinite(gn) and gn > 0
