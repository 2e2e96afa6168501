"""GNN family: GCN, PNA, EGNN, NequIP — segment-op message passing.

JAX has no sparse SpMM beyond BCOO; the message-passing primitive here is
gather(src) -> transform -> ``jax.ops.segment_sum``/``segment_max`` (dst),
exactly as the kernel-taxonomy prescribes. The same edge-index layout feeds
the ``segment_mp`` Pallas kernel on TPU (see repro/kernels).

Equivariant models use the **Cartesian tensor basis** for irreps up to l=2
(l=0 scalar, l=1 vector, l=2 symmetric-traceless 3x3). For l<=2 this is an
equivalent change of basis from real spherical harmonics; tensor-product
paths (CG contractions) become dot/cross/symmetric-outer/mat-vec products —
MXU/VPU friendly and exactly E(3)-equivariant (tested by rotating inputs).
See DESIGN.md §Hardware-adaptation.

All models expose ``init_params``, ``forward`` and a scalar ``loss`` so the
runtime's generic train loop / dry-run drivers treat every family uniformly.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import AxisRules, constrain, dense_init, key_tree


# ---------------------------------------------------------------------------
# graph batch + segment helpers
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GNNConfig:
    name: str
    model: str                  # gcn | pna | egnn | nequip
    n_layers: int
    d_hidden: int
    n_classes: int = 16
    d_feat: int = 128
    n_species: int = 16         # equivariant models: atom-type vocabulary
    l_max: int = 2              # nequip
    n_rbf: int = 8              # nequip
    cutoff: float = 5.0         # nequip
    aggregators: tuple[str, ...] = ("mean", "max", "min", "std")  # pna
    scalers: tuple[str, ...] = ("identity", "amplification", "attenuation")


def seg_sum(x, idx, n):
    return jax.ops.segment_sum(x, idx, num_segments=n)


def mp_aggregate(msg, dst, n, rules, op: str = "sum"):
    """Distributed message aggregation (the GNN hot path).

    Vertex-partitioned DistGNN schedule: EDGE arrays are sharded over the
    DP axes, NODE tensors are sharded on the node dim. Each shard
    segment-reduces its local edges into a full [n, D] partial; a
    ``psum_scatter`` over the DP axes combines partials *and* leaves the
    result node-sharded (half the bytes of psum, no replicated outputs).
    GSPMD cannot shard the scatter on its own (it replicates multi-GB
    operands; §Perf iteration G1) — shard_map pins the layout.

    ``op="max"``: pmax has no AD rule, so a custom VJP routes the cotangent
    to the argmax positions (ties receive it jointly — subgradient).
    """
    mesh = rules.mesh
    if mesh is None or not rules.batch:
        if op == "sum":
            return seg_sum(msg, dst, n)
        raw = jax.ops.segment_max(msg, dst, num_segments=n)
        has = seg_sum(jnp.ones((msg.shape[0], 1), msg.dtype), dst, n) > 0
        return jnp.where(has, raw, 0.0)

    from jax.sharding import PartitionSpec as P
    batch = rules.batch
    nsh = 1
    for ax in batch:
        nsh *= mesh.shape[ax]
    assert n % nsh == 0, f"node dim {n} not divisible by {nsh}"

    if op == "sum":
        def body(msg_b, dst_b):
            part = jax.ops.segment_sum(msg_b, dst_b, num_segments=n)
            return jax.lax.psum_scatter(part, batch, scatter_dimension=0,
                                        tiled=True)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(batch, None), P(batch)),
                             out_specs=P(batch, None))(msg, dst)

    def run_max(m, d):
        def body(mb, db):
            part = jax.ops.segment_max(mb, db, num_segments=n)
            full = jax.lax.pmax(part, batch)
            has = jax.lax.psum(jax.ops.segment_sum(
                jnp.ones((mb.shape[0], 1), mb.dtype), db,
                num_segments=n), batch) > 0
            full = jnp.where(has, full, 0.0)
            # keep only this shard's node slice (node-sharded output)
            idx = jnp.int32(0)
            for ax in batch:
                idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
            return jax.lax.dynamic_slice_in_dim(full, idx * (n // nsh),
                                                n // nsh, axis=0)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(batch, None), P(batch)),
                             out_specs=P(batch, None))(m, d)

    @jax.custom_vjp
    def f(m, d):
        return run_max(m, d)

    def fwd(m, d):
        y = run_max(m, d)
        return y, (m, d, y)

    def bwd(res, g):
        m, d, y = res
        dmsg = jnp.where(m == y[d], g[d], 0.0)
        return dmsg, None

    f.defvjp(fwd, bwd)
    return f(msg, dst)


def seg_mean(x, idx, n, eps=1e-9):
    s = seg_sum(x, idx, n)
    cnt = seg_sum(jnp.ones((x.shape[0], 1), x.dtype), idx, n)
    return s / (cnt + eps)


def seg_max(x, idx, n):
    """segment_max with empty segments mapped to 0 (not -inf)."""
    raw = jax.ops.segment_max(x, idx, num_segments=n,
                              indices_are_sorted=False)
    has = jax.ops.segment_sum(jnp.ones((x.shape[0], 1), x.dtype), idx,
                              num_segments=n) > 0
    return jnp.where(has, raw, 0.0)


def seg_min(x, idx, n):
    return -seg_max(-x, idx, n)


def degrees(dst: jnp.ndarray, n: int) -> jnp.ndarray:
    return seg_sum(jnp.ones((dst.shape[0],), jnp.float32), dst, n)


def _mlp(params: list, x: jnp.ndarray, act=jax.nn.silu) -> jnp.ndarray:
    for i, (w, b) in enumerate(params):
        x = x @ w + b
        if i < len(params) - 1:
            x = act(x)
    return x


def _mlp_init(key, dims: list[int], dtype=jnp.float32) -> list:
    ks = key_tree(key, len(dims) - 1)
    return [(dense_init(k, (dims[i], dims[i + 1]), dtype=dtype),
             jnp.zeros((dims[i + 1],), dtype))
            for i, k in enumerate(ks)]


# ---------------------------------------------------------------------------
# GCN (Kipf & Welling) — sym-normalized SpMM via segments
# ---------------------------------------------------------------------------

def gcn_init(cfg: GNNConfig, key: jax.Array) -> dict:
    ks = key_tree(key, cfg.n_layers)
    dims = [cfg.d_feat] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    return {"w": [dense_init(ks[i], (dims[i], dims[i + 1]), dtype=jnp.float32)
                  for i in range(cfg.n_layers)]}


def gcn_forward(cfg: GNNConfig, params: dict, feat: jnp.ndarray,
                edge_index: jnp.ndarray, rules: AxisRules) -> jnp.ndarray:
    """feat [N, F]; edge_index [E, 2] (src, dst). Self-loops added here."""
    n = feat.shape[0]
    src, dst = edge_index[:, 0], edge_index[:, 1]
    ones = jnp.ones((src.shape[0], 1), jnp.float32)
    deg = mp_aggregate(ones, dst, n, rules)[:, 0] + 1.0   # +1 self loop
    inv_sqrt = jax.lax.rsqrt(deg)
    x = feat

    def layer(x, w, last):
        x = x @ w
        msg = x[src] * (inv_sqrt[src] * inv_sqrt[dst])[:, None]
        agg = mp_aggregate(msg, dst, n, rules) \
            + x * (inv_sqrt * inv_sqrt)[:, None]
        return agg if last else jax.nn.relu(agg)

    for i, w in enumerate(params["w"]):
        x = jax.checkpoint(layer, static_argnums=(2,))(
            x, w, i == len(params["w"]) - 1)
    return x


# ---------------------------------------------------------------------------
# PNA (Corso et al.) — multi-aggregator + degree scalers
# ---------------------------------------------------------------------------

def pna_init(cfg: GNNConfig, key: jax.Array) -> dict:
    ks = key_tree(key, 2 + 3 * cfg.n_layers)
    h = cfg.d_hidden
    n_agg = len(cfg.aggregators) * len(cfg.scalers)
    layers = []
    for i in range(cfg.n_layers):
        layers.append({
            "msg": _mlp_init(ks[2 + 3 * i], [2 * h, h, h]),
            "post": _mlp_init(ks[3 + 3 * i], [n_agg * h + h, h, h]),
        })
    return {
        "encode": _mlp_init(ks[0], [cfg.d_feat, h]),
        "layers": layers,
        "decode": _mlp_init(ks[1], [h, h, cfg.n_classes]),
    }


def pna_forward(cfg: GNNConfig, params: dict, feat: jnp.ndarray,
                edge_index: jnp.ndarray, rules: AxisRules) -> jnp.ndarray:
    n = feat.shape[0]
    src, dst = edge_index[:, 0], edge_index[:, 1]
    ones = jnp.ones((src.shape[0], 1), jnp.float32)
    cnt = mp_aggregate(ones, dst, n, rules)
    deg = cnt[:, 0]
    safe_cnt = jnp.maximum(cnt, 1.0)
    # PNA degree scalers, delta = mean log(deg+1) over the batch graph
    logd = jnp.log(deg + 1.0)
    delta = jnp.mean(logd) + 1e-9
    scaler_map = {
        "identity": jnp.ones_like(deg),
        "amplification": logd / delta,
        # deg-0 rows aggregate to zero anyway; clamp keeps the scaler finite
        "attenuation": delta / jnp.maximum(logd, np.log(2.0)),
    }
    x = _mlp(params["encode"], feat)

    def layer(x, lp):
        m = _mlp(lp["msg"], jnp.concatenate([x[dst], x[src]], axis=-1))
        mean = mp_aggregate(m, dst, n, rules) / safe_cnt
        aggs = []
        for a in cfg.aggregators:
            if a == "mean":
                aggs.append(mean)
            elif a == "max":
                aggs.append(mp_aggregate(m, dst, n, rules, op="max"))
            elif a == "min":
                aggs.append(-mp_aggregate(-m, dst, n, rules, op="max"))
            elif a == "std":
                sq = mp_aggregate(m * m, dst, n, rules) / safe_cnt
                aggs.append(jnp.sqrt(jnp.maximum(sq - mean * mean, 0.0)
                                     + 1e-9))
        scaled = []
        for s in cfg.scalers:
            for a in aggs:
                scaled.append(a * scaler_map[s][:, None])
        h = jnp.concatenate(scaled + [x], axis=-1)
        return x + _mlp(lp["post"], h)

    for lp in params["layers"]:
        x = jax.checkpoint(layer)(x, lp)
    return _mlp(params["decode"], x)


# ---------------------------------------------------------------------------
# EGNN (Satorras et al.) — E(n)-equivariant, scalar-distance messages
# ---------------------------------------------------------------------------

def egnn_init(cfg: GNNConfig, key: jax.Array) -> dict:
    ks = key_tree(key, 2 + 3 * cfg.n_layers)
    h = cfg.d_hidden
    layers = []
    for i in range(cfg.n_layers):
        base = 2 + 3 * i
        layers.append({
            "phi_e": _mlp_init(ks[base], [2 * h + 1, h, h]),
            "phi_x": _mlp_init(ks[base + 1], [h, h, 1]),
            "phi_h": _mlp_init(ks[base + 2], [2 * h, h, h]),
        })
    return {
        "embed": dense_init(ks[0], (cfg.n_species, h), dtype=jnp.float32),
        "layers": layers,
        "decode": _mlp_init(ks[1], [h, h, 1]),
    }


def egnn_forward(cfg: GNNConfig, params: dict, species: jnp.ndarray,
                 coords: jnp.ndarray, edge_index: jnp.ndarray,
                 rules: AxisRules) -> tuple[jnp.ndarray, jnp.ndarray]:
    """species [N] int, coords [N,3]. Returns (h [N,H], coords' [N,3])."""
    n = coords.shape[0]
    src, dst = edge_index[:, 0], edge_index[:, 1]
    ones = jnp.ones((src.shape[0], 1), jnp.float32)
    safe_cnt = jnp.maximum(mp_aggregate(ones, dst, n, rules), 1.0)
    h = params["embed"][species]
    x = coords

    def layer(h, x, lp):
        rel = x[dst] - x[src]
        d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)
        m = _mlp(lp["phi_e"], jnp.concatenate([h[dst], h[src], d2], axis=-1))
        # coordinate update, normalized for stability (EGNN §3.1 variant:
        # unit-ish direction + bounded coefficient keeps |x| from blowing up)
        coef = jnp.tanh(_mlp(lp["phi_x"], m))
        upd = mp_aggregate(rel / (jnp.sqrt(d2) + 1.0) * coef, dst, n, rules)
        x = x + upd / safe_cnt
        # feature update
        magg = mp_aggregate(m, dst, n, rules)
        h = h + _mlp(lp["phi_h"], jnp.concatenate([h, magg], axis=-1))
        return h, x

    for lp in params["layers"]:
        h, x = jax.checkpoint(layer)(h, x, lp)
    return h, x


def egnn_energy(cfg: GNNConfig, params: dict, species, coords, edge_index,
                graph_ids, n_graphs: int, rules: AxisRules) -> jnp.ndarray:
    h, _ = egnn_forward(cfg, params, species, coords, edge_index, rules)
    e_atom = _mlp(params["decode"], h)[:, 0]
    return seg_sum(e_atom, graph_ids, n_graphs)


# ---------------------------------------------------------------------------
# NequIP (Batzner et al.) — E(3)-equivariant tensor products, l_max = 2
# Cartesian irrep basis: l0 [., C], l1 [., C, 3], l2 [., C, 3, 3] (sym-tr.)
# ---------------------------------------------------------------------------

def _sym_traceless(M: jnp.ndarray) -> jnp.ndarray:
    Ms = 0.5 * (M + jnp.swapaxes(M, -1, -2))
    tr = jnp.trace(Ms, axis1=-2, axis2=-1)[..., None, None]
    eye = jnp.eye(3, dtype=M.dtype)
    return Ms - tr * eye / 3.0


def _bessel_rbf(r: jnp.ndarray, n_rbf: int, cutoff: float) -> jnp.ndarray:
    """NequIP radial basis: sin(n pi r / rc) / r with polynomial cutoff."""
    n = jnp.arange(1, n_rbf + 1, dtype=jnp.float32)
    rc = cutoff
    rs = jnp.clip(r, 1e-5, rc)
    basis = jnp.sqrt(2.0 / rc) * jnp.sin(n * jnp.pi * rs[..., None] / rc) \
        / rs[..., None]
    u = jnp.clip(r / rc, 0.0, 1.0)
    env = 1.0 - 10.0 * u**3 + 15.0 * u**4 - 6.0 * u**5   # p=3 polynomial
    return basis * env[..., None]


def nequip_init(cfg: GNNConfig, key: jax.Array) -> dict:
    C = cfg.d_hidden
    ks = key_tree(key, 3 + 8 * cfg.n_layers)
    layers = []
    for i in range(cfg.n_layers):
        base = 3 + 8 * i
        layers.append({
            # radial MLP -> per-path, per-channel weights (6 paths, see fwd)
            "radial": _mlp_init(ks[base], [cfg.n_rbf, C, 6 * C]),
            # channel mixers per output l
            "mix0": dense_init(ks[base + 1], (2 * C, C), dtype=jnp.float32),
            "mix1": dense_init(ks[base + 2], (3 * C, C), dtype=jnp.float32),
            "mix2": dense_init(ks[base + 3], (2 * C, C), dtype=jnp.float32),
            # gates: scalars produced from l0 to gate l1/l2
            "gate": _mlp_init(ks[base + 4], [C, 2 * C]),
            "self0": dense_init(ks[base + 5], (C, C), dtype=jnp.float32),
            "self1": dense_init(ks[base + 6], (C, C), dtype=jnp.float32),
            "self2": dense_init(ks[base + 7], (C, C), dtype=jnp.float32),
        })
    return {
        "embed": dense_init(ks[0], (cfg.n_species, C), dtype=jnp.float32),
        "layers": layers,
        "decode": _mlp_init(ks[1], [C, C, 1]),
    }


def _nequip_messages(cfg: GNNConfig, radial_mlp, rbf, Y1, Y2, s0, s1, s2,
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Tensor-product messages for one edge set (chunk-shaped or full).

    CG contractions in Cartesian form:
      p0: l0 x Y0 -> l0        p1: l0 x Y1 -> l1     p2: l0 x Y2 -> l2
      p3: l1 . Y1 -> l0        p4: l1 x Y1 -> l1 (cross)
      p5: l2 @ Y1 -> l1        (+ l1 (x) Y1 -> l2 sym-traceless outer)
    Returns flattened (m0 [E,2C], m1 [E,3C*3], m2 [E,2C*9]).
    """
    C = cfg.d_hidden
    E = rbf.shape[0]
    W = _mlp(radial_mlp, rbf).reshape(-1, 6, C)        # [E, 6 paths, C]
    m0_a = W[:, 0] * s0
    m1_a = W[:, 1][..., None] * (s0[..., None] * Y1[:, None, :])
    m2_a = W[:, 2][..., None, None] * (s0[..., None, None]
                                       * Y2[:, None, :, :])
    m0_b = W[:, 3] * jnp.einsum("eci,ei->ec", s1, Y1)
    m1_b = W[:, 4][..., None] * jnp.cross(s1, Y1[:, None, :])
    m1_c = W[:, 5][..., None] * jnp.einsum("ecij,ej->eci", s2, Y1)
    m2_b = _sym_traceless(s1[..., :, None] * Y1[:, None, None, :])
    m2_b = W[:, 3][..., None, None] * m2_b   # reuse radial ch. (path share)
    m0 = jnp.concatenate([m0_a, m0_b], -1)
    m1 = jnp.concatenate([m1_a, m1_b, m1_c], 1).reshape(E, -1)
    m2 = jnp.concatenate([m2_a, m2_b], 1).reshape(E, -1)
    return m0, m1, m2


def _nequip_aggregate_fused(cfg: GNNConfig, lp, h0, h1, h2, src, dst, rbf,
                            Y1, Y2, n: int, rules: AxisRules,
                            n_chunks: int = 8):
    """Fused, edge-chunked message+aggregate under shard_map (§Perf G2).

    The unfused path materializes [E_local, 2C*9] message tensors (~9 GB on
    ogb_products); here each shard scans its local edges in chunks — remat'd
    chunk bodies recompute messages in backward — and psum_scatters each
    chunk's partial straight onto the node shards, so peak edge state is
    one chunk.
    """
    from jax.sharding import PartitionSpec as P
    mesh, batch = rules.mesh, rules.batch
    C = cfg.d_hidden
    nsh = 1
    for ax in batch:
        nsh *= mesh.shape[ax]
    radial_leaves, radial_def = jax.tree.flatten(lp["radial"])

    def body(h0_l, h1_l, h2_l, src_b, dst_b, rbf_b, Y1_b, Y2_b, *rleaves):
        radial = radial_def.unflatten(list(rleaves))
        h0f = jax.lax.all_gather(h0_l, batch, axis=0, tiled=True)
        h1f = jax.lax.all_gather(h1_l, batch, axis=0, tiled=True)
        h2f = jax.lax.all_gather(h2_l, batch, axis=0, tiled=True)
        E_l = src_b.shape[0]
        bc = -(-E_l // n_chunks)            # ceil; tail masked below

        @jax.checkpoint
        def chunk(carry, i):
            a0, a1, a2 = carry
            start = jnp.maximum(jnp.minimum(i * bc, E_l - bc), 0)  # clamp...
            pos = start + jnp.arange(bc)
            live = (pos < E_l) & (pos >= i * bc)       # ... overlap masked
            sl = lambda a: jax.lax.dynamic_slice_in_dim(   # noqa: E731
                a, start, bc, axis=0)
            sc = jnp.where(live, sl(src_b), 0)
            dc = jnp.where(live, sl(dst_b), 0)
            m0, m1, m2 = _nequip_messages(
                cfg, radial, sl(rbf_b), sl(Y1_b), sl(Y2_b),
                h0f[sc], h1f[sc], h2f[sc])
            lv = live[:, None]
            p0 = jax.ops.segment_sum(jnp.where(lv, m0, 0), dc,
                                     num_segments=n)
            p1 = jax.ops.segment_sum(jnp.where(lv, m1, 0), dc,
                                     num_segments=n)
            p2 = jax.ops.segment_sum(jnp.where(lv, m2, 0), dc,
                                     num_segments=n)
            a0 += jax.lax.psum_scatter(p0, batch, scatter_dimension=0,
                                       tiled=True)
            a1 += jax.lax.psum_scatter(p1, batch, scatter_dimension=0,
                                       tiled=True)
            a2 += jax.lax.psum_scatter(p2, batch, scatter_dimension=0,
                                       tiled=True)
            return (a0, a1, a2), None

        zeros = tuple(
            jax.lax.pcast(jnp.zeros((n // nsh, d), jnp.float32), batch,
                          to="varying")
            for d in (2 * C, 3 * C * 3, 2 * C * 9))
        (a0, a1, a2), _ = jax.lax.scan(chunk, zeros, jnp.arange(n_chunks))
        return a0, a1, a2

    nsp = P(batch, None)
    rspecs = tuple(P(*([None] * leaf.ndim)) for leaf in radial_leaves)
    a0, a1, a2 = jax.shard_map(
        body, mesh=mesh,
        in_specs=(nsp, P(batch, None, None), P(batch, None, None, None),
                  P(batch), P(batch), nsp, nsp,
                  P(batch, None, None)) + rspecs,
        out_specs=(nsp, nsp, nsp))(
        h0, h1, h2, src, dst, rbf, Y1, Y2, *radial_leaves)
    return (a0, a1.reshape(n, 3 * C, 3), a2.reshape(n, 2 * C, 3, 3))


def nequip_forward(cfg: GNNConfig, params: dict, species: jnp.ndarray,
                   coords: jnp.ndarray, edge_index: jnp.ndarray,
                   rules: AxisRules) -> dict:
    """Returns final irrep features {l0:[N,C], l1:[N,C,3], l2:[N,C,3,3]}."""
    n = coords.shape[0]
    C = cfg.d_hidden
    src, dst = edge_index[:, 0], edge_index[:, 1]
    rel = coords[src] - coords[dst]                    # [E, 3]
    r = jnp.sqrt(jnp.sum(rel * rel, axis=-1) + 1e-12)
    rhat = rel / r[:, None]
    # spherical harmonics, Cartesian basis
    Y1 = rhat                                          # [E, 3]
    Y2 = _sym_traceless(rhat[:, :, None] * rhat[:, None, :])  # [E, 3, 3]
    rbf = _bessel_rbf(r, cfg.n_rbf, cfg.cutoff)        # [E, n_rbf]

    h0 = params["embed"][species]                      # [N, C]
    h1 = jnp.zeros((n, C, 3), jnp.float32)
    h2 = jnp.zeros((n, C, 3, 3), jnp.float32)
    fused = rules.mesh is not None and bool(rules.batch)

    def layer(h0, h1, h2, lp):
        if fused:
            a0, a1, a2 = _nequip_aggregate_fused(
                cfg, lp, h0, h1, h2, src, dst, rbf, Y1, Y2, n, rules)
        else:
            s0, s1, s2 = h0[src], h1[src], h2[src]
            m0, m1, m2 = _nequip_messages(cfg, lp["radial"], rbf, Y1, Y2,
                                          s0, s1, s2)
            a0 = mp_aggregate(m0, dst, n, rules)
            a1 = mp_aggregate(m1, dst, n, rules).reshape(n, 3 * C, 3)
            a2 = mp_aggregate(m2, dst, n, rules).reshape(n, 2 * C, 3, 3)

        # channel mixing + self-interaction
        n0 = a0 @ lp["mix0"] + h0 @ lp["self0"]
        n1 = jnp.einsum("nkx,kc->ncx",
                        a1.reshape(n, 3 * C, 3), lp["mix1"]) \
            + jnp.einsum("ncx,cd->ndx", h1, lp["self1"])
        n2 = jnp.einsum("nkxy,kc->ncxy",
                        a2.reshape(n, 2 * C, 3, 3), lp["mix2"]) \
            + jnp.einsum("ncxy,cd->ndxy", h2, lp["self2"])

        # gated nonlinearity: scalars via silu; l>0 gated by sigmoids of l0
        gates = _mlp(lp["gate"], n0)
        g1, g2 = jnp.split(jax.nn.sigmoid(gates), 2, axis=-1)
        h0 = h0 + jax.nn.silu(n0)
        h1 = h1 + n1 * g1[..., None]
        h2 = h2 + n2 * g2[..., None, None]
        return h0, h1, h2

    for lp in params["layers"]:
        h0, h1, h2 = jax.checkpoint(layer)(h0, h1, h2, lp)
    return {"l0": h0, "l1": h1, "l2": h2}


def nequip_energy(cfg: GNNConfig, params: dict, species, coords, edge_index,
                  graph_ids, n_graphs: int, rules: AxisRules) -> jnp.ndarray:
    feats = nequip_forward(cfg, params, species, coords, edge_index, rules)
    e_atom = _mlp(params["decode"], feats["l0"])[:, 0]
    return seg_sum(e_atom, graph_ids, n_graphs)


# ---------------------------------------------------------------------------
# uniform family API: init / forward / loss
# ---------------------------------------------------------------------------

def gnn_init(cfg: GNNConfig, key: jax.Array) -> dict:
    return {"gcn": gcn_init, "pna": pna_init, "egnn": egnn_init,
            "nequip": nequip_init}[cfg.model](cfg, key)


def gnn_loss(cfg: GNNConfig, params: dict, batch: dict,
             rules: AxisRules) -> tuple[jnp.ndarray, dict]:
    """Family-uniform loss.

    batch keys (invariant models): feat [N,F], edge_index [E,2],
      labels [N] int, label_mask [N] float
    batch keys (equivariant): species [N], coords [N,3], edge_index,
      graph_ids [N], energy [G], (label_mask unused)
    """
    if cfg.model in ("gcn", "pna"):
        fwd = gcn_forward if cfg.model == "gcn" else pna_forward
        logits = fwd(cfg, params, batch["feat"], batch["edge_index"], rules)
        lg = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, batch["labels"][:, None],
                                     axis=-1)[:, 0]
        nll = (lse - picked) * batch["label_mask"]
        loss = nll.sum() / jnp.maximum(batch["label_mask"].sum(), 1.0)
        return loss, {"nll": loss}
    energy_fn = egnn_energy if cfg.model == "egnn" else nequip_energy
    n_graphs = batch["energy"].shape[0]
    pred = energy_fn(cfg, params, batch["species"], batch["coords"],
                     batch["edge_index"], batch["graph_ids"], n_graphs, rules)
    loss = jnp.mean((pred - batch["energy"]) ** 2)
    return loss, {"mse": loss}
