"""Decoder-only transformer LM family (dense, MoE, local/global hybrid).

Covers the five assigned LM architectures through one config:

- qwen3-0.6b / qwen3-1.7b : dense, GQA, per-head qk RMSNorm, SwiGLU
- gemma2-2b               : GQA, alternating local(window)/global attention,
                            attn + final logit softcaps, GeGLU, sandwich norm
- phi3.5-moe-42b          : 16-expert top-2 MoE FFN
- granite-moe-1b          : 32-expert top-8 MoE FFN (tiny per-expert d_ff)

Implementation notes (distribution-minded; see DESIGN.md §5):

- layers run under ``lax.scan`` with stacked [L, ...] params and ``remat``
  on the body — small HLO, low compile time, activation memory O(√L)-style;
- training attention is **query-chunked** (exact softmax over full rows,
  computed per q-chunk via scan) so prefill at 32k never materializes the
  [S, S] score matrix;
- decode attends against a KV cache with masked positions — O(S) per token,
  which also serves ``long_500k`` (B=1, 512k cache) on a sequence-sharded
  cache;
- MoE dispatch is sort-based with per-expert capacity (MegaBlocks-flavoured,
  no [T, E, C] one-hot tensor): top-k -> argsort by expert -> rank-in-expert
  -> scatter into an [E*C, D] buffer -> batched expert GEMMs -> weighted
  combine. Load-balance aux loss included (Switch-style).

Sharding: FSDP over the d_model ("data" axis) + TP over heads/ffn/vocab/
experts ("model" axis); batch over ("pod", "data"). Expressed as
PartitionSpec constraints only — the same code compiles on any mesh.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import (ACTIVATIONS, AxisRules, constrain, dense_init,
                     embed_init, key_tree, rms_norm, rope, softcap)

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 128
    # MoE
    n_experts: int = 0                  # 0 == dense FFN
    top_k: int = 0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    # attention flavor
    attn_pattern: str = "global"        # "global" | "local_global"
    window: int = 4096
    attn_softcap: float | None = None
    final_softcap: float | None = None
    qk_norm: bool = False
    sandwich_norm: bool = False         # gemma2 pre+post norms
    scale_embed: bool = False           # gemma2 sqrt(d_model) embed scaling
    rope_theta: float = 10_000.0
    act: str = "silu"
    tie_embeddings: bool = True
    vocab_pad_multiple: int = 256
    q_chunk: int = 512

    @property
    def moe(self) -> bool:
        return self.n_experts > 0

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab + m - 1) // m) * m

    @property
    def group_size(self) -> int:
        return self.n_heads // self.n_kv_heads

    def layer_windows(self) -> np.ndarray:
        """Per-layer attention window (0 == global causal)."""
        if self.attn_pattern == "local_global":
            # gemma2: even layers local sliding-window, odd layers global
            return np.array([self.window if i % 2 == 0 else 0
                             for i in range(self.n_layers)], dtype=np.int32)
        return np.zeros(self.n_layers, dtype=np.int32)

    def param_count(self) -> int:
        """Exact parameter count (excl. vocab padding)."""
        d, dh = self.d_model, self.d_head
        attn = d * self.n_heads * dh + 2 * d * self.n_kv_heads * dh \
            + self.n_heads * dh * d
        if self.moe:
            ffn = self.n_experts * 3 * d * self.d_ff + d * self.n_experts
        else:
            ffn = 3 * d * self.d_ff
        norms = d * (4 if self.sandwich_norm else 2)
        if self.qk_norm:
            norms += 2 * dh
        per_layer = attn + ffn + norms
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top-k experts)."""
        if not self.moe:
            return self.param_count()
        d = self.d_model
        dense_like = self.param_count() \
            - self.n_layers * self.n_experts * 3 * d * self.d_ff
        return dense_like + self.n_layers * self.top_k * 3 * d * self.d_ff


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_lm_params(cfg: LMConfig, key: jax.Array,
                   dtype=jnp.bfloat16) -> dict:
    L, d, dh = cfg.n_layers, cfg.d_model, cfg.d_head
    H, Kh, F = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    ks = key_tree(key, 12)

    def stack(initfn, shape, k):
        keys = jax.random.split(k, L)
        return jnp.stack([initfn(kk, shape, dtype=dtype) for kk in keys])

    p: dict = {
        "embed": embed_init(ks[0], (cfg.padded_vocab, d), dtype=dtype),
        "final_norm": jnp.ones((d,), dtype=jnp.float32),
        "layers": {
            "wq": stack(dense_init, (d, H * dh), ks[1]),
            "wk": stack(dense_init, (d, Kh * dh), ks[2]),
            "wv": stack(dense_init, (d, Kh * dh), ks[3]),
            "wo": stack(dense_init, (H * dh, d), ks[4]),
            "ln_attn": jnp.ones((L, d), dtype=jnp.float32),
            "ln_mlp": jnp.ones((L, d), dtype=jnp.float32),
        },
    }
    lay = p["layers"]
    if cfg.sandwich_norm:
        lay["ln_attn_post"] = jnp.ones((L, d), dtype=jnp.float32)
        lay["ln_mlp_post"] = jnp.ones((L, d), dtype=jnp.float32)
    if cfg.qk_norm:
        lay["q_norm"] = jnp.ones((L, dh), dtype=jnp.float32)
        lay["k_norm"] = jnp.ones((L, dh), dtype=jnp.float32)
    if cfg.moe:
        E = cfg.n_experts
        lay["router"] = stack(dense_init, (d, E), ks[5]).astype(jnp.float32)
        lay["wi_gate"] = stack(dense_init, (E, d, F), ks[6])
        lay["wi_up"] = stack(dense_init, (E, d, F), ks[7])
        lay["wo_ffn"] = stack(dense_init, (E, F, d), ks[8])
    else:
        lay["wi_gate"] = stack(dense_init, (d, F), ks[6])
        lay["wi_up"] = stack(dense_init, (d, F), ks[7])
        lay["wo_ffn"] = stack(dense_init, (F, d), ks[8])
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[9], (d, cfg.padded_vocab), dtype=dtype)
    return p


def param_shardings(cfg: LMConfig, rules: AxisRules) -> dict:
    """PartitionSpec tree matching init_lm_params (FSDP + TP)."""
    from jax.sharding import PartitionSpec as P
    fs, tp = rules.fsdp, rules.tp
    lay = {
        "wq": P(None, fs, tp),
        "wk": P(None, fs, None),       # kv heads < tp degree: replicate
        "wv": P(None, fs, None),
        "wo": P(None, tp, fs),
        "ln_attn": P(None, None),
        "ln_mlp": P(None, None),
    }
    if cfg.sandwich_norm:
        lay["ln_attn_post"] = P(None, None)
        lay["ln_mlp_post"] = P(None, None)
    if cfg.qk_norm:
        lay["q_norm"] = P(None, None)
        lay["k_norm"] = P(None, None)
    if cfg.moe:
        lay["router"] = P(None, fs, None)
        lay["wi_gate"] = P(None, tp, fs, None)   # experts over TP
        lay["wi_up"] = P(None, tp, fs, None)
        lay["wo_ffn"] = P(None, tp, None, fs)
    else:
        lay["wi_gate"] = P(None, fs, tp)
        lay["wi_up"] = P(None, fs, tp)
        lay["wo_ffn"] = P(None, tp, fs)
    p = {"embed": P(tp, fs), "final_norm": P(None), "layers": lay}
    if not cfg.tie_embeddings:
        p["lm_head"] = P(fs, tp)
    return p


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _mask_logits(logits: jnp.ndarray, q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                 window) -> jnp.ndarray:
    """Causal + optional sliding-window mask. window==0 -> global."""
    causal = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        in_win = k_pos[None, :] > (q_pos[:, None] - window)
        use_win = window > 0
        causal = causal & (in_win | jnp.logical_not(use_win))
    return jnp.where(causal[None, None, None, :, :], logits, NEG_INF)


def chunked_causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             window, attn_softcap: float | None,
                             q_chunk: int, rules: AxisRules) -> jnp.ndarray:
    """Exact causal attention, scanned over query chunks.

    q: [B,S,H,dh], k/v: [B,S,Kh,dh]; window is a traced int32 scalar
    (0 == global) so local/global layers share one compiled body.
    """
    B, S, H, dh = q.shape
    Kh = k.shape[2]
    G = H // Kh
    scale = dh ** -0.5
    qr = q.reshape(B, S, Kh, G, dh)
    if S <= q_chunk:
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qr, k,
                            preferred_element_type=jnp.float32) * scale
        logits = softcap(logits, attn_softcap)
        pos = jnp.arange(S)
        logits = _mask_logits(logits, pos, pos, window)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
        return out.reshape(B, S, H, dh)

    n_chunks = S // q_chunk
    assert S % q_chunk == 0, "sequence must be divisible by q_chunk"
    k_pos = jnp.arange(S)

    # flash-style memory behavior: remat the chunk body so backward
    # recomputes the [bq, S] probs per chunk instead of saving all of them
    @jax.checkpoint
    def body(_, idx):
        qc = jax.lax.dynamic_slice_in_dim(qr, idx * q_chunk, q_chunk, axis=1)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qc, k,
                            preferred_element_type=jnp.float32) * scale
        logits = softcap(logits, attn_softcap)
        q_pos = idx * q_chunk + jnp.arange(q_chunk)
        logits = _mask_logits(logits, q_pos, k_pos, window)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
        return None, out

    _, outs = jax.lax.scan(body, None, jnp.arange(n_chunks))
    # [n_chunks, B, q_chunk, Kh, G, dh] -> [B, S, H, dh]
    outs = jnp.moveaxis(outs, 0, 1).reshape(B, S, Kh, G, dh)
    return outs.reshape(B, S, H, dh)


def cache_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, pos: jnp.ndarray, window,
                    attn_softcap: float | None) -> jnp.ndarray:
    """Decode attention: q [B,1,H,dh] vs cache [B,Smax,Kh,dh]; O(Smax)."""
    B, Q, H, dh = q.shape
    Kh = k_cache.shape[2]
    G = H // Kh
    scale = dh ** -0.5
    qr = q.reshape(B, Q, Kh, G, dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qr, k_cache,
                        preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, attn_softcap)
    s_pos = jnp.arange(k_cache.shape[1])
    valid = s_pos[None, :] <= pos
    if window is not None:
        in_win = s_pos[None, :] > (pos - window)
        valid = valid & (in_win | jnp.logical_not(window > 0))
    logits = jnp.where(valid[None, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_cache)
    return out.reshape(B, Q, H, dh)


# ---------------------------------------------------------------------------
# FFN: dense GLU + sort-based MoE
# ---------------------------------------------------------------------------

def dense_ffn(cfg: LMConfig, lp: dict, x: jnp.ndarray,
              rules: AxisRules) -> tuple[jnp.ndarray, jnp.ndarray]:
    act = ACTIVATIONS[cfg.act]
    h = act(x @ lp["wi_gate"]) * (x @ lp["wi_up"])
    h = constrain(h, rules.batch, None, rules.tp)
    out = h @ lp["wo_ffn"]
    return out, jnp.zeros((), jnp.float32)


def _moe_core(cfg: LMConfig, router, wi_gate, wi_up, wo_ffn, x, e0,
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Group-local sort-based top-k dispatch for a contiguous expert slice.

    x [G, Tg, D]; router scores ALL E experts; this shard computes only
    experts [e0, e0 + E_local) where E_local = wi_gate.shape[0]. Non-local
    assignments contribute zero — the caller psums over the expert shards.
    Everything here is local array math (sort along the last axis, scatter
    into a per-group capacity buffer, batched expert GEMMs).
    """
    G, Tg, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    El = wi_gate.shape[0]
    act = ACTIVATIONS[cfg.act]

    logits = jnp.einsum("gtd,de->gte", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)                   # [G, Tg, E]
    weights, ids = jax.lax.top_k(probs, K)                    # [G, Tg, K]
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    # load-balance aux (Switch): E * sum_e f_e * P_e  (local-token means;
    # callers pmean over the batch shards)
    f_e = jnp.mean(jax.nn.one_hot(ids[..., 0], E, dtype=jnp.float32),
                   axis=(0, 1))
    P_e = probs.mean(axis=(0, 1))
    aux = E * jnp.sum(f_e * P_e)

    # per-group, per-expert capacity (multiple of 8 keeps layouts tidy)
    C = int(max(8, np.ceil(Tg * K / E * cfg.capacity_factor / 8) * 8)) \
        if Tg * K >= 8 * E else int(max(1, np.ceil(K * cfg.capacity_factor)))

    A = Tg * K
    flat_ids = ids.reshape(G, A)
    order = jnp.argsort(flat_ids, axis=-1)
    sorted_ids = jnp.take_along_axis(flat_ids, order, axis=-1)
    starts = jax.vmap(
        lambda s: jnp.searchsorted(s, jnp.arange(E)))(sorted_ids)  # [G, E]
    rank = (jnp.arange(A)[None, :]
            - jnp.take_along_axis(starts, sorted_ids, axis=-1))
    local_e = sorted_ids - e0
    keep = (rank < C) & (local_e >= 0) & (local_e < El)
    dest = jnp.where(keep, local_e * C + rank, El * C)        # El*C == drop
    token_of = order // K                                     # [G, A]

    g_idx = jnp.arange(G)[:, None]
    src = jnp.take_along_axis(x, token_of[..., None], axis=1)  # [G, A, D]
    buf = jnp.zeros((G, El * C + 1, D), x.dtype).at[
        g_idx, dest].set(src)[:, :El * C].reshape(G, El, C, D)

    h = act(jnp.einsum("gecd,edf->gecf", buf, wi_gate)) \
        * jnp.einsum("gecd,edf->gecf", buf, wi_up)
    out_buf = jnp.einsum("gecf,efd->gecd", h, wo_ffn)

    flat_out = out_buf.reshape(G, El * C, D)
    gathered = jnp.take_along_axis(
        flat_out, jnp.minimum(dest, El * C - 1)[..., None], axis=1)
    w_sorted = jnp.take_along_axis(weights.reshape(G, A), order,
                                   axis=-1).astype(x.dtype)
    contrib = gathered * (w_sorted * keep)[..., None]
    y = jnp.zeros((G, Tg, D), x.dtype).at[g_idx, token_of].add(contrib)
    return y, aux


def moe_ffn(cfg: LMConfig, lp: dict, x: jnp.ndarray,
            rules: AxisRules) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via explicit SPMD (shard_map).

    Activations are batch-sharded and replicated over TP; experts live on
    TP ranks (EP). Each rank dispatches its local tokens to its local expert
    slice — all bookkeeping is shard-local — and one ``psum`` over the TP
    axis combines expert outputs (the exact cost of a row-parallel matmul
    all-reduce). FSDP weight shards are all-gathered explicitly.

    GSPMD cannot shard the dispatch scatter/gather well on its own (it
    replicates multi-GB operands — measured in EXPERIMENTS.md §Perf); the
    shard_map formulation pins the memory to the intended layout. Without a
    mesh (CPU tests) the single-shard core runs directly.
    """
    mesh = rules.mesh
    tp = rules.tp
    use_smap = (mesh is not None and tp in tuple(mesh.axis_names)
                and cfg.n_experts % mesh.shape[tp] == 0)
    if not use_smap:
        y, aux = _moe_core(cfg, lp["router"], lp["wi_gate"], lp["wi_up"],
                           lp["wo_ffn"], x, 0)
        return y, aux

    from jax.sharding import PartitionSpec as P
    fsdp, batch = rules.fsdp, rules.batch
    El = cfg.n_experts // mesh.shape[tp]

    def body(router, wig, wiu, wof, xb):
        if fsdp is not None:
            wig = jax.lax.all_gather(wig, fsdp, axis=1, tiled=True)
            wiu = jax.lax.all_gather(wiu, fsdp, axis=1, tiled=True)
            wof = jax.lax.all_gather(wof, fsdp, axis=2, tiled=True)
        e0 = jax.lax.axis_index(tp) * El
        y, aux = _moe_core(cfg, router, wig, wiu, wof, xb, e0)
        y = jax.lax.psum(y, tp)
        aux = jax.lax.pmean(aux, batch)
        return y, aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(tp, fsdp, None), P(tp, fsdp, None),
                  P(tp, None, fsdp), P(batch, None, None)),
        out_specs=(P(batch, None, None), P()),
    )(lp["router"], lp["wi_gate"], lp["wi_up"], lp["wo_ffn"], x)
    return y, aux


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _layer(cfg: LMConfig, lp: dict, x: jnp.ndarray, window,
           positions: jnp.ndarray, rules: AxisRules,
           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    B, S, D = x.shape
    H, Kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head

    h = rms_norm(x, lp["ln_attn"])
    q = (h @ lp["wq"]).reshape(B, S, H, dh)
    k = (h @ lp["wk"]).reshape(B, S, Kh, dh)
    v = (h @ lp["wv"]).reshape(B, S, Kh, dh)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"])
        k = rms_norm(k, lp["k_norm"])
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = constrain(q, rules.batch, None, rules.tp, None)
    attn = chunked_causal_attention(q, k, v, window, cfg.attn_softcap,
                                    cfg.q_chunk, rules)
    attn = (attn.reshape(B, S, H * dh) @ lp["wo"])
    if cfg.sandwich_norm:
        attn = rms_norm(attn, lp["ln_attn_post"])
    x = x + attn
    x = constrain(x, rules.batch, None, None)

    h = rms_norm(x, lp["ln_mlp"])
    ffn = moe_ffn if cfg.moe else dense_ffn
    out, aux = ffn(cfg, lp, h, rules)
    if cfg.sandwich_norm:
        out = rms_norm(out, lp["ln_mlp_post"])
    x = x + out
    return constrain(x, rules.batch, None, None), aux


def lm_forward(cfg: LMConfig, params: dict, tokens: jnp.ndarray,
               rules: AxisRules) -> tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B, S] -> (logits [B, S, V_padded], aux_loss)."""
    B, S = tokens.shape
    x = params["embed"][tokens]                  # gather, vocab-sharded
    if cfg.scale_embed:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    x = constrain(x, rules.batch, None, None)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    windows = jnp.asarray(cfg.layer_windows())

    def body(carry, scanned):
        x, aux = carry
        lp, window = scanned
        x, aux_l = _layer(cfg, lp, x, window, positions, rules)
        return (x, aux + aux_l), None

    body = jax.checkpoint(body,
                          policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (params["layers"], windows))
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    logits = softcap(logits, cfg.final_softcap)
    logits = constrain(logits, rules.batch, None, rules.tp)
    return logits, aux / cfg.n_layers


def lm_loss(cfg: LMConfig, params: dict, tokens: jnp.ndarray,
            rules: AxisRules) -> tuple[jnp.ndarray, dict]:
    """Next-token cross-entropy over [B, S] tokens."""
    logits, aux = lm_forward(cfg, params, tokens, rules)
    labels = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    nll = (lse - picked).mean()
    loss = nll + cfg.aux_loss_weight * aux
    return loss, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode with KV cache
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: LMConfig, batch: int, max_seq: int,
                  dtype=jnp.bfloat16) -> dict:
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_shardings(cfg: LMConfig, rules: AxisRules, seq_shard: bool = False):
    """KV cache specs [L, B, S, Hkv, dh].

    Batch over the DP axes AND sequence over the TP axis — kv-head counts
    (4-8) cannot fill a 16-way TP axis, but the cache *sequence* can; this
    is what keeps 32k-cache decode under HBM (§Perf iteration D1).
    ``seq_shard`` (B == 1 long-context): all axes go to the sequence dim.
    """
    from jax.sharding import PartitionSpec as P
    if seq_shard:
        axes = (rules.fsdp, rules.tp) if rules.fsdp else (rules.tp,)
        spec = P(None, None, axes, None, None)
    else:
        spec = P(None, rules.batch, rules.tp, None, None)
    return {"k": spec, "v": spec}


def lm_decode_step(cfg: LMConfig, params: dict, cache: dict,
                   tokens: jnp.ndarray, pos: jnp.ndarray,
                   rules: AxisRules) -> tuple[jnp.ndarray, dict]:
    """One decode step. tokens [B, 1]; pos: scalar int32 (current index).

    Returns (logits [B, 1, V], updated cache). The per-layer KV gets written
    at ``pos`` and attention sees positions <= pos.
    """
    B = tokens.shape[0]
    H, Kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    x = params["embed"][tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    windows = jnp.asarray(cfg.layer_windows())

    def body(x, scanned):
        lp, window, kc, vc = scanned
        h = rms_norm(x, lp["ln_attn"])
        q = (h @ lp["wq"]).reshape(B, 1, H, dh)
        k = (h @ lp["wk"]).reshape(B, 1, Kh, dh)
        v = (h @ lp["wv"]).reshape(B, 1, Kh, dh)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"])
            k = rms_norm(k, lp["k_norm"])
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k, pos, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v, pos, axis=1)
        attn = cache_attention(q, kc, vc, pos, window, cfg.attn_softcap)
        attn = attn.reshape(B, 1, H * dh) @ lp["wo"]
        if cfg.sandwich_norm:
            attn = rms_norm(attn, lp["ln_attn_post"])
        x = x + attn
        h2 = rms_norm(x, lp["ln_mlp"])
        ffn = moe_ffn if cfg.moe else dense_ffn
        out, _ = ffn(cfg, lp, h2, rules)
        if cfg.sandwich_norm:
            out = rms_norm(out, lp["ln_mlp_post"])
        return x + out, (kc, vc)

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], windows, cache["k"], cache["v"]))
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = softcap(x @ head, cfg.final_softcap)
    return logits, {"k": new_k, "v": new_v}


def lm_prefill(cfg: LMConfig, params: dict, tokens: jnp.ndarray,
               rules: AxisRules) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Prefill pass: logits only (cache fill elided in dry-run shapes)."""
    return lm_forward(cfg, params, tokens, rules)
