"""Latent-attention decoder with fine-grained experts (DeepSeek-V3 class:
Moonlight-16B-A3B).

Block ``i < first_k_dense``: RMSNorm -> MLA -> residual -> RMSNorm -> SwiGLU
of width ``d_ff`` -> residual.  Every later block replaces the MLP by

    y = shared(x) + sum over the top_k chosen experts e of w_e * expert_e(x)

with a sigmoid router computed in float32: experts are chosen by the top_k
of (score + selection bias), and weighted by their un-biased scores,
normalised to sum 1 and scaled by ``routed_scaling_factor``.

**Expert share.**  The layer is told which experts it holds
(``expert_offset`` .. ``+ n_experts_held``): it routes over all
``n_experts`` and computes only its held experts' part of the sum, as one
member of an expert-parallel group does before the exchange.  Routing is
dropless: the (token, slot) pairs that chose a held expert are sorted by
expert into a buffer sized for the worst case (every token choosing
``min(top_k, held)`` held experts), and grouped matmuls run over each
expert's rows only.

MLA (no q compression): q = x Wq per head as [nope, rope]; the latent
c = RMSNorm((x Wkv_a)[:r]) (eps 1e-6) and one rope key k_pe = (x Wkv_a)[r:] shared by
every head; [k_nope, v] = c Wkv_b per head; keys are [k_nope, rope(k_pe)],
softmax scale (nope + rope)^-1/2, causal.  Rope pairs halves.

Param tree: ``embed.table`` (V, d), ``dense`` and ``layers`` (stacked
blocks), ``head`` {``final_norm``, ``lm_head`` (d, V)}: the output head is
untied.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import megablox

from repro.models import layers as nn

# tokens per routed-expert pass: the dropless buffer of one pass holds
# chunk * min(top_k, held) rows, so longer batches scan over chunks
MOE_TOKEN_CHUNK = 8192
# the latent's RMSNorm is built with the HF module's default eps, not
# rms_norm_eps (DeepseekV3RMSNorm(kv_lora_rank))
KV_NORM_EPS = 1e-6
# grouped-matmul tiles (rows, contraction, output columns): the fastest of
# four tilings for an expert bank at d 2048, width 1,408 on a v5e
GMM_TILING = (512, 1024, 1024)
# rows of logits per pass of the training loss (4096 x a 20,480-row head
# slice is 0.34 GB in f32)
LOSS_ROW_CHUNK = 4096


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_mla(key, cfg):
    d, nh, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": nn.dense_init(ks[0], (d, nh * (nope + rope)), d,
                            cfg.param_dtype),
        "wkv_a": nn.dense_init(ks[1], (d, r + rope), d, cfg.param_dtype),
        "kv_norm": nn.init_rmsnorm(r, cfg.param_dtype),
        "wkv_b": nn.dense_init(ks[2], (r, nh * (nope + v)), r,
                               cfg.param_dtype),
        "wo": nn.dense_init(ks[3], (nh * v, d), nh * v, cfg.param_dtype),
    }


def init_expert(key, d: int, f: int, dtype):
    ks = jax.random.split(key, 3)
    return {"w_gate": nn.dense_init(ks[0], (d, f), d, dtype),
            "w_up": nn.dense_init(ks[1], (d, f), d, dtype),
            "w_down": nn.dense_init(ks[2], (f, d), f, dtype)}


def held_experts(cfg) -> jnp.ndarray:
    return cfg.expert_offset + jnp.arange(cfg.experts_held)


def init_moe(key, cfg):
    """Router, selection bias (a buffer: zero, never trained), the held
    experts (expert e drawn from ``fold_in(key, e)``, so a share holds the
    same weights as the whole layer) and the shared experts."""
    d, E = cfg.d_model, cfg.n_experts
    k_router, k_exp, k_shared = jax.random.split(key, 3)
    experts = jax.vmap(lambda e: init_expert(
        jax.random.fold_in(k_exp, e), d, cfg.moe_d_ff, cfg.param_dtype))(
        held_experts(cfg))
    return {"router": nn.dense_init(k_router, (d, E), d, cfg.param_dtype),
            "bias": jnp.zeros((E,), cfg.param_dtype),
            **experts,
            "shared": init_expert(k_shared, d,
                                  cfg.n_shared_experts * cfg.moe_d_ff,
                                  cfg.param_dtype)}


def init_layer(key, cfg, dense: bool):
    k1, k2 = jax.random.split(key)
    mlp = (nn.init_swiglu(k2, cfg) if dense else init_moe(k2, cfg))
    return {"attn_norm": nn.init_rmsnorm(cfg.d_model, cfg.param_dtype),
            "attn": init_mla(k1, cfg),
            "mlp_norm": nn.init_rmsnorm(cfg.d_model, cfg.param_dtype),
            "mlp" if dense else "moe": mlp}


def init_params(cfg, key):
    ke, kd, kl, kh = jax.random.split(key, 4)
    n_dense = cfg.first_k_dense
    return {
        "embed": nn.init_embedding(ke, cfg.vocab_size, cfg.d_model,
                                   cfg.param_dtype),
        "dense": jax.vmap(lambda k: init_layer(k, cfg, True))(
            jax.random.split(kd, n_dense)),
        "layers": jax.vmap(lambda k: init_layer(k, cfg, False))(
            jax.random.split(kl, cfg.n_layers - n_dense)),
        "head": {"final_norm": nn.init_rmsnorm(cfg.d_model, cfg.param_dtype),
                 "lm_head": nn.dense_init(kh, (cfg.d_model, cfg.vocab_size),
                                          cfg.d_model, cfg.param_dtype)},
    }


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def _mla_latent(p, x, cfg, positions):
    """x: (b, s, d) -> q (b, s, nh, nope + rope), with rope applied, and the
    latent row of each position (b, s, r + rope): the normed latent and
    the rope key all heads share (what a decode cache holds)."""
    b, s, _ = x.shape
    nh, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(b, s, nh, nope + rope)
    q = jnp.concatenate([q[..., :nope], nn.apply_rope(
        q[..., nope:], positions, cfg.rope_theta)], -1)
    kv = x @ p["wkv_a"].astype(dt)
    c = nn.rms_norm(p["kv_norm"], kv[..., :r], eps=KV_NORM_EPS)
    k_pe = nn.apply_rope(kv[..., r:][:, :, None, :], positions,
                         cfg.rope_theta)
    return q, jnp.concatenate([c, k_pe[:, :, 0]], -1)


def _mla_keys(p, latent, cfg):
    """Latent rows (b, S, r + rope) -> k (b, S, nh, nope + rope), v."""
    b, S, _ = latent.shape
    nh, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    kvb = (latent[..., :r] @ p["wkv_b"].astype(latent.dtype)).reshape(
        b, S, nh, -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        latent[:, :, None, r:], (b, S, nh, rope))], -1)
    return k, kvb[..., nope:]


def mla(p, x, cfg):
    """Causal latent attention over the whole sequence.  x: (b, s, d)."""
    b, s, _ = x.shape
    with jax.named_scope("mla"):
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        q, latent = _mla_latent(p, x, cfg, pos)
        k, v = _mla_keys(p, latent, cfg)
        o = nn.sdpa(q, k, v, causal=True)
        return o.reshape(b, s, -1) @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# experts
# ---------------------------------------------------------------------------

def route(p, x, cfg):
    """Sigmoid router over all ``n_experts`` in float32.  x: (T, d) ->
    (expert ids (T, top_k), weights (T, top_k) f32)."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(p["bias"].astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + bias, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.top_k > 1:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling_factor


def _row_tile(m: int) -> int:
    return math.gcd(m, GMM_TILING[0])


def grouped_mm(x, w, group_sizes):
    """Rows of ``x`` sorted by group times that group's matrix: (M, k) x
    (G, k, n) -> (M, n); rows past ``sum(group_sizes)`` come out zero.

    The Pallas megablox ``gmm`` (interpreted off the TPU): it visits only
    the row tiles that hold a group's rows, so the worst-case buffer costs
    nothing past them; on a v5e it ran an expert bank 1.3x faster than
    ``jax.lax.ragged_dot``.  The rows past the groups are passed as one more
    group that no matrix holds, which the kernel zeroes."""
    m, k = x.shape
    rest = m - jnp.sum(group_sizes)
    tiling = (_row_tile(m), min(GMM_TILING[1], k),
              min(GMM_TILING[2], w.shape[-1]))
    return megablox.gmm(x, w, jnp.concatenate([group_sizes, rest[None]]),
                        x.dtype, tiling,
                        interpret=jax.default_backend() != "tpu")


def rows_computed(group_sizes, m: int):
    """Rows the grouped matmul computes for each group, padding included:
    whole row tiles from the one holding a group's first row to the one
    holding its last."""
    tm = _row_tile(m)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = -(-ends // tm) - starts // tm
    return jnp.where(group_sizes > 0, tiles * tm, 0)


def _held_rows(cfg, idx):
    """Sort the (token, slot) pairs by held expert.  Returns the token of
    each buffer row (M,), the pair each row came from (M,), the rows per
    held expert (H,), the buffer's row count M, and for each pair (T, K)
    its buffer row and whether it chose a held expert."""
    T, K = idx.shape
    H = cfg.experts_held
    local = idx.reshape(-1) - cfg.expert_offset
    key = jnp.where((local >= 0) & (local < H), local, H)
    # a stable counting sort by key: each pair goes to its key's offset
    # plus the pairs of that key before it (a sort op of T * K keys took
    # the TPU compiler 20 s, this a tenth of that)
    onehot = (key[:, None] == jnp.arange(H + 1)[None]).astype(jnp.int32)
    counts = jnp.sum(onehot, axis=0)
    before = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, -1)
    dest = (jnp.cumsum(counts) - counts)[key] + before
    order = jnp.zeros_like(key).at[dest].set(
        jnp.arange(key.shape[0], dtype=key.dtype))
    M = T * min(K, H)                       # every held choice fits
    pair = order[:M]
    held = (key < H).reshape(T, K)
    return (pair // K, pair, counts[:H], M,
            jnp.minimum(dest, M - 1).reshape(T, K), held)


# Tokens to buffer rows and back, by gathers in both directions: XLA's
# transpose of a row gather is a scatter-add, which took the TPU compiler
# 20 s for one (49,152 x 2,048) buffer.

def _sum_held(rows, dest, held):
    """(M, d) buffer rows -> (T, d): each token's held rows summed in f32."""
    y = 0
    for k in range(dest.shape[1]):
        y = y + jnp.where(held[:, k, None], rows[dest[:, k]],
                          0).astype(jnp.float32)
    return y.astype(rows.dtype)


@jax.custom_vjp
def _dispatch(x, tok, dest, held):
    """(T, d) tokens -> (M, d) buffer rows, ``x[tok]``.  Rows past the
    groups are padding: their cotangent must be zero, and is dropped."""
    return x[tok]


def _dispatch_fwd(x, tok, dest, held):
    return x[tok], (dest, held)


def _dispatch_bwd(res, g):
    dest, held = res
    return _sum_held(g, dest, held), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, tok, dest, held):
    """(M, d) buffer rows -> (T, d): the sum of each token's held rows."""
    return _sum_held(rows, dest, held)


def _combine_fwd(rows, tok, dest, held):
    return _sum_held(rows, dest, held), (tok, held)


def _combine_bwd(res, g):
    tok, held = res
    valid = jnp.arange(tok.shape[0]) < jnp.sum(held)
    return jnp.where(valid[:, None], g[tok], 0), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(p, x, cfg):
    """The held experts' part of the routed sum.  x: (T, d) ->
    (y (T, d), rows computed per held expert (H,), padding included)."""
    T, d = x.shape
    dt = x.dtype
    with jax.named_scope("moe.router"):
        idx, w = route(p, x, cfg)
    with jax.named_scope("moe.routed"):
        tok, pair, sizes, M, dest, held = _held_rows(cfg, idx)
        valid = jnp.arange(M) < jnp.sum(sizes)
        xs = _dispatch(x, tok, dest, held)
        g = grouped_mm(xs, p["w_gate"].astype(dt), sizes)
        u = grouped_mm(xs, p["w_up"].astype(dt), sizes)
        # the routing weight scales each row before the (linear) down
        # projection; rows past the groups carry weight 0
        wr = w.reshape(-1)[pair].astype(dt)
        h = jnp.where(valid[:, None], jax.nn.silu(g) * u * wr[:, None], 0)
        rows = grouped_mm(h, p["w_down"].astype(dt), sizes)
        y = _combine(rows, tok, dest, held)
    return y, rows_computed(sizes, M)


def moe(p, x, cfg):
    """Shared experts plus the held experts' routed part.  x: (b, s, d) ->
    (y, counters): ``expert_rows``, rows computed per held expert, and
    ``expert_passes``, the routed passes (token chunks) that computed them."""
    b, s, d = x.shape
    T = b * s
    xt = x.reshape(T, d)
    if T > MOE_TOKEN_CHUNK and T % MOE_TOKEN_CHUNK == 0:
        # one chunk's dropless buffer at a time, in the backward pass too;
        # a Python loop, since a lax.scan kept every chunk's residuals
        one = jax.checkpoint(partial(routed_experts, cfg=cfg))
        ys, rows = [], 0
        for c in range(T // MOE_TOKEN_CHUNK):
            yc, rc = one(p, xt[c * MOE_TOKEN_CHUNK:(c + 1) * MOE_TOKEN_CHUNK])
            ys.append(yc)
            rows = rows + rc
        y = jnp.concatenate(ys)
        passes = T // MOE_TOKEN_CHUNK
    else:
        (y, rows), passes = routed_experts(p, xt, cfg), 1
    with jax.named_scope("moe.shared"):
        y = y + nn.swiglu(p["shared"], xt)
    return y.reshape(b, s, d), {"expert_rows": rows,
                                "expert_passes": jnp.int32(passes)}


# ---------------------------------------------------------------------------
# blocks / model
# ---------------------------------------------------------------------------

def _norm(cfg, p, x):
    return nn.rms_norm(p, x, eps=cfg.norm_eps)


def dense_layer(cfg, lp, x):
    x = x + mla(lp["attn"], _norm(cfg, lp["attn_norm"], x), cfg)
    return x + nn.swiglu(lp["mlp"], _norm(cfg, lp["mlp_norm"], x))


def moe_layer(cfg, lp, x):
    """One MoE block -> (x, the MoE's counters)."""
    x = x + mla(lp["attn"], _norm(cfg, lp["attn_norm"], x), cfg)
    y, counts = moe(lp["moe"], _norm(cfg, lp["mlp_norm"], x), cfg)
    return x + y, counts


def _layers(stacked):
    n = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda a: a[i], stacked) for i in range(n)]


# The stacked blocks run in a Python loop, not a lax.scan: under a scan,
# differentiating the blockwise attention's custom VJP also keeps the
# residuals of its forward loops (every score tile), which the rule never
# reads.  A SHARP segment holds one block, so nothing is unrolled there.

def apply_dense_range(cfg, stacked, x):
    fn = partial(dense_layer, cfg)
    if cfg.remat:
        fn = jax.checkpoint(fn)
    for lp in _layers(stacked):
        x = fn(lp, x)
    return x


def apply_layer_range(cfg, stacked, x):
    """A slice of the stacked MoE blocks -> (x, the MoE counters summed
    over the slice)."""
    fn = partial(moe_layer, cfg)
    if cfg.remat:
        fn = jax.checkpoint(fn)
    counts = None
    for lp in _layers(stacked):
        x, c = fn(lp, x)
        counts = c if counts is None else jax.tree.map(jnp.add, counts, c)
    return x, counts


def head_logits(cfg, hp, x):
    """Final norm and the untied head, logits in f32."""
    x = _norm(cfg, hp["final_norm"], x)
    return jnp.einsum("bsd,dv->bsv", x, hp["lm_head"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def head_loss(cfg, hp, x, labels):
    """Mean next-token cross-entropy, LOSS_ROW_CHUNK rows of logits at a
    time (each pass rematerialised), so the (tokens, vocab) logits never
    exist whole, in the forward or the backward pass."""
    d = x.shape[-1]
    xs, ls = x.reshape(-1, d), labels.reshape(-1)
    n = xs.shape[0]
    rows = LOSS_ROW_CHUNK if n % LOSS_ROW_CHUNK == 0 else n

    @jax.checkpoint
    def one(xc, lc):
        logits = head_logits(cfg, hp, xc[None])[0]
        gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    def body(total, xl):
        return total + one(*xl), None
    total, _ = jax.lax.scan(body, jnp.float32(0),
                            (xs.reshape(-1, rows, d), ls.reshape(-1, rows)))
    return total / n


def forward(cfg, params, batch, *, window=None, last_only=False):
    x = nn.embed(params["embed"], batch["tokens"], cfg.dtype)
    x = apply_dense_range(cfg, params["dense"], x)
    x, _ = apply_layer_range(cfg, params["layers"], x)
    if last_only:
        x = x[:, -1:]
    return head_logits(cfg, params["head"], x)


# ---------------------------------------------------------------------------
# decode through the latent cache (no serving engine uses it yet)
# ---------------------------------------------------------------------------

def init_decode_state(cfg, batch: int, max_seq: int):
    """Per layer, the normed latent (r) and the rope key (rope) of every
    position: the MLA cache, one row of r + rope per token."""
    shape = (cfg.n_layers, batch, max_seq,
             cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    return {"latent": jnp.zeros(shape, cfg.dtype),
            "index": jnp.zeros((), jnp.int32)}


def _mla_decode(p, x, cfg, cache, index):
    """Attend ``x`` (b, t, d) at rows index.. over the cache (b, S, r+rope)."""
    b, t, _ = x.shape
    pos = jnp.broadcast_to(index + jnp.arange(t, dtype=jnp.int32)[None],
                           (b, t))
    q, latent = _mla_latent(p, x, cfg, pos)
    cache = jax.lax.dynamic_update_slice_in_dim(
        cache, latent.astype(cache.dtype), index, axis=1)
    k, v = _mla_keys(p, cache.astype(x.dtype), cfg)
    o = nn.sdpa(q, k, v, causal=True, q_positions=pos[0],
                kv_positions=jnp.arange(cache.shape[1]))
    return o.reshape(b, t, -1) @ p["wo"].astype(x.dtype), cache


def decode_step(cfg, params, state, tokens, *, window=None):
    x = nn.embed(params["embed"], tokens, cfg.dtype)
    index = state["index"]
    n_dense = cfg.first_k_dense

    def block(moe_block):
        def body(h, xs):
            lp, cache = xs
            a, cache = _mla_decode(lp["attn"], _norm(cfg, lp["attn_norm"], h),
                                   cfg, cache, index)
            h = h + a
            hn = _norm(cfg, lp["mlp_norm"], h)
            h = h + (moe(lp["moe"], hn, cfg)[0] if moe_block
                     else nn.swiglu(lp["mlp"], hn))
            return h, cache
        return body

    lat = state["latent"]
    x, dense_cache = jax.lax.scan(block(False), x,
                                  (params["dense"], lat[:n_dense]))
    x, moe_cache = jax.lax.scan(block(True), x,
                                (params["layers"], lat[n_dense:]))
    logits = head_logits(cfg, params["head"], x)
    return logits, {"latent": jnp.concatenate([dense_cache, moe_cache]),
                    "index": index + tokens.shape[1]}


def _register():
    import sys

    from repro.models import registry
    reason = ("training only: the serving engines have no latent-cache "
              "path, and a chip's expert share computes only its held "
              "experts' part of each layer")
    registry.register(registry.FamilySpec(
        family="mla_moe", module=sys.modules[__name__],
        batched_prefill=False, padded_prefill=False, paging=False,
        pure_kv_state=False, servable=False, token_stream_data=True,
        notes={"batched_prefill": reason, "padded_prefill": reason,
               "paging": reason, "pure_kv_state": reason,
               "servable": reason, "spec_draftable": reason}))


_register()
