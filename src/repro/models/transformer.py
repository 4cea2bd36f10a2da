"""Dense decoder-only transformer (llama/qwen/yi/command-r class) and its
VLM/encoder variants (LLaVA backbone, BERT*/ViT* from the paper's workloads).

Param tree layout (Hydra shards over the leading ``layers`` axis):

    {"embed": {...}, "layers": stacked-per-layer tree, "final_norm": {...}}

``forward`` drives the stacked layers with ``jax.lax.scan`` so the lowered
HLO is O(1) in depth; ``apply_layer_range`` applies a contiguous slice of
layers — this is the primitive Hydra's shard units execute.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as nn
from repro.sharding.context import constrain_batch


def init_layer(key, cfg):
    k1, k2 = jax.random.split(key)
    norm_init = nn.init_rmsnorm if cfg.norm == "rms" else nn.init_layernorm
    mlp_init = nn.init_swiglu if cfg.mlp == "swiglu" else nn.init_gelu_mlp
    return {
        "attn_norm": norm_init(cfg.d_model, cfg.param_dtype),
        "attn": nn.init_attention(k1, cfg),
        "mlp_norm": norm_init(cfg.d_model, cfg.param_dtype),
        "mlp": mlp_init(k2, cfg),
    }


def init_params(cfg, key):
    ke, kl = jax.random.split(key)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    norm_init = nn.init_rmsnorm if cfg.norm == "rms" else nn.init_layernorm
    return {
        "embed": nn.init_embedding(ke, cfg.vocab_size, cfg.d_model,
                                   cfg.param_dtype),
        "layers": stacked,
        "final_norm": norm_init(cfg.d_model, cfg.param_dtype),
    }


def _norm(cfg, p, x):
    return nn.rms_norm(p, x) if cfg.norm == "rms" else nn.layer_norm(p, x)


def apply_layer(cfg, lp, x, *, window: Optional[int] = None,
                positions=None, impl: Optional[str] = None):
    """One pre-norm transformer block. x: (b, s, d)."""
    impl = impl or cfg.attn_impl
    # Megatron-style sequence parallelism: the residual stream between
    # layers is seq-sharded over 'model'; norms run on it directly, and the
    # normed input is re-gathered (seq replicated) so tensor parallelism
    # owns the model axis inside attention/MLP.
    xn = constrain_batch(_norm(cfg, lp["attn_norm"], x), seq_parallel=False)
    h, _ = nn.attention(lp["attn"], xn, cfg,
                        positions=positions, causal=cfg.causal,
                        window=window if window is not None else cfg.window,
                        impl=impl)
    x = x + h
    hn = constrain_batch(_norm(cfg, lp["mlp_norm"], x), seq_parallel=False)
    h = (nn.swiglu(lp["mlp"], hn) if cfg.mlp == "swiglu"
         else nn.gelu_mlp(lp["mlp"], hn))
    return x + h


def apply_layer_decode(cfg, lp, x, cache, *, window=None):
    """One block in decode mode. cache: per-layer {"k","v","index"}."""
    positions = cache["index"] + jnp.arange(x.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, (x.shape[0], x.shape[1]))
    h, new_cache = nn.attention(
        lp["attn"], _norm(cfg, lp["attn_norm"], x), cfg,
        positions=positions, causal=True,
        window=window if window is not None else cfg.window,
        kv_cache=cache)
    x = x + h
    hn = _norm(cfg, lp["mlp_norm"], x)
    h = (nn.swiglu(lp["mlp"], hn) if cfg.mlp == "swiglu"
         else nn.gelu_mlp(lp["mlp"], hn))
    return x + h, new_cache


def embed_inputs(cfg, params, batch):
    if cfg.takes_embeddings and "embeds" in batch:
        return batch["embeds"].astype(cfg.dtype)
    return nn.embed(params["embed"], batch["tokens"], cfg.dtype)


def apply_layer_range(cfg, stacked_slice, x, *, window=None, remat=None):
    """Apply a contiguous slice of stacked layer params (Hydra shard unit)."""
    remat = cfg.remat if remat is None else remat
    fn = partial(apply_layer, cfg, window=window)
    if remat:
        fn = jax.checkpoint(fn)

    def body(h, lp):
        return constrain_batch(fn(lp, h)), None

    out, _ = jax.lax.scan(body, constrain_batch(x), stacked_slice)
    return out


def forward(cfg, params, batch, *, window=None, last_only=False):
    """Full forward to logits. batch: {"tokens": (b,s)} or {"embeds": ...}.

    ``last_only``: unembed only the final position (prefill serving) — the
    (b, s, V) logits tensor is never materialized."""
    x = embed_inputs(cfg, params, batch)
    x = apply_layer_range(cfg, params["layers"], x, window=window)
    if last_only:
        x = x[:, -1:]
    x = _norm(cfg, params["final_norm"], x)
    return nn.unembed(params["embed"], x)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_decode_state(cfg, batch: int, max_seq: int):
    return {"kv": nn.init_kv_cache(cfg, batch, max_seq)}


def paged_decode_step(cfg, params, pages, tables, lengths, tokens, *,
                      window=None, impl="jnp"):
    """One decode step over a paged KV cache shared by all lanes.

    tokens: (n, 1); pages: {"k","v"} of (L, P, bs, nkv, hd) — plus per-row
    {"k_scale","v_scale"} planes of (L, P, bs, nkv) when the pool is
    int8-quantized; tables: (n, B) physical block ids per lane; lengths:
    (n,) rows already written (this token's row index).  Batched over
    lanes rather than vmapped — the pages are shared state, so the
    per-lane programs are not independent — with the per-layer page
    pytree scanned exactly like ``decode_step`` scans the contiguous
    cache.  ``impl='fused'``/``'fused_interpret'`` runs the whole block
    through ``kernels.fused_decode`` when the config qualifies (RMSNorm +
    SwiGLU, fp pool); other configs quietly take the equivalent unfused
    Pallas path.  Returns (logits (n, 1, V), new pages).
    """
    x = nn.embed(params["embed"], tokens, cfg.dtype)
    win = window if window is not None else cfg.window
    fused = (impl in ("fused", "fused_interpret") and cfg.norm == "rms"
             and cfg.mlp == "swiglu" and "k_scale" not in pages)

    def body(h, xs):
        lp, pg = xs
        if fused:
            return nn.paged_decode_layer_fused(
                lp, h, cfg, pages=pg, tables=tables, lengths=lengths,
                window=win, interpret=(impl == "fused_interpret"))
        a, npg = nn.paged_attention_decode(
            lp["attn"], _norm(cfg, lp["attn_norm"], h), cfg,
            pages=pg, tables=tables, lengths=lengths,
            window=win, impl=impl)
        h = h + a
        hn = _norm(cfg, lp["mlp_norm"], h)
        m = (nn.swiglu(lp["mlp"], hn) if cfg.mlp == "swiglu"
             else nn.gelu_mlp(lp["mlp"], hn))
        return h + m, npg

    x, new_pages = jax.lax.scan(body, x, (params["layers"], pages))
    x = _norm(cfg, params["final_norm"], x)
    return nn.unembed(params["embed"], x), new_pages


def decode_step(cfg, params, state, tokens, *, window=None):
    """One decode step: tokens (b, 1) -> logits (b, 1, V), new state."""
    x = nn.embed(params["embed"], tokens, cfg.dtype)
    kv = state["kv"]

    def body(h, xs):
        lp, k_l, v_l = xs
        cache = {"k": k_l, "v": v_l, "index": kv["index"]}
        h, nc = apply_layer_decode(cfg, lp, h, cache, window=window)
        return constrain_batch(h), (nc["k"], nc["v"])

    x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], kv["k"], kv["v"]))
    x = _norm(cfg, params["final_norm"], x)
    logits = nn.unembed(params["embed"], x)
    new_state = {"kv": {"k": nk, "v": nv, "index": kv["index"] + tokens.shape[1]}}
    return logits, new_state


# ---------------------------------------------------------------------------
# speculative verify (k tokens scored against cached state in one forward)
# ---------------------------------------------------------------------------

def verify_step(cfg, params, state, tokens, *, window=None):
    """Score k draft positions against the contiguous KV cache in ONE
    forward: tokens ``(b, k)`` (last committed token + k-1 drafts) ->
    ``(logits (b, k, V), new state)`` with the cache index advanced by k.

    This is exactly the batched-prefill mechanism pointed at mid-decode:
    the causal chunk mask keeps intra-chunk attention correct, so position
    ``i``'s logits equal what i single-token decode steps would produce.
    The caller rolls the state back past the accept point with
    ``rollback_decode_state`` — rejected rows are never read again (decode
    masks keys at ``kvpos > qpos``) and are overwritten as decode resumes.
    """
    return decode_step(cfg, params, state, tokens, window=window)


def rollback_decode_state(cfg, state, delta):
    """Rewind the cache write index by ``delta`` rows (per-batch array or
    scalar).  Rows past the rewound index are stale but invisible: decode
    attention masks ``kvpos > qpos`` and later writes overwrite in place."""
    kv = state["kv"]
    return {"kv": {"k": kv["k"], "v": kv["v"],
                   "index": kv["index"] - delta}}


def paged_verify_step(cfg, params, pages, tables, lengths, tokens, *,
                      window=None, impl="jnp"):
    """The paged twin of ``verify_step``: score k positions per lane
    through per-lane block tables.  tokens ``(n, k)``; returns
    ``(logits (n, k, V), new pages)``.  The caller owns rollback: advance
    ``lengths`` by only the accepted rows and free/rewind tail blocks —
    rows past a lane's length are masked to zero weight, so rejected
    draft rows never perturb later decode.  ``impl`` routes the per-lane
    attention: 'jnp' is the historical gathered path, 'pallas' the Mosaic
    multi-query kernel (`kernels/paged_attention.py`)."""
    x = nn.embed(params["embed"], tokens, cfg.dtype)

    def body(h, xs):
        lp, pg = xs
        a, npg = nn.paged_attention_verify(
            lp["attn"], _norm(cfg, lp["attn_norm"], h), cfg,
            pages=pg, tables=tables, lengths=lengths,
            window=window if window is not None else cfg.window, impl=impl)
        h = h + a
        hn = _norm(cfg, lp["mlp_norm"], h)
        m = (nn.swiglu(lp["mlp"], hn) if cfg.mlp == "swiglu"
             else nn.gelu_mlp(lp["mlp"], hn))
        return h + m, npg

    x, new_pages = jax.lax.scan(body, x, (params["layers"], pages))
    x = _norm(cfg, params["final_norm"], x)
    return nn.unembed(params["embed"], x), new_pages


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def _kv_state_bytes(cfg, batch: int, max_seq: int) -> int:
    """Analytic residency of the pure KV decode state: K + V planes of
    (L, b, s, n_kv, hd) in ``cfg.kv_cache_dtype`` plus the int32 write
    index — must agree with ``jax.eval_shape`` over ``init_decode_state``
    (tests/test_registry.py cross-checks)."""
    item = jnp.dtype(cfg.kv_cache_dtype).itemsize
    kv = 2 * cfg.n_layers * batch * max_seq * cfg.n_kv_heads \
        * cfg.head_dim * item
    return kv + jnp.dtype(jnp.int32).itemsize


def _kv_block_bytes(cfg, block_size: int, kv_dtype=None) -> int:
    """Analytic residency of ONE physical KV block across all layers.

    ``kv_dtype='int8'`` prices the quantized pool: one int8 byte per
    cache element plus a 4-byte f32 scale per (row, kv head) — the page
    layout ``models.api.init_kv_pages`` allocates."""
    rows = 2 * cfg.n_layers * block_size * cfg.n_kv_heads
    if kv_dtype == "int8":
        return rows * (cfg.head_dim + jnp.dtype(jnp.float32).itemsize)
    item = jnp.dtype(cfg.kv_cache_dtype).itemsize
    return rows * cfg.head_dim * item


def _register():
    import sys

    from repro.models import registry
    mod = sys.modules[__name__]
    for family, tokens_only in (("dense", True), ("vlm", False)):
        registry.register(registry.FamilySpec(
            family=family, module=mod,
            batched_prefill=True, padded_prefill=True, paging=True,
            pure_kv_state=True, servable=True, spec_draftable=True,
            kv_quant=True,
            token_stream_data=tokens_only,
            notes={} if tokens_only else {
                "token_stream_data": "VLM batches carry fused patch+text "
                                     "embeddings, not raw token streams"},
            decode_state_cost=_kv_state_bytes,
            kv_block_cost=_kv_block_bytes))


_register()
