"""Plain reference of bert-large-1b (the paper's BERT-Large*, 1.07 B
parameters) and the counts its metrics need.

The model as the configuration file states it: token embedding (tied to
the output head), 36 pre-norm blocks of LayerNorm -> multi-head attention
with q/k/v biases, rotary positions and no causal mask -> residual ->
LayerNorm -> GELU (tanh) MLP with biases -> residual, a final LayerNorm,
and next-token cross-entropy over every position.  Weights are drawn from
the seed by the initialisation the configuration states: normal with
std 1/sqrt(fan_in) for projections, 0.02 for the embedding, zero biases,
unit norm scales, keys split as (embedding, layers) then per layer
(attention, MLP).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import refops as R


def init_layer(m: dict, key) -> dict:
    d, h, hd, f = m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"]
    kv = m["n_kv_heads"]
    k_attn, k_mlp = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 2)
    return {
        "attn": {"wq": R.normal(ka[0], (d, h * hd), 1 / math.sqrt(d)),
                 "wk": R.normal(ka[1], (d, kv * hd), 1 / math.sqrt(d)),
                 "wv": R.normal(ka[2], (d, kv * hd), 1 / math.sqrt(d)),
                 "wo": R.normal(ka[3], (h * hd, d), 1 / math.sqrt(h * hd)),
                 "bq": jnp.zeros((h * hd,)), "bk": jnp.zeros((kv * hd,)),
                 "bv": jnp.zeros((kv * hd,))},
        "attn_norm": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        "mlp": {"w_in": R.normal(km[0], (d, f), 1 / math.sqrt(d)),
                "b_in": jnp.zeros((f,)),
                "w_out": R.normal(km[1], (f, d), 1 / math.sqrt(f)),
                "b_out": jnp.zeros((d,))},
        "mlp_norm": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
    }


def layer_keys(m: dict, seed: int):
    k_embed, k_layers = jax.random.split(jax.random.PRNGKey(seed))
    return k_embed, jax.random.split(k_layers, m["n_layers"])


def init_embed(m: dict, key) -> jnp.ndarray:
    return R.normal(key, (m["vocab_size"], m["d_model"]), 0.02)


def init_final_norm(m: dict) -> dict:
    return {"scale": jnp.ones((m["d_model"],)),
            "bias": jnp.zeros((m["d_model"],))}


def layer(m: dict, lp: dict, x, precision: str):
    """One block.  x: (b, t, d) float32."""
    b, t, _ = x.shape
    h, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = lp["attn"]
    xn = R.layer_norm(x, lp["attn_norm"]["scale"], lp["attn_norm"]["bias"],
                      1e-5)
    q = (R.mm(xn, a["wq"], precision) + a["bq"]).reshape(b, t, h, hd)
    k = (R.mm(xn, a["wk"], precision) + a["bk"]).reshape(b, t, kv, hd)
    v = (R.mm(xn, a["wv"], precision) + a["bv"]).reshape(b, t, kv, hd)
    pos = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    q, k = R.rope(q, pos, m["rope_theta"]), R.rope(k, pos, m["rope_theta"])
    o = R.attention(q, k, v, causal=False, precision=precision)
    x = x + R.mm(o.reshape(b, t, h * hd), a["wo"], precision)
    mp = lp["mlp"]
    hn = R.layer_norm(x, lp["mlp_norm"]["scale"], lp["mlp_norm"]["bias"],
                      1e-5)
    u = R.gelu_tanh(R.mm(hn, mp["w_in"], precision) + mp["b_in"])
    return x + R.mm(u, mp["w_out"], precision) + mp["b_out"]


def head_loss(m: dict, final_norm: dict, table, x, labels, precision: str):
    xn = R.layer_norm(x, final_norm["scale"], final_norm["bias"], 1e-5)
    logits = R.einsum("btd,vd->btv", xn, table, precision)
    return R.xent(logits, labels)


# -- counts -----------------------------------------------------------------

def n_params(m: dict) -> int:
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d + h * hd + 2 * kv * hd
    mlp = 2 * d * f + f + d
    norms = 4 * d
    return m["n_layers"] * (attn + mlp + norms) + m["vocab_size"] * d + 2 * d


def train_flops_per_token(m: dict, seq: int) -> float:
    """Model FLOPs of one trained token: 6 N for the weights (the tied
    table counted once, as the output head), plus 12 L d s for the
    forward and backward of non-causal attention scores and values.
    Recomputation is not counted."""
    return 6.0 * n_params(m) + 12.0 * m["n_layers"] * m["d_model"] * seq
