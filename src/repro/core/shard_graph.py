"""Model-as-a-queue-of-segments: the structural substrate for Hydra.

A *segment* is the finest cut-point granularity (one layer / layer-group, or
the embed / bridge / head ends).  The partitioner groups contiguous segments
into *shards*; SHARP schedules *shard units* (forward or backward of one
shard on one mini-batch).

Two parameter classes:

* **own** params — spillable; live host-side, promoted with their shard,
  optimizer-stepped right after the shard's backward unit (paper semantics).
* **shared** params — referenced by more than one segment (tied embedding
  table; zamba2's shared attention block).  One host copy; promoted alongside
  any shard that references them; gradients accumulate across backward units
  and step once when the model's mini-batch completes.  This is the one
  structural extension over the paper's queue model (DESIGN.md §4).

Segments pass a pytree ``act``.  Non-chain data flow lives inside ``act``:
encoder-decoder segments carry ``{"x", "enc"}`` (identity passthrough of
``enc`` makes vjp accumulate cross-attention gradients); MoE segments carry
running aux-loss scalars whose loss cotangent is constant.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import encdec, hybrid, mla_moe, moe, ssm, transformer
from repro.models import layers as nn
from repro.training.losses import softmax_xent

Act = Any
ParamTree = Any


@dataclass(frozen=True)
class Segment:
    """One cut-point unit of a model.

    apply(cfg, own_params, shared_params: dict, act, batch) -> act
    """
    name: str
    param_ref: Optional[tuple]        # ref for own params (None = stateless)
    shared: tuple                      # names of shared param groups used
    apply: Callable[..., Act]
    flops_weight: float = 1.0          # relative cost hint (pilot fallback)


@dataclass
class ShardPlan:
    cfg: Any
    segments: list[Segment]
    shared_refs: dict[str, tuple]      # name -> ref into the full param tree
    loss: Callable[..., jnp.ndarray]   # loss(cfg, act, batch)


# ---------------------------------------------------------------------------
# param_ref resolution (host trees are dicts of numpy/jnp stacked arrays)
# ---------------------------------------------------------------------------

def resolve_ref(params: ParamTree, ref: Optional[tuple]):
    if ref is None:
        return None
    if len(ref) == 4 and ref[0] == "stack_slice":
        _, key, lo, hi = ref
        return jax.tree.map(lambda a: a[lo:hi], params[key])
    node = params
    for k in ref:
        node = node[k]
    return node


def update_with_ref(params: ParamTree, ref: tuple, new_val) -> int:
    """Write ``new_val`` back at ``ref`` into the host tree (in place).  A
    layer slice is copied into the stacked arrays; any other ref takes
    ``new_val``'s arrays as they are.  Returns the bytes copied."""
    if ref is None:
        return 0
    if len(ref) == 4 and ref[0] == "stack_slice":
        _, key, lo, hi = ref
        copied = 0

        def write(dst, src):
            nonlocal copied
            dst = np.asarray(dst)
            if not dst.flags.writeable:
                dst = dst.copy()
                copied += dst.nbytes
            src = np.asarray(src)
            dst[lo:hi] = src
            copied += src.nbytes
            return dst

        params[key] = jax.tree.map(write, params[key], new_val)
        return copied
    node = params
    for k in ref[:-1]:
        node = node[k]
    node[ref[-1]] = jax.tree.map(np.asarray, new_val)
    return 0


# ---------------------------------------------------------------------------
# family shard plans
# ---------------------------------------------------------------------------

def _xent_loss(cfg, act, batch):
    loss = softmax_xent(act["logits"], batch["labels"])
    if "aux" in act:
        # act carries per-layer sums; the reference loss uses layer means
        loss = loss + (0.01 * act["aux"]["lb"]
                       + 1e-3 * act["aux"]["z"]) / cfg.n_layers
    return loss


def _slice1(lp):
    return jax.tree.map(lambda a: a[0], lp)


def _dense_plan(cfg) -> ShardPlan:
    def embed_apply(cfg, own, shared, act, batch):
        x = transformer.embed_inputs(cfg, {"embed": shared["embed"]}, batch)
        return {"x": x}

    def layer_apply(cfg, own, shared, act, batch):
        return {"x": transformer.apply_layer_range(cfg, own, act["x"])}

    def head_apply(cfg, own, shared, act, batch):
        x = transformer._norm(cfg, own, act["x"])
        return {"logits": nn.unembed(shared["embed"], x)}

    segs = [Segment("embed", None, ("embed",), embed_apply, 0.1)]
    for i in range(cfg.n_layers):
        segs.append(Segment(f"layer{i}", ("stack_slice", "layers", i, i + 1),
                            (), layer_apply))
    segs.append(Segment("head", ("final_norm",), ("embed",), head_apply, 0.5))
    return ShardPlan(cfg, segs, {"embed": ("embed",)}, _xent_loss)


def _moe_plan(cfg) -> ShardPlan:
    def embed_apply(cfg, own, shared, act, batch):
        x = transformer.embed_inputs(cfg, {"embed": shared["embed"]}, batch)
        zero = jnp.zeros((), jnp.float32)
        return {"x": x, "aux": {"lb": zero, "z": zero}}

    def layer_apply(cfg, own, shared, act, batch):
        x, aux = moe.apply_layer_range(cfg, own, act["x"])
        return {"x": x, "aux": {"lb": act["aux"]["lb"] + aux["lb_loss"],
                                "z": act["aux"]["z"] + aux["z_loss"]}}

    def head_apply(cfg, own, shared, act, batch):
        x = nn.rms_norm(own, act["x"])
        return {"logits": nn.unembed(shared["embed"], x), "aux": act["aux"]}

    segs = [Segment("embed", None, ("embed",), embed_apply, 0.1)]
    for i in range(cfg.n_layers):
        segs.append(Segment(f"layer{i}", ("stack_slice", "layers", i, i + 1),
                            (), layer_apply))
    segs.append(Segment("head", ("final_norm",), ("embed",), head_apply, 0.5))
    return ShardPlan(cfg, segs, {"embed": ("embed",)}, _xent_loss)


def _mla_moe_plan(cfg) -> ShardPlan:
    """Untied: the embedding and the head are each one segment's own params,
    and nothing is shared.  MoE segments hand the rows their held experts
    computed, and in how many passes, out of the chain as ``act["counts"]``
    (``ShardFunctions``)."""
    n_dense = cfg.first_k_dense

    def embed_apply(cfg, own, shared, act, batch):
        return {"x": nn.embed(own, batch["tokens"], cfg.dtype)}

    def dense_apply(cfg, own, shared, act, batch):
        return {"x": mla_moe.apply_dense_range(cfg, own, act["x"])}

    def layer_apply(cfg, own, shared, act, batch):
        x, counts = mla_moe.apply_layer_range(cfg, own, act["x"])
        return {"x": x, "counts": counts}

    def head_apply(cfg, own, shared, act, batch):
        return {"loss": mla_moe.head_loss(cfg, own, act["x"],
                                          batch["labels"])}

    segs = [Segment("embed", ("embed",), (), embed_apply, 0.1)]
    for i in range(cfg.n_layers):
        if i < n_dense:
            segs.append(Segment(f"dense{i}", ("stack_slice", "dense", i,
                                              i + 1), (), dense_apply))
        else:
            j = i - n_dense
            segs.append(Segment(f"layer{i}", ("stack_slice", "layers", j,
                                              j + 1), (), layer_apply))
    segs.append(Segment("head", ("head",), (), head_apply, 0.5))
    return ShardPlan(cfg, segs, {}, lambda cfg, act, batch: act["loss"])


def _ssm_plan(cfg) -> ShardPlan:
    def embed_apply(cfg, own, shared, act, batch):
        return {"x": nn.embed(shared["embed"], batch["tokens"], cfg.dtype)}

    def group_apply(cfg, own, shared, act, batch):
        return {"x": ssm.apply_layer_range(cfg, own, act["x"])}

    def head_apply(cfg, own, shared, act, batch):
        x = nn.rms_norm(own, act["x"])
        return {"logits": nn.unembed(shared["embed"], x)}

    segs = [Segment("embed", None, ("embed",), embed_apply, 0.1)]
    for i in range(ssm.n_groups(cfg)):
        segs.append(Segment(f"group{i}", ("stack_slice", "layers", i, i + 1),
                            (), group_apply, 2.0))
    segs.append(Segment("head", ("final_norm",), ("embed",), head_apply, 0.5))
    return ShardPlan(cfg, segs, {"embed": ("embed",)}, _xent_loss)


def _hybrid_plan(cfg) -> ShardPlan:
    flags = np.asarray(hybrid.attn_flags(cfg))

    def embed_apply(cfg, own, shared, act, batch):
        return {"x": nn.embed(shared["embed"], batch["tokens"], cfg.dtype)}

    def make_layer_apply(i):
        use_attn = bool(flags[i])

        def layer_apply(cfg, own, shared, act, batch):
            lp = _slice1(own)
            x = act["x"]
            x = x + ssm.mamba2_forward(lp["mamba"],
                                       nn.rms_norm(lp["norm"], x), cfg)
            if use_attn:
                x, _ = hybrid.apply_shared_attn(cfg, shared["attn"], x)
            return {"x": x}

        return layer_apply

    def head_apply(cfg, own, shared, act, batch):
        x = nn.rms_norm(own, act["x"])
        return {"logits": nn.unembed(shared["embed"], x)}

    segs = [Segment("embed", None, ("embed",), embed_apply, 0.1)]
    for i in range(cfg.n_layers):
        shared_names = ("attn",) if flags[i] else ()
        segs.append(Segment(f"mamba{i}", ("stack_slice", "layers", i, i + 1),
                            shared_names, make_layer_apply(i),
                            2.0 if flags[i] else 1.0))
    segs.append(Segment("head", ("final_norm",), ("embed",), head_apply, 0.5))
    return ShardPlan(cfg, segs,
                     {"embed": ("embed",), "attn": ("shared_attn",)},
                     _xent_loss)


def _audio_plan(cfg) -> ShardPlan:
    def front_apply(cfg, own, shared, act, batch):
        x = batch["enc_embeds"].astype(cfg.dtype)
        x = x + encdec.sinusoidal_positions(
            x.shape[1], cfg.d_model).astype(cfg.dtype)
        return {"enc_x": x}

    def enc_layer_apply(cfg, own, shared, act, batch):
        lp = _slice1(own)
        return {"enc_x": encdec.apply_enc_layer(cfg, lp, act["enc_x"])}

    def bridge_apply(cfg, own, shared, act, batch):
        enc = nn.layer_norm(own["enc_final_norm"], act["enc_x"])
        tokens = batch["tokens"]
        x = nn.embed(shared["embed"], tokens, cfg.dtype)
        x = x + own["dec_pos"][:tokens.shape[1]].astype(cfg.dtype)[None]
        return {"x": x, "enc": enc}

    def dec_layer_apply(cfg, own, shared, act, batch):
        lp = _slice1(own)
        x = encdec.apply_dec_layer(cfg, lp, act["x"], act["enc"])
        return {"x": x, "enc": act["enc"]}   # passthrough accumulates grads

    def head_apply(cfg, own, shared, act, batch):
        x = nn.layer_norm(own, act["x"])
        return {"logits": nn.unembed(shared["embed"], x)}

    class _BridgeRef(dict):
        pass

    segs = [Segment("frontend", None, (), front_apply, 0.1)]
    for i in range(cfg.n_encoder_layers):
        segs.append(Segment(f"enc{i}", ("stack_slice", "encoder", i, i + 1),
                            (), enc_layer_apply))
    segs.append(Segment("bridge", ("bridge_group",), ("embed",),
                        bridge_apply, 0.1))
    for i in range(cfg.n_layers):
        segs.append(Segment(f"dec{i}", ("stack_slice", "decoder", i, i + 1),
                            (), dec_layer_apply, 1.5))
    segs.append(Segment("head", ("final_norm",), ("embed",), head_apply, 0.5))
    return ShardPlan(cfg, segs, {"embed": ("embed",)}, _xent_loss)


def prepare_host_params(cfg, params) -> ParamTree:
    """Family-specific host-tree tweaks (adds grouped views where needed)."""
    params = dict(params)
    if cfg.family == "audio" and "bridge_group" not in params:
        params["bridge_group"] = {
            "enc_final_norm": params.pop("enc_final_norm"),
            "dec_pos": params.pop("dec_pos"),
        }
    return params


def restore_model_params(cfg, host_params) -> ParamTree:
    """Inverse of prepare_host_params (for checkpoint / reference compare)."""
    params = dict(host_params)
    if cfg.family == "audio" and "bridge_group" in params:
        grp = params.pop("bridge_group")
        params["enc_final_norm"] = grp["enc_final_norm"]
        params["dec_pos"] = grp["dec_pos"]
    return params


import functools


@functools.lru_cache(maxsize=None)
def build_plan(cfg) -> ShardPlan:
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return _dense_plan(cfg)
    if fam == "moe":
        return _moe_plan(cfg)
    if fam == "mla_moe":
        return _mla_moe_plan(cfg)
    if fam == "ssm":
        return _ssm_plan(cfg)
    if fam == "hybrid":
        return _hybrid_plan(cfg)
    if fam == "audio":
        return _audio_plan(cfg)
    raise ValueError(fam)
