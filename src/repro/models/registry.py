"""Capability-driven family registry: one ``FamilySpec`` per model family.

The execution layers (serving backends, prefill factories, admission
sizing, the session planner) must never hard-code which families support
which optimization — that couples every new serving feature to a hunt
through call sites (the dispatch-dict / predicate-zoo problem this module
replaces).  Instead each family module registers a spec declaring:

* ``module`` — the implementation exposing the family surface
  (``init_params`` / ``forward`` / ``decode_step`` / ...);
* capability flags — ``batched_prefill``, ``padded_prefill``, ``paging``,
  ``pure_kv_state``, ``servable``, ``token_stream_data`` — each with a
  recorded *reason* when absent (``notes``), so fallback warnings and
  plan metadata can explain themselves;
* decode-state cost fns — ``decode_state_bytes`` / ``kv_block_bytes`` —
  the byte quantities admission control charges against the session's
  ``DeviceMemory`` ledger (defaults derive from ``jax.eval_shape`` over
  the module's constructors: weak-type correct, zero allocation).

Consumers ask ``spec(cfg)`` (or ``spec("dense")``) and read capabilities;
adding a family means registering one spec, and adding a capability means
one new field with a default — no call-site hunting either way.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from importlib import import_module
from types import ModuleType
from typing import Any, Callable, Optional

import jax


class CapabilityFallbackWarning(UserWarning):
    """A requested serving feature is not in the family's declared
    capabilities; execution fell back to the closest supported mode."""


def _tree_bytes(tree) -> int:
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def _default_decode_state_bytes(mod: ModuleType, cfg, batch: int,
                                max_seq: int) -> int:
    spec = jax.eval_shape(lambda: mod.init_decode_state(cfg, batch, max_seq))
    return _tree_bytes(spec)


def _default_kv_block_bytes(cfg, block_size: int) -> int:
    from repro.models import layers as nn
    pages = jax.eval_shape(lambda: nn.init_kv_cache(cfg, 1, block_size))
    return _tree_bytes({"k": pages["k"], "v": pages["v"]})


@dataclass(frozen=True)
class FamilySpec:
    """One model family's declared surface + capabilities + cost model."""

    family: str                     # cfg.family value ("dense", "moe", ...)
    module: ModuleType              # implementation module
    # -- capabilities --------------------------------------------------------
    batched_prefill: bool = False   # whole prompt chunk in ONE decode_step
    padded_prefill: bool = False    # right-padded prefill token-identical
    paging: bool = False            # decode state can live in paged KV blocks
    pure_kv_state: bool = False     # decode state is a pure KV cache
    servable: bool = True           # InferenceEngine can serve this family
    token_stream_data: bool = True  # train/eval batches are {tokens, labels}
    spec_draftable: bool = False    # multi-token verify + KV rollback work:
    #   the family can be the target (or draft) of speculative decoding
    kv_quant: bool = False          # paged KV pool can be int8-quantized
    #   (per-row scales stored alongside pages; requires paging)
    # capability -> one-line reason it is absent (warnings / plan meta)
    notes: dict = field(default_factory=dict)
    # -- cost fns (admission control charges these against the ledger) ------
    decode_state_cost: Optional[Callable[[Any, int, int], int]] = None
    kv_block_cost: Optional[Callable[..., int]] = None

    def decode_state_bytes(self, cfg, batch: int, max_seq: int) -> int:
        """Residency bytes of one decode state (slot-granular admission)."""
        if self.decode_state_cost is not None:
            return self.decode_state_cost(cfg, batch, max_seq)
        return _default_decode_state_bytes(self.module, cfg, batch, max_seq)

    def kv_block_bytes(self, cfg, block_size: int, kv_dtype=None) -> int:
        """Residency bytes of ONE physical KV block across all layers
        (page-granular admission).  Only meaningful when ``paging``.
        ``kv_dtype='int8'`` prices the quantized pool (pages + per-row
        scale planes) and requires the ``kv_quant`` capability."""
        if kv_dtype in (None, "fp"):
            if self.kv_block_cost is not None:
                return self.kv_block_cost(cfg, block_size)
            return _default_kv_block_bytes(cfg, block_size)
        if not self.kv_quant:
            raise ValueError(
                f"{self.family}: kv_dtype={kv_dtype!r} unsupported — "
                f"{self.why_not('kv_quant')}")
        return self.kv_block_cost(cfg, block_size, kv_dtype)

    @property
    def preemptible(self) -> bool:
        """A RUNNING request can be descheduled and later resumed with
        prefill skipped.  Derived, not declared: preemption rides on the
        paged backend's refcounted block tables (snapshot the table,
        keep the blocks), so exactly the ``paging`` families qualify —
        a family cannot promise preemption without paged KV."""
        return self.paging

    def capabilities(self) -> dict:
        """JSON-ready capability record (plan meta / poll / summaries)."""
        return {"batched_prefill": self.batched_prefill,
                "padded_prefill": self.padded_prefill,
                "paging": self.paging,
                "pure_kv_state": self.pure_kv_state,
                "servable": self.servable,
                "spec_draftable": self.spec_draftable,
                "kv_quant": self.kv_quant,
                "preemptible": self.preemptible}

    def why_not(self, capability: str) -> str:
        if capability == "kv_quant" and "kv_quant" not in self.notes:
            return ("int8 KV quantizes paged blocks on write; " +
                    ("the family has not declared a quantized page "
                     "layout + cost model" if self.paging
                     else self.why_not("paging")))
        if capability == "preemptible" and "preemptible" not in self.notes:
            # derived from paging: explain through the underlying flag
            return ("preemption snapshots paged block tables; " +
                    ("the slot/spec backends keep contiguous or lockstep "
                     "decode state — serve with backend='paged'"
                     if self.paging else self.why_not("paging")))
        return self.notes.get(capability, "not declared by the family spec")


_REGISTRY: dict[str, FamilySpec] = {}

# family -> module that registers it (lazy: spec() works regardless of
# which repro.models submodule the caller happened to import first)
_FAMILY_MODULES = {
    "dense": "repro.models.transformer",
    "vlm": "repro.models.transformer",
    "moe": "repro.models.moe",
    "mla_moe": "repro.models.mla_moe",
    "ssm": "repro.models.ssm",
    "hybrid": "repro.models.hybrid",
    "audio": "repro.models.encdec",
}


def register(spec: FamilySpec) -> FamilySpec:
    """Register (or re-register) one family spec; returns it."""
    if not spec.family:
        raise ValueError("FamilySpec.family must be a non-empty name")
    _REGISTRY[spec.family] = spec
    return spec


def spec(family_or_cfg) -> FamilySpec:
    """Look up the FamilySpec for a family name or an ArchConfig."""
    family = getattr(family_or_cfg, "family", family_or_cfg)
    if family not in _REGISTRY:
        mod = _FAMILY_MODULES.get(family)
        if mod is not None:
            import_module(mod)          # registration side effect
    if family not in _REGISTRY:
        raise KeyError(
            f"no registered model family {family!r} "
            f"(have {sorted(set(_REGISTRY) | set(_FAMILY_MODULES))})")
    return _REGISTRY[family]


def registered_families() -> tuple[str, ...]:
    """Every registerable family name, importing lazily as needed."""
    for fam in _FAMILY_MODULES:
        spec(fam)
    return tuple(sorted(_REGISTRY))


def families_with(capability: str) -> tuple[str, ...]:
    """Family names declaring ``capability`` True (registry-wide query)."""
    return tuple(f for f in registered_families()
                 if getattr(spec(f), capability))
