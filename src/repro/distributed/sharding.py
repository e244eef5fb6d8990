"""Logical-axis -> mesh PartitionSpec rules.

Every parameter / activation in the model zoo declares *logical dims*
(e.g. ``("D", "F")`` for an MLP weight, ``("L", "E", "D", "F")`` for stacked
MoE experts).  This module maps those names onto the physical mesh axes
(``pod``/``data``/``model``) with divisibility checks, greedy conflict
resolution (one mesh axis may appear at most once per tensor) and a
context-managed rule table so serving and training can use different
layouts without touching model code.

The defaults implement:
  - TP over ``model`` for heads / d_ff / experts / vocab,
  - FSDP over ``data`` for the d_model rows (ZeRO-style param+opt sharding),
  - batch over ``(pod, data)``,
  - KV-pool sequence axis over ``model`` (the pooled-HBM capacity axis),
  - sequence-parallel residual stream over ``model`` during training.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# rule table: logical dim -> ordered mesh-axis preference
# ---------------------------------------------------------------------------

# Axis name conventions used across the model zoo:
#   B   batch                      S   sequence (activations)
#   SP  pool sequence (KV pool)    D   d_model (rows)
#   H   attention heads (fused)    KV  kv heads (fused)
#   F   ffn hidden                 E   experts
#   V   vocab                      L   stacked layer axis (never sharded)
#   C   latent / small dims        Hm  ssm heads
#   K   top-k axis (never sharded)

TRAIN_RULES: Dict[str, Tuple[str, ...]] = {
    "B": ("pod", "data"),
    "S": ("model",),          # sequence-parallel residual stream
    "Sq": (),                 # sequence axis inside attention (heads take TP)
    "SP": ("model",),
    "D": ("data",),           # FSDP rows (ZeRO param+opt sharding)
    "DE": ("data",),          # expert-weight rows (always capacity-sharded)
    "H": ("model",),
    "Hq": ("model",),         # head axis of attention activations
    "KV": ("model",),
    "F": ("model",),
    "E": ("model", "data"),
    "V": ("model",),
    "Hm": ("model",),
    "G": (),                  # small/replicated dims (norm gammas, head_dim)
    "L": (),                  # stacked-layer axes are never sharded
    "C": (),                  # latent / low-rank dims
    "K": (),                  # top-k axis
}

SERVE_RULES: Dict[str, Tuple[str, ...]] = {
    "B": ("pod", "data"),     # DP attention: each request on one data shard
    "S": ("model",),
    "Sq": (),
    "SP": ("model",),         # pool pages spread over the pooled-HBM axis
    "D": (),                  # NO row-sharding at serve: FSDP rows force a
                              # per-layer weight all-gather in decode
                              # (§Perf iteration A1); TP over model suffices
    "DE": ("data",),          # expert rows stay sharded (capacity: MoE
                              # weights are the TB-scale tensors)
    "H": ("model",),
    "Hq": ("model",),
    "KV": ("model",),
    "F": ("model",),
    "E": ("model", "data"),
    "V": ("model",),
    "Hm": ("model",),
    "G": (),
    "L": (),
    "C": (),
    "K": (),
}

_state = threading.local()


def _rules() -> Dict[str, Tuple[str, ...]]:
    return getattr(_state, "rules", TRAIN_RULES)


def _mesh() -> Optional[Mesh]:
    m = getattr(_state, "mesh", None)
    if m is not None:
        return m
    # fall back to the ambient mesh if one is active
    env = jax.sharding.get_abstract_mesh()
    return env if env and env.shape_tuple else None


@contextlib.contextmanager
def use_rules(rules: Dict[str, Tuple[str, ...]], mesh: Optional[Mesh] = None):
    old_r = getattr(_state, "rules", None)
    old_m = getattr(_state, "mesh", None)
    _state.rules = rules
    _state.mesh = mesh
    try:
        yield
    finally:
        if old_r is None:
            del _state.rules
        else:
            _state.rules = old_r
        _state.mesh = old_m


# ---------------------------------------------------------------------------
# spec derivation
# ---------------------------------------------------------------------------


def spec_for(dims: Sequence[str], shape: Sequence[int],
             mesh: Optional[Mesh] = None,
             rules: Optional[Dict[str, Tuple[str, ...]]] = None) -> P:
    """Derive a PartitionSpec for logical ``dims`` of ``shape``.

    Greedy: walk dims left to right; give each dim the first mesh axis from
    its preference list that (a) is present in the mesh, (b) is still unused
    in this tensor, and (c) divides the dim size.  Multi-axis entries (e.g.
    batch over ("pod", "data")) are taken as a group when every member
    divides cumulatively.
    """
    mesh = mesh or _mesh()
    rules = rules or _rules()
    if mesh is None:
        return P(*([None] * len(dims)))
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) \
        if isinstance(mesh, Mesh) else dict(mesh.shape_tuple)
    used: set = set()
    out: List[Optional[Tuple[str, ...]]] = []
    for dim, size in zip(dims, shape):
        prefs = rules.get(dim, ())
        picked: List[str] = []
        rem = size
        for ax in prefs:
            if ax not in axis_sizes or ax in used:
                continue
            n = axis_sizes[ax]
            if rem % n == 0:
                picked.append(ax)
                used.add(ax)
                rem //= n
        out.append(tuple(picked) if picked else None)
    return P(*out)


def named_sharding(mesh: Mesh, dims: Sequence[str], shape: Sequence[int],
                   rules: Optional[Dict[str, Tuple[str, ...]]] = None
                   ) -> NamedSharding:
    return NamedSharding(mesh, spec_for(dims, shape, mesh=mesh, rules=rules))


def constrain(x, dims: Sequence[str]):
    """with_sharding_constraint from logical dims (no-op without a mesh)."""
    mesh = _mesh()
    if mesh is None:
        return x
    spec = spec_for(dims, x.shape, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec) if isinstance(mesh, Mesh) else spec)


def params_shardings(specs_tree, mesh: Mesh, rules=None):
    """ParamSpec pytree -> NamedSharding pytree (same structure)."""
    from repro.models.layers import ParamSpec

    def one(s: ParamSpec):
        return named_sharding(mesh, s.dims, s.shape, rules=rules)

    return jax.tree.map(one, specs_tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))
