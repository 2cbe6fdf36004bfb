"""Logical-axis sharding rules.

Every tensor dimension in the model carries a *logical* name ("batch",
"heads", "mlp", ...). A :class:`RuleSet` maps logical names to an ordered
tuple of physical mesh axes. The resolver assigns mesh axes to dims with two
safety properties:

* **divisibility fallback** — a mesh axis whose size does not divide the dim
  is dropped (e.g. ``kv_heads=10`` over ``model=16`` resolves to replicated),
  never an error;
* **no double-use** — a mesh axis is used by at most one dim of a tensor.

Models call :func:`shard` on activations; parameter shardings are resolved
from per-leaf logical specs. When no mesh context is active (unit tests on
one device) everything is a no-op.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Logical = Tuple[Optional[str], ...]


@dataclass(frozen=True)
class RuleSet:
    """Mapping logical axis name -> ordered physical mesh axes to try."""

    rules: Dict[str, Tuple[str, ...]]

    def get(self, name: Optional[str]) -> Tuple[str, ...]:
        if name is None:
            return ()
        return self.rules.get(name, ())

    def override(self, **kw: Tuple[str, ...]) -> "RuleSet":
        d = dict(self.rules)
        d.update(kw)
        return RuleSet(d)


# ---------------------------------------------------------------------------
# Default rule tables. ``pod`` only exists on the multi-pod mesh; the
# resolver silently skips axes missing from the mesh.
# ---------------------------------------------------------------------------
def train_rules() -> RuleSet:
    return RuleSet({
        # activations
        "batch": ("pod", "data"),
        "seq": (),
        "embed_act": (),
        "heads_act": ("model",),
        "mlp_act": ("model",),
        "vocab_act": ("model",),
        "expert_act": ("model",),
        "kv_seq": ("model",),
        # params: fsdp over (pod,data), tensor-parallel over model
        "p_vocab": ("model",),
        "p_embed": ("pod", "data"),
        "p_heads": ("model",),
        "p_kv_heads": ("model",),
        "p_mlp": ("model",),
        "p_expert": ("model",),
        "p_inner": ("model",),        # mamba d_inner
        "p_state": (),
        "p_head_dim": (),
        "p_ff_fsdp": ("pod", "data"),  # second fsdp-able dim for expert w
    })


def serve_rules(serve_fsdp: bool = False, batch1: bool = False) -> RuleSet:
    fsdp: Tuple[str, ...] = ("pod", "data") if serve_fsdp else ()
    return RuleSet({
        "batch": ("pod", "data"),
        "seq": (),
        "embed_act": (),
        "heads_act": ("model",),
        "mlp_act": ("model",),
        "vocab_act": ("model",),
        "expert_act": ("model",),
        # decode caches: sequence-sharded (flash-decode combine); when
        # batch==1 the data axis is idle, so shard kv_seq over both.
        "kv_seq": ("pod", "data", "model") if batch1 else ("model",),
        "p_vocab": ("model",),
        "p_embed": fsdp,
        "p_heads": ("model",),
        "p_kv_heads": ("model",),
        "p_mlp": ("model",),
        "p_expert": ("model",),
        "p_inner": ("model",),
        "p_state": (),
        "p_head_dim": (),
        "p_ff_fsdp": fsdp,
    })


# ---------------------------------------------------------------------------
# Context.
# ---------------------------------------------------------------------------
class _Ctx(threading.local):
    def __init__(self) -> None:
        self.mesh: Optional[Mesh] = None
        self.rules: Optional[RuleSet] = None


_CTX = _Ctx()


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], rules: Optional[RuleSet]):
    """Activate (mesh, rules) for `shard()` calls during tracing."""
    old = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = old


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


# ---------------------------------------------------------------------------
# Resolution.
# ---------------------------------------------------------------------------
def resolve_spec(shape: Sequence[int], logical: Logical, rules: RuleSet,
                 mesh: Mesh) -> P:
    """Resolve logical names to a PartitionSpec honoring divisibility and
    single-use of mesh axes."""
    assert len(shape) == len(logical), (shape, logical)
    used: set = set()
    out = []
    sizes = getattr(mesh, "axis_sizes", None)
    if sizes is None:  # concrete Mesh without axis_sizes property
        sizes = mesh.devices.shape
    axis_sizes = dict(zip(mesh.axis_names, sizes))
    for dim, name in zip(shape, logical):
        cand = [a for a in rules.get(name)
                if a in axis_sizes and a not in used]
        # Greedily keep a prefix of candidate axes whose product divides dim.
        chosen: list = []
        prod = 1
        for a in cand:
            if dim % (prod * axis_sizes[a]) == 0:
                chosen.append(a)
                prod *= axis_sizes[a]
        for a in chosen:
            used.add(a)
        if not chosen:
            out.append(None)
        elif len(chosen) == 1:
            out.append(chosen[0])
        else:
            out.append(tuple(chosen))
    # Trim trailing Nones (canonical form).
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(shape: Sequence[int], logical: Logical,
                   mesh: Optional[Mesh] = None,
                   rules: Optional[RuleSet] = None) -> Optional[NamedSharding]:
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.rules
    if mesh is None or rules is None:
        return None
    return NamedSharding(mesh, resolve_spec(shape, logical, rules, mesh))


def shard(x: jax.Array, logical: Logical) -> jax.Array:
    """Apply a logical sharding constraint (no-op without mesh context)."""
    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None or rules is None:
        return x
    spec = resolve_spec(x.shape, logical, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_shardings(tree_of_shapes, tree_of_logical, mesh: Mesh,
                   rules: RuleSet):
    """Map (shape-tree, logical-tree) -> NamedSharding tree."""
    return jax.tree.map(
        lambda shp, lg: NamedSharding(mesh, resolve_spec(shp, lg, rules, mesh)),
        tree_of_shapes, tree_of_logical,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (int, str, type(None))) for e in x),
    )
