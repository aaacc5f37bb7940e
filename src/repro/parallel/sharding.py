"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Model code annotates tensors with *logical* axis names; the active rule set
maps them to mesh axes.  Changing distribution strategy (FSDP on/off, TP
degree, sequence parallelism, expert placement) is a rules edit, not a model
edit — which is what makes the §Perf hillclimbs cheap to express.

Mesh axes: ("pod", "data", "model") multi-pod, ("data", "model") single pod.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field, replace

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

MESH_AXES = ("pod", "data", "model")

# logical axis -> mesh axis (or tuple, or None)
DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,            # "model" under sequence parallelism
    "embed": "data",        # FSDP: weight d_model dim sharded over data
    "embed_act": None,      # activation d_model dim (None; "model" under SP)
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_mlp": None,     # d_ff inside experts when experts aren't sharded
    "vocab": "model",
    "state": None,          # SSM / RG-LRU recurrent state dim
    "stage": None,          # layer-stack dim under scan
    "kv_batch": ("pod", "data"),  # KV-cache batch dim
    "kv_seq": None,
}


@dataclass(frozen=True)
class ShardingRules:
    rules: dict = field(default_factory=lambda: dict(DEFAULT_RULES))

    def spec(self, *logical: str | None) -> P:
        return P(*(self.rules.get(ax) if ax is not None else None
                   for ax in logical))

    def with_overrides(self, **kw) -> "ShardingRules":
        new = dict(self.rules)
        new.update(kw)
        return ShardingRules(new)


_state = threading.local()


def current_rules() -> ShardingRules:
    return getattr(_state, "rules", None) or ShardingRules()


@contextmanager
def use_rules(rules: ShardingRules):
    prev = getattr(_state, "rules", None)
    _state.rules = rules
    try:
        yield rules
    finally:
        _state.rules = prev


def logical(*axes: str | None) -> P:
    """PartitionSpec for logical axes under the active rules, pruned to the
    axes that exist in the current mesh (so single-pod meshes accept
    ('pod','data') batch rules transparently)."""
    spec = current_rules().spec(*axes)
    mesh = current_mesh()
    if mesh is None:
        return spec
    names = set(mesh.axis_names)

    def prune(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    return P(*(prune(e) for e in spec))


def current_mesh():
    """The mesh activated by ``jax.set_mesh``, or None outside any mesh
    context (where JAX reports an empty abstract mesh)."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def tree_as_shardings(mesh, tree):
    """Wrap PartitionSpec leaves into NamedSharding on ``mesh`` (None leaves
    pass through), so ``jit`` shardings need no mesh context."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
        tree,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )


def shard(x, *axes: str | None):
    """with_sharding_constraint under the active logical rules (no-op when
    tracing without a mesh).  Axes whose mesh-shard product does not divide
    the tensor dim are pruned — e.g. a 51865-entry vocab stays unsharded on a
    16-way model axis, and batch=1 long-context decode replicates batch."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = prune_spec_for_shape(logical(*axes), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def _axis_size(mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def prune_spec_for_shape(spec: P, shape, mesh) -> P:
    """Drop spec entries that do not evenly divide the corresponding dim, and
    de-duplicate mesh axes (first positional use wins — e.g. under sequence
    parallelism `seq` and `heads` both map to 'model'; the earlier dim keeps
    the sharding)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used: set = set()
    out = []
    for dim, entry in zip(shape, entries):
        names = (entry if isinstance(entry, (tuple, list))
                 else [entry]) if entry is not None else []
        if any(a in used for a in names) or dim % _axis_size(mesh, entry) != 0:
            out.append(None)
            continue
        used.update(names)
        out.append(entry)
    return P(*out)


def prune_tree_specs(spec_tree, abstract_tree, mesh):
    """prune_spec_for_shape over matching pytrees (params/opt-state/caches)."""
    return jax.tree.map(
        lambda s, a: prune_spec_for_shape(s, a.shape, mesh),
        spec_tree, abstract_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def param_specs(tree_axes):
    """Map a pytree of logical-axis tuples to PartitionSpecs."""
    return jax.tree.map(
        lambda axes: logical(*axes),
        tree_axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x),
    )
