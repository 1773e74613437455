"""Logical-axis sharding: one rules table maps model-level axis names onto
physical mesh axes (GSPMD/MaxText style).

Models annotate activations/params with LOGICAL axes ("batch", "heads",
"ffn", "vocab", "experts", ...).  The rules decide the physical mapping:

  single-pod mesh (16, 16) = (data, model)
  multi-pod mesh (2, 16, 16) = (pod, data, model)

Parallelism styles expressed purely through rules:
  * DP/FSDP: batch -> (pod, data); fsdp param axis -> (pod, data)
  * TP:      heads/ffn/vocab/experts -> model
  * SP:      seq_kv -> (data,)/(model,) for long-context decode
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Optional, Tuple

import jax
from jax._src.mesh import thread_resources
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis -> physical mesh axis (or tuple, or None=replicated)."""
    batch: tuple[str, ...] | str | None = ("pod", "data")
    seq: tuple[str, ...] | str | None = None          # activation seq axis
    seq_kv: tuple[str, ...] | str | None = None       # KV-cache seq axis (SP)
    d_model: tuple[str, ...] | str | None = None
    heads: tuple[str, ...] | str | None = "model"
    kv_heads: tuple[str, ...] | str | None = "model"
    head_dim: tuple[str, ...] | str | None = None
    ffn: tuple[str, ...] | str | None = "model"
    vocab: tuple[str, ...] | str | None = "model"
    experts: tuple[str, ...] | str | None = "model"
    expert_capacity: tuple[str, ...] | str | None = None
    conv_dim: tuple[str, ...] | str | None = "model"  # mamba inner dim
    state: tuple[str, ...] | str | None = None        # ssm/xlstm state dims
    fsdp: tuple[str, ...] | str | None = ("pod", "data")  # param FSDP axis
    layers: tuple[str, ...] | str | None = None       # stacked-unit axis

    def lookup(self, logical: Optional[str]) -> tuple[str, ...] | str | None:
        if logical is None:
            return None
        try:
            return getattr(self, logical)
        except AttributeError as e:
            raise KeyError(f"unknown logical axis {logical!r}") from e


# Default rules (single-device / test): everything replicated.
REPLICATED_RULES = ShardingRules(
    batch=None, heads=None, kv_heads=None, ffn=None, vocab=None,
    experts=None, conv_dim=None, fsdp=None,
)

_state = threading.local()


def set_rules(rules: Optional[ShardingRules]) -> None:
    _state.rules = rules


def current_rules() -> Optional[ShardingRules]:
    return getattr(_state, "rules", None)


class use_rules:
    """Context manager scoping the active sharding rules."""

    def __init__(self, rules: Optional[ShardingRules]):
        self.rules = rules

    def __enter__(self):
        self.prev = current_rules()
        set_rules(self.rules)
        return self.rules

    def __exit__(self, *exc):
        set_rules(self.prev)
        return False


def _mesh_axes(mesh: Mesh) -> set[str]:
    return set(mesh.axis_names)


def logical_to_spec(
    logical_axes: Tuple[Optional[str], ...],
    rules: Optional[ShardingRules] = None,
    mesh: Optional[Mesh] = None,
) -> P:
    """Resolve logical axis names to a PartitionSpec under the rules.

    Physical axes absent from the mesh are dropped (so the same rules work
    on single-pod (data, model) and multi-pod (pod, data, model) meshes).
    """
    rules = rules or current_rules() or REPLICATED_RULES
    mesh = mesh or _current_mesh()
    avail = _mesh_axes(mesh) if mesh is not None else None

    spec = []
    for ax in logical_axes:
        phys = rules.lookup(ax)
        if phys is None:
            spec.append(None)
            continue
        if isinstance(phys, str):
            phys = (phys,)
        if avail is not None:
            phys = tuple(a for a in phys if a in avail)
        if len(phys) == 0:
            spec.append(None)
        elif len(phys) == 1:
            spec.append(phys[0])
        else:
            spec.append(phys)
    return P(*spec)


def _current_mesh() -> Optional[Mesh]:
    # ``jax.set_mesh`` sets the abstract mesh; ``with mesh:`` only the
    # thread-local resource env.
    env_mesh = jax.sharding.get_abstract_mesh()
    if env_mesh.shape_tuple:
        return env_mesh
    m = thread_resources.env.physical_mesh
    return None if m.empty else m


def logical_constraint(
    x: jax.Array, logical_axes: Tuple[Optional[str], ...]
) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without mesh/rules."""
    rules = current_rules()
    if rules is None:
        return x
    mesh = _current_mesh()
    if mesh is None:
        return x
    spec = logical_to_spec(logical_axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def named_sharding(mesh: Mesh, *logical_axes: Optional[str]) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(tuple(logical_axes), mesh=mesh))
