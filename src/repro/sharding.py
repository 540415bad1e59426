"""Mesh construction + logical-axis sharding for the whole stack.

Two layers live here:

* **Logical-axis rules** (``use_rules``/``constrain``): model code annotates
  activations with *logical* axis names; a rule set maps them to mesh axes
  at launch time. Outside any ``use_rules`` context (unit tests, CPU smoke
  runs) ``constrain`` is the identity, so the model code is mesh-agnostic.
* **Mesh helpers** (``client_mesh``/``head_mesh``/``data_mesh`` +
  ``shard_clients``/``replicate``/``named``): the cross-silo execution
  layer. The federated fit shards the stacked ``(N, …)`` client slab over a
  1-D ``"clients"`` axis and runs under ``shard_map``
  (``core.federated.fedavg_round_sharded``); the serve engine shards its KV
  pools over ``"heads"`` (tensor-parallel attention) and/or ``"data"``
  (slot-parallel decode) via plain GSPMD propagation from the pool
  placement. ``ENGINE_RULES`` maps the logical names the attention code
  already annotates (``constrain`` calls in ``models/attention.py``) onto
  those mesh axes.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Sequence[str], None]


def shard_map(f, mesh: Mesh, in_specs, out_specs, *, check: bool = False):
    """``jax.shard_map`` with the replication check off by default — the
    one call site every sharded path (fits, expert-parallel MoE) uses."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)

_CURRENT: Optional[tuple] = None  # (mesh, rules: dict[str, Axis])


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: dict):
    global _CURRENT
    prev = _CURRENT
    _CURRENT = (mesh, rules)
    try:
        yield
    finally:
        _CURRENT = prev


def active() -> bool:
    return _CURRENT is not None


def _axis_size(mesh: Mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis]
    return int(np.prod([mesh.shape[a] for a in axis]))


def resolve_spec(logical: Sequence, shape: Sequence[int]) -> Optional[P]:
    """Map logical axis names to a PartitionSpec under the active rules,
    dropping axes whose size does not divide the dimension. A trailing "!"
    on a logical name opts into uneven (GSPMD-padded) sharding — used e.g.
    to shard 56 attention heads over a 16-way axis (4 chips idle-padded)."""
    if _CURRENT is None:
        return None
    mesh, rules = _CURRENT
    out = []
    for dim, name in zip(shape, logical):
        uneven = isinstance(name, str) and name.endswith("!")
        key = name[:-1] if uneven else name
        axis = rules.get(key) if key is not None else None
        if axis is not None:
            # a rule naming an axis the live mesh doesn't carry (e.g.
            # ENGINE_RULES' "heads" on a 1-D data mesh) replicates
            names = (axis,) if isinstance(axis, str) else tuple(axis)
            if any(a not in mesh.shape for a in names):
                axis = None
        if axis is not None and not uneven \
                and dim % _axis_size(mesh, axis) != 0:
            axis = None  # non-divisible → replicate this dim
        out.append(axis)
    return P(*out)


def constrain(x: jax.Array, logical: Sequence) -> jax.Array:
    """Annotate x with the sharding implied by logical axis names."""
    if _CURRENT is None:
        return x
    mesh, _ = _CURRENT
    spec = resolve_spec(logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def kernel_call(f, args: Sequence, logical: Sequence[Sequence]):
    """Call a Pallas kernel wrapper ``f(*args)`` under the active rules.
    XLA cannot partition a Mosaic kernel, so on a mesh the call runs per
    device under ``shard_map``: each operand is split along the mesh axes
    its logical names resolve to (``resolve_spec`` — non-dividing dims
    replicate), and the output is laid out like the first operand. Outside
    any ``use_rules`` context it is ``f(*args)``."""
    if _CURRENT is None:
        return f(*args)
    mesh, _ = _CURRENT
    specs = tuple(resolve_spec(lg, jax.numpy.shape(a))
                  for lg, a in zip(logical, args))
    return shard_map(f, mesh, specs, specs[0])(*args)


# ---------------------------------------------------------------------------
# Mesh construction (the cross-silo execution layer)
# ---------------------------------------------------------------------------

#: default logical→mesh rules for the mesh-sharded serve engine: the
#: attention code's existing annotations map heads onto the "heads" axis
#: (tensor-parallel) and the batch/slot dim onto "data" (slot-parallel).
#: ``heads4d`` is the uneven-shardable 4-D head annotation attention uses.
ENGINE_RULES = {"heads": "heads", "heads4d": "heads", "batch": "data"}


def make_mesh(shape: dict, *, devices=None) -> Mesh:
    """Build a mesh from ``{axis_name: size}`` over the first
    ``prod(sizes)`` local devices (or an explicit device list). Raises a
    clear error when the host has too few devices — on CPU, force more
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set
    before jax initializes)."""
    names = tuple(shape)
    sizes = tuple(int(shape[n]) for n in names)
    need = int(np.prod(sizes))
    devices = list(jax.devices()) if devices is None else list(devices)
    if need > len(devices):
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {need} devices, host has "
            f"{len(devices)} — on CPU set XLA_FLAGS="
            f"'--xla_force_host_platform_device_count={need}' before jax "
            "initializes")
    arr = np.asarray(devices[:need]).reshape(sizes)
    return Mesh(arr, names)


def client_mesh(n_devices: Optional[int] = None, *, devices=None) -> Mesh:
    """1-D ``("clients",)`` mesh for the sharded federated fit — each
    device owns a contiguous block of the stacked client slab."""
    n = n_devices if n_devices is not None else len(
        devices if devices is not None else jax.devices())
    return make_mesh({"clients": n}, devices=devices)


def head_mesh(n_devices: Optional[int] = None, *, devices=None) -> Mesh:
    """1-D ``("heads",)`` mesh: tensor-parallel attention heads for the
    serve engine (KV pool leaves sharded over their Hkv dim)."""
    n = n_devices if n_devices is not None else len(
        devices if devices is not None else jax.devices())
    return make_mesh({"heads": n}, devices=devices)


def data_mesh(n_devices: Optional[int] = None, *, devices=None) -> Mesh:
    """1-D ``("data",)`` mesh: slot-parallel decode for the serve engine
    (pool leaves sharded over their slot/batch dim; per-slot math is
    untouched, so tokens stay bit-identical to the solo engine)."""
    n = n_devices if n_devices is not None else len(
        devices if devices is not None else jax.devices())
    return make_mesh({"data": n}, devices=devices)


def named(mesh: Mesh, *spec) -> NamedSharding:
    """Shorthand: ``named(mesh, None, "clients")`` ≡
    ``NamedSharding(mesh, P(None, "clients"))``."""
    return NamedSharding(mesh, P(*spec))


def replicate(tree, mesh: Mesh):
    """device_put every leaf fully replicated over ``mesh``."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def shard_leading(tree, mesh: Mesh, axis: str):
    """device_put every leaf with its leading dim sharded over mesh axis
    ``axis`` (replicated when the dim doesn't divide the axis — a clear
    error beats silent GSPMD padding for the client slab, so callers that
    require even sharding should check first)."""
    n = mesh.shape[axis]

    def put(a):
        a = jax.numpy.asarray(a) if not hasattr(a, "shape") else a
        spec = P(axis) if a.ndim and a.shape[0] % n == 0 else P()
        return jax.device_put(a, NamedSharding(mesh, spec))

    return jax.tree.map(put, tree)


def shard_clients(data, mesh: Mesh):
    """Place a stacked federated dataset ``{"x": (N, D, d), ...}`` with the
    client axis sharded over the mesh's ``"clients"`` axis — each device
    holds N/n_dev clients, no full replication. Requires N divisible by the
    axis size (``pad_client_axis`` in ``core.federated`` pads a ragged
    stack up)."""
    N = jax.tree.leaves(data)[0].shape[0]
    n = mesh.shape["clients"]
    if N % n != 0:
        raise ValueError(
            f"client stack N={N} does not divide the clients mesh axis "
            f"({n}) — pad the stack (core.federated.pad_client_axis) or "
            "resize the mesh")
    return jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("clients"))),
        data)


def kv_pool_spec(leaf_ndim: int, mesh: Mesh, leaf_shape=None, *,
                 paged: bool = False) -> P:
    """PartitionSpec for a KV-pool leaf: 5-D pool leaves are
    ``(n_units, slots|pages, Hkv, seq, hd)`` — shard the slot dim over
    ``"data"`` and/or the head dim over ``"heads"`` when the mesh carries
    those axes and the dim divides; everything else replicates. A paged
    pool's page dim always replicates: any decode row may read any page,
    so a page-sharded pool would be gathered whole into every device's
    kernel call. Non-5-D leaves (SSM states etc.) shard their dim-1 batch
    over ``"data"`` only."""
    axes = dict(mesh.shape)

    def fits(dim_size, ax):
        return ax in axes and dim_size is not None \
            and dim_size % axes[ax] == 0

    shape = leaf_shape if leaf_shape is not None else [None] * leaf_ndim
    spec = [None] * leaf_ndim
    if leaf_ndim >= 2 and not paged and fits(shape[1], "data"):
        spec[1] = "data"
    if leaf_ndim == 5 and fits(shape[2], "heads"):
        spec[2] = "heads"
    return P(*spec)


def shard_kv_pool(pool, mesh: Mesh, *, paged: bool = False):
    """device_put a KV pool (slot or page regime) with each leaf sharded
    per ``kv_pool_spec`` — the engine's mesh placement."""
    return jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(
            mesh, kv_pool_spec(a.ndim, mesh, a.shape, paged=paged))),
        pool)
