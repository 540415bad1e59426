"""Weights made from the seed, on the device, in the type they are served
in: one jitted call per model and one for the router.

The program supplies only the layout (``jax.eval_shape`` of its own
initialiser, for the ``ModelConfig`` the model's family gives, gives the
tree of shapes); every value is drawn here, by ``_model_leaf`` or, for the
leaves a family draws itself, by its ``leaf``. Norm scales are drawn
around 1 and the QKV biases away from 0, so that a path that skipped them
would not agree with the reference.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

#: leaves larger than this are drawn in slices (f32 draws, then a cast)
_SLICE_ELEMS = 1 << 25


def _names(path) -> list:
    return [p.key if hasattr(p, "key") else str(p.idx) for p in path]


def _draw(key, shape, scale, dtype):
    """scale * N(0, 1) of ``shape`` in ``dtype``, drawn in slices along the
    leading axis so that no f32 temporary of a large leaf is live."""
    size = int(np.prod(shape))
    n = 1
    if size > _SLICE_ELEMS:
        lead = shape[0]
        n = next(d for d in range(1, lead + 1)
                 if lead % d == 0 and size // d <= _SLICE_ELEMS or d == lead)
    if n == 1:
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(
            dtype)
    sub = (shape[0] // n,) + tuple(shape[1:])
    out = jax.lax.map(lambda k: (scale * jax.random.normal(
        k, sub, jnp.float32)).astype(dtype), jax.random.split(key, n))
    return out.reshape(shape)


def _model_leaf(key, names, shape, dtype, m):
    leaf = names[-1]
    if leaf == "scale":                               # every RMSNorm
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    if leaf in ("bq", "bk", "bv"):
        return (0.3 * jax.random.normal(key, shape)).astype(dtype)
    if leaf == "tok":
        return _draw(key, shape, 0.02, dtype)
    if leaf == "unembed":
        return _draw(key, shape, m["hidden_size"] ** -0.5, dtype)
    return _draw(key, shape, shape[-2] ** -0.5, dtype)   # (.., in, out)


@functools.lru_cache(maxsize=None)
def _model_maker(fam, m_json, sharding=None):
    from repro.models import init_params
    m = json.loads(m_json)
    cfg = fam.model_config(m)
    own = getattr(fam, "leaf", None)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    tied = m["tie_word_embeddings"]

    def make(key):
        leaves, by_name = [], {}
        for i, (path, s) in enumerate(flat):
            names = _names(path)
            if tied and names[-1] == "unembed":
                leaves.append(None)
                continue
            k = jax.random.fold_in(key, i)
            a = own(k, names, s.shape, s.dtype, m) if own else None
            if a is None:
                a = _model_leaf(k, names, s.shape, s.dtype, m)
            by_name[tuple(names)] = a
            leaves.append(a)
        if tied:   # the output head is the token table, transposed
            tok = by_name[("embed", "tok")]
            leaves = [tok.T if a is None else a for a in leaves]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    if sharding is None:
        return cfg, jax.jit(make)
    return cfg, jax.jit(make, out_shardings=sharding)


def model_params(fam, m: dict, seed: int, sharding=None):
    """(ModelConfig, params) of one model of family ``fam`` (a module under
    ``bench/models/``) from ``seed``. With ``sharding`` (a mesh's
    replicated ``NamedSharding``), every leaf is made in place on every
    chip of the mesh, and no chip ever holds a second copy."""
    cfg, make = _model_maker(fam, json.dumps(m, sort_keys=True), sharding)
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, seed >> 31)
    return cfg, make(key)


def router_state(rcfg, quality_logits, costs, seed: int,
                 head_scale: float = 1.0):
    """The MLP router's state: trunk and heads drawn from ``seed``. The
    heads' biases put each lane's accuracy logit and cost where the
    configuration says; their weights are scaled by ``head_scale`` and
    centred over the trunk's features (whose GELU outputs share a positive
    mean), so that the split between lanes is set by lambda and the
    biases, and only its edges by the query and the seed."""
    from repro.core.mlp_router import init_mlp_router
    M = len(costs)
    shapes = jax.eval_shape(lambda k: init_mlp_router(k, rcfg, M),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    qb = jnp.asarray(quality_logits, jnp.float32)
    cb = jnp.asarray(costs, jnp.float32)

    @jax.jit
    def make(key):
        out = []
        for i, (path, s) in enumerate(flat):
            leaf = _names(path)[-1]
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, s.shape, s.dtype)
            if leaf == "w":
                out.append(z * s.shape[0] ** -0.5)
            elif leaf in ("acc_w", "cost_w"):
                z = z - z.mean(axis=0, keepdims=True)
                out.append(head_scale * z * s.shape[0] ** -0.5)
            elif leaf == "ln_s":
                out.append(1.0 + 0.1 * z)
            elif leaf == "acc_b":
                out.append(qb + 0.1 * z)
            elif leaf == "cost_b":
                out.append(cb + 0.1 * z)
            else:                                    # b, ln_b
                out.append(0.1 * z)
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.fold_in(jax.random.PRNGKey(1), seed & 0x7FFFFFFF)
    return make(jax.random.fold_in(key, seed >> 31))


def balance_router(state, x, lam: float):
    """Shift the first lane's cost bias so that, at ``lam``, half of the
    queries ``x`` prefer each of two lanes (the median utility gap is 0):
    a router tuned to an operating point, whatever the seed drew. The
    utilities are the float32 reference's."""
    import numpy as np
    from bench import reference
    U = reference.router_utility(state, x, np.full(len(x), lam))
    shift = float(np.median(U[:, 0] - U[:, 1])) / lam
    heads = dict(state["heads"], cost_b=state["heads"]["cost_b"].at[0].add(
        shift))
    return dict(state, heads=heads)
