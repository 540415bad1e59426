"""Weights made from the seed, on the device, in the type they are served
in: one jitted call per model and one for the router.

The program supplies only the layout (``jax.eval_shape`` of its own
initialiser gives the tree of shapes); every value is drawn here. Norm
scales are drawn around 1 and the QKV biases away from 0, so that a path
that skipped them would not agree with the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: leaves larger than this are drawn in slices (f32 draws, then a cast)
_SLICE_ELEMS = 1 << 25


def model_config(m: dict):
    """The program's ModelConfig for a model's config keys."""
    from repro.config import ModelConfig
    return ModelConfig(
        name=f"{m['architectures'][0]}-{m['num_hidden_layers']}L"
             f"-{m['hidden_size']}",
        arch_type="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], head_dim=m["head_dim"],
        qk_norm=m["qk_norm"], qkv_bias=m["attention_bias"],
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
        dtype=m["torch_dtype"], tie_embeddings=False)


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
def _model_maker(m_items):
    from repro.models import init_params
    m = dict(m_items)
    cfg = model_config(m)
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
            a = _model_leaf(jax.random.fold_in(key, i), names, s.shape,
                            s.dtype, m)
            by_name[tuple(names)] = a
            leaves.append(a)
        if tied:   # the output head is the token table, transposed
            tok = by_name[("embed", "tok")]
            leaves = [tok.T if a is None else a for a in leaves]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return cfg, jax.jit(make)


#: the configuration keys a model is built from
MODEL_KEYS = ("architectures", "num_hidden_layers", "hidden_size",
              "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "vocab_size", "rope_theta",
              "rms_norm_eps", "attention_bias", "qk_norm",
              "tie_word_embeddings", "torch_dtype")


def _frozen(d: dict):
    """The model's keys as a hashable tuple."""
    return tuple((k, tuple(d[k]) if isinstance(d[k], list) else d[k])
                 for k in MODEL_KEYS)


def model_params(m: dict, seed: int):
    """(ModelConfig, params) of one model from ``seed``."""
    cfg, make = _model_maker(_frozen(m))
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
