"""Plain reference of one federated refit of the MLP router (the paper's
Algorithm 1, FedAvg over client-local AdamW), importing nothing of the
program.

Given the router's parameters before a sync, the stacked client
evaluations and the sync's PRNG key, it replays the same draws as the
deployment's protocol: per round a key split into client selection
(``participation`` of the clients, by a permutation), per-client keys and
an aggregation key; per local step a key split into a minibatch draw
(``batch_size`` rows with replacement from the client's real rows) and the
dropout masks (one per hidden layer, keep probability 1 - dropout); AdamW
with global-norm clipping and decoupled weight decay; weights of the
average proportional to each selected client's row count. Every product
runs in float32 at the highest precision; ``dtype=bfloat16`` is the
control, with parameters, optimizer state and data held in bfloat16.

The trunk's GELU is the exact one (erf), as the published description has
it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


def _trunk(params, x, rng, dropout, prec):
    h = x
    for lyr in params["trunk"]:
        h = jnp.matmul(h, lyr["w"], precision=prec) + lyr["b"]
        mu = h.mean(-1, keepdims=True)
        var = ((h - mu) ** 2).mean(-1, keepdims=True)
        h = (h - mu) * jax.lax.rsqrt(var + 1e-5) * lyr["ln_s"] + lyr["ln_b"]
        h = _gelu(h)
        if dropout > 0.0:
            rng, sub = jax.random.split(rng)
            keep = jax.random.bernoulli(sub, 1.0 - dropout, h.shape)
            h = jnp.where(keep, h / (1.0 - dropout), 0.0)
    return h


def loss(params, batch, rng, dropout, prec):
    """Mean over real rows of the squared errors of the logged model's
    predicted accuracy (sigmoid head) and cost (linear head)."""
    h = _trunk(params, batch["x"], rng, dropout, prec)
    hd = params["heads"]
    A = jax.nn.sigmoid(jnp.matmul(h, hd["acc_w"], precision=prec)
                       + hd["acc_b"])
    C = jnp.matmul(h, hd["cost_w"], precision=prec) + hd["cost_b"]
    m = batch["m"][:, None]
    a = jnp.take_along_axis(A, m, 1)[:, 0]
    c = jnp.take_along_axis(C, m, 1)[:, 0]
    err = (a - batch["acc"]) ** 2 + (c - batch["cost"]) ** 2
    w = batch["w"]
    return jnp.sum(err * w) / jnp.maximum(jnp.sum(w), 1.0)


def _adamw(p, g, m, v, step, fc):
    lr, b1, b2, eps = fc["lr"], 0.9, 0.999, 1e-8
    leaves = jax.tree.leaves(g)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                      for x in leaves))
    scale = jnp.minimum(1.0, fc["clip_norm"] / jnp.maximum(gn, 1e-12))
    g = jax.tree.map(lambda x: x * scale.astype(x.dtype), g)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    t = step.astype(jnp.float32)
    mh, vh = 1.0 / (1 - b1 ** t), 1.0 / (1 - b2 ** t)
    p = jax.tree.map(lambda w, a, b: (w - lr * (
        (a * mh) / (jnp.sqrt(b * vh) + eps) + fc["weight_decay"] * w)
    ).astype(w.dtype), p, m, v)
    return p, m, v


def _client(params, d, key, fc, steps, dropout, prec):
    n = jnp.sum(d["w"]).astype(jnp.int32)
    n_active = jnp.ceil(n / fc["batch_size"]).astype(jnp.int32)
    zeros = jax.tree.map(jnp.zeros_like, params)

    def step(carry, s):
        p, m, v, key = carry
        key, k_idx, k_drop = jax.random.split(key, 3)
        idx = jax.random.randint(k_idx, (fc["batch_size"],), 0,
                                 jnp.maximum(n, 1))
        batch = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), d)
        lv, g = jax.value_and_grad(loss)(p, batch, k_drop, dropout, prec)
        p2, m2, v2 = _adamw(p, g, m, v, (s + 1).astype(jnp.int32), fc)
        on = s < n_active
        pick = lambda a, b: jax.tree.map(lambda x, y: jnp.where(on, x, y),
                                         a, b)
        return (pick(p2, p), pick(m2, m), pick(v2, v), key), lv

    (p, _, _, _), losses = jax.lax.scan(step, (params, zeros, zeros, key),
                                        jnp.arange(steps))
    return p, jnp.mean(losses)


@functools.partial(jax.jit, static_argnames=("fc_items", "rounds", "steps",
                                             "dropout", "dtype"))
def sync(params, data, key, *, fc_items, rounds, steps, dropout, dtype):
    """One refit: ``rounds`` FedAvg rounds from ``params`` over the stacked
    client data. Returns (new params, per-round losses)."""
    fc = dict(fc_items)
    low = dtype == "bfloat16"
    prec = None if low else HI
    cast = (lambda t: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        t)) if low else (lambda t: t)
    params, data = cast(params), cast(data)
    N = data["x"].shape[0]
    n_sel = max(1, int(round(fc["participation"] * N)))
    key, _ = jax.random.split(key)             # the fit's own init key

    def one_round(carry, _):
        p, key = carry
        key, k_r = jax.random.split(key)
        _, k_sel, k_cli, _ = jax.random.split(k_r, 4)
        perm = jax.random.permutation(k_sel, N)
        active = jnp.zeros((N,)).at[perm[:n_sel]].set(1.0)
        cp, cl = jax.vmap(lambda d, k: _client(p, d, k, fc, steps, dropout,
                                               prec))(
            data, jax.random.split(k_cli, N))
        wts = jnp.sum(data["w"].astype(jnp.float32), -1) * active
        wn = wts / jnp.maximum(jnp.sum(wts), 1e-12)
        newp = jax.tree.map(lambda s, o: jnp.tensordot(
            wn.astype(s.dtype), s, axes=1, precision=prec).astype(o.dtype),
            cp, p)
        return (newp, key), jnp.sum(cl.astype(jnp.float32) * wn)

    (params, _), losses = jax.lax.scan(one_round, (params, key), None,
                                       length=rounds)
    return jax.tree.map(lambda a: a.astype(jnp.float32), params), losses


def first_grad(params, data, key, *, fc_items, dropout):
    """Per-leaf norms of the first client-local gradient (client 0, first
    minibatch) in float32: the rule that leaves out leaves whose gradient
    is nought to rounding reads it."""
    fc = dict(fc_items)
    n = jnp.sum(data["w"][0]).astype(jnp.int32)
    d = jax.tree.map(lambda a: a[0], data)
    k_idx, k_drop = jax.random.split(key)
    idx = jax.random.randint(k_idx, (fc["batch_size"],), 0, jnp.maximum(n, 1))
    batch = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), d)
    g = jax.grad(loss)(params, batch, k_drop, dropout, HI)
    return jax.tree.map(lambda a: float(jnp.linalg.norm(a)), g)
