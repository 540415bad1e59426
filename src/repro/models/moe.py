"""Mixture-of-Experts layer with top-k token-choice routing.

Two dispatch implementations (selected by ``mode``):

  * ``dense``    — computes every expert for every token and masks by the
                   top-k gate. Semantically exact (no token dropping), but
                   does E/top_k × extra FLOPs. This is the naive baseline the
                   §Perf log starts from.
  * ``capacity`` — Switch/GShard-style: tokens are sorted by expert id and
                   scattered into an (E, C, d) buffer (capacity
                   C = ceil(T·top_k·cf / E)); experts run as batched matmuls
                   (MXU-friendly); outputs are gathered back and combined
                   with the gate weights. Overflowing tokens are dropped —
                   the production-realistic TPU dispatch (pre-megablox).

Expert weights carry a leading E dim and are sharded over the "model" mesh
axis (expert parallelism); token dims shard over ("pod","data").

The router load-balance auxiliary loss (Switch eq. 4) is returned alongside.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.sharding import constrain, shard_map


def init_moe(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_expert, m.num_experts
    dt = L.dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in, s_out = d ** -0.5, f ** -0.5
    return {
        "router": L._normal(k1, (d, E), s_in, jnp.float32),
        "wg": L._normal(k2, (E, d, f), s_in, dt),
        "wu": L._normal(k3, (E, d, f), s_in, dt),
        "wd": L._normal(k4, (E, f, d), s_out, dt),
    }


def _router_probs(p, x, cfg: ModelConfig):
    """x: (T, d) → top-k (weights (T,k), ids (T,k)), full probs (T,E)."""
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, cfg.moe.top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_w, top_ids, probs


def _aux_loss(probs, top_ids, cfg: ModelConfig):
    E = cfg.moe.num_experts
    # fraction of tokens dispatched to each expert (first choice proxy)
    counts = jnp.mean(jax.nn.one_hot(top_ids[:, 0], E, dtype=jnp.float32), 0)
    imp = jnp.mean(probs, axis=0)
    return E * jnp.sum(counts * imp)


def moe_forward(p: dict, x: jnp.ndarray, cfg: ModelConfig, *,
                mode: str = "dense", capacity_factor: float = 1.25) -> tuple:
    """x: (B, S, d) → (out (B,S,d), aux_loss scalar).

    On a mesh (active sharding rules), mode="capacity" runs the dispatch
    inside ``shard_map``: the sort/scatter machinery stays LOCAL to each
    data shard and each model-column computes only its expert slice; the
    only cross-chip traffic is the FSDP weight all-gather and one psum of
    the (T_loc, d) outputs over the expert axis. (A naive pjit capacity
    dispatch makes XLA all-gather the global sort — measured 50× worse;
    see EXPERIMENTS.md §Perf H1.)
    """
    import repro.sharding as shd

    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    if mode == "capacity" and shd.active():
        out, aux = _capacity_shard_map(p, xt, cfg, capacity_factor)
        return out.reshape(B, S, d).astype(x.dtype), aux * cfg.moe.aux_coef
    top_w, top_ids, probs = _router_probs(p, xt, cfg)
    aux = _aux_loss(probs, top_ids, cfg) * cfg.moe.aux_coef
    if mode == "dense":
        out = _dense_dispatch(p, xt, top_w, top_ids, cfg)
    elif mode == "capacity":
        out = _capacity_dispatch(p, xt, top_w, top_ids, cfg, capacity_factor)
    else:
        raise ValueError(f"unknown moe mode {mode!r}")
    return out.reshape(B, S, d).astype(x.dtype), aux


def _expert_mlp(p, xe):
    """xe: (E, C, d) → (E, C, d); batched SwiGLU over the expert dim."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["wg"]))
    u = jnp.einsum("ecd,edf->ecf", xe, p["wu"])
    return jnp.einsum("ecf,efd->ecd", g * u, p["wd"])


def _dense_dispatch(p, xt, top_w, top_ids, cfg: ModelConfig):
    E = cfg.moe.num_experts
    T, d = xt.shape
    # gate (T, E): top-k weights scattered into full expert dim
    gate = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], top_ids].add(top_w)
    # all-experts compute: (T, E, f) intermediate
    g = jax.nn.silu(constrain(jnp.einsum("td,edf->tef", xt, p["wg"]),
                              ("tokens", "experts", None)))
    u = constrain(jnp.einsum("td,edf->tef", xt, p["wu"]),
                  ("tokens", "experts", None))
    y = constrain(jnp.einsum("tef,efd->ted", g * u, p["wd"]),
                  ("tokens", "experts", None))
    return jnp.einsum("ted,te->td", y, gate.astype(y.dtype))


def _capacity_shard_map(p, xt, cfg: ModelConfig, cf: float):
    """Expert-parallel capacity dispatch under shard_map (see moe_forward).

    Layout: tokens sharded over the batch axes, experts over the expert
    ("model") axis, expert weights FSDP-sharded on d over "data" and
    all-gathered inside the block (the per-layer FSDP gather).
    """
    import functools

    import repro.sharding as shd
    from jax.sharding import PartitionSpec as P

    mesh, rules = shd._CURRENT
    tok_ax = rules.get("tokens")
    exp_ax = rules.get("experts")
    E, k = cfg.moe.num_experts, cfg.moe.top_k
    n_exp_shards = mesh.shape[exp_ax] if exp_ax else 1
    if exp_ax is None or E % n_exp_shards != 0:
        # cannot expert-shard — fall back to the single-block path
        top_w, top_ids, probs = _router_probs(p, xt, cfg)
        return (_capacity_dispatch(p, xt, top_w, top_ids, cfg, cf),
                _aux_loss(probs, top_ids, cfg))

    fsdp_ax = "data"
    w_specs = {
        "router": P(None, None),
        "wg": P(exp_ax, fsdp_ax, None),
        "wu": P(exp_ax, fsdp_ax, None),
        "wd": P(exp_ax, None, fsdp_ax),
    }

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(w_specs, P(tok_ax, None)),
                       out_specs=(P(tok_ax, None), P()))
    def block(w, xt_loc):
        # FSDP all-gather of this layer's expert-shard weights
        wg = jax.lax.all_gather(w["wg"], fsdp_ax, axis=1, tiled=True)
        wu = jax.lax.all_gather(w["wu"], fsdp_ax, axis=1, tiled=True)
        wd = jax.lax.all_gather(w["wd"], fsdp_ax, axis=2, tiled=True)
        E_loc = wg.shape[0]
        T_loc = xt_loc.shape[0]

        top_w, top_ids, probs = _router_probs(w, xt_loc, cfg)
        lo = jax.lax.axis_index(exp_ax) * E_loc
        local = (top_ids >= lo) & (top_ids < lo + E_loc)
        ids_loc = jnp.where(local, top_ids - lo, E_loc)  # E_loc = drop bucket
        w_loc = jnp.where(local, top_w, 0.0)

        C = max(1, int(T_loc * k * cf) // E)
        flat_e = ids_loc.reshape(-1)
        flat_w = w_loc.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T_loc), k)
        order = jnp.argsort(flat_e)
        se, sw, stk = flat_e[order], flat_w[order], flat_t[order]
        counts = jnp.bincount(flat_e, length=E_loc + 1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(T_loc * k) - starts[se]
        keep = (pos < C) & (se < E_loc)
        pos_c = jnp.where(keep, pos, 0)
        se_c = jnp.where(keep, se, 0)

        buf = jnp.zeros((E_loc, C, xt_loc.shape[1]), xt_loc.dtype)
        buf = buf.at[se_c, pos_c].add(
            jnp.where(keep[:, None], xt_loc[stk], 0), mode="drop")
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        ye = jnp.einsum("ecf,efd->ecd", g * u, wd)
        y_tok = ye[se_c, pos_c] * jnp.where(keep, sw, 0.0)[:, None].astype(
            ye.dtype)
        out = jnp.zeros_like(xt_loc).at[stk].add(y_tok, mode="drop")
        out = jax.lax.psum(out, exp_ax)
        # aux loss (Switch eq. 4) is bilinear in two means — pmean the means
        # over token shards BEFORE the product, so it matches the global term
        counts = jnp.mean(jax.nn.one_hot(top_ids[:, 0], E,
                                         dtype=jnp.float32), 0)
        imp = jnp.mean(probs, axis=0)
        if tok_ax:
            counts = jax.lax.pmean(counts, tok_ax)
            imp = jax.lax.pmean(imp, tok_ax)
        aux = E * jnp.sum(counts * imp)
        return out, aux

    return block(p, xt)


def _capacity_dispatch(p, xt, top_w, top_ids, cfg: ModelConfig, cf: float):
    E, k = cfg.moe.num_experts, cfg.moe.top_k
    T, d = xt.shape
    C = max(1, int(T * k * cf) // E)

    flat_e = top_ids.reshape(-1)                       # (T*k,)
    flat_w = top_w.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)

    order = jnp.argsort(flat_e)                        # stable
    se, sw, stk = flat_e[order], flat_w[order], flat_t[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts               # exclusive cumsum
    pos = jnp.arange(T * k) - starts[se]               # position within expert
    keep = pos < C
    pos_c = jnp.where(keep, pos, 0)

    buf = jnp.zeros((E, C, d), xt.dtype)
    buf = buf.at[se, pos_c].add(
        jnp.where(keep[:, None], xt[stk], 0), mode="drop")
    buf = constrain(buf, ("experts", None, None))
    ye = constrain(_expert_mlp(p, buf), ("experts", None, None))  # (E, C, d)
    y_tok = ye[se, pos_c] * jnp.where(keep, sw, 0.0)[:, None].astype(ye.dtype)
    out = jnp.zeros((T, d), ye.dtype).at[stk].add(y_tok, mode="drop")
    return out
