"""Distributed federated-router training driver.

Maps the paper's client/server communication pattern onto the TPU mesh
(DESIGN.md §3): clients are sharded over a 1-D "clients" mesh axis with
``shard_map``; each device runs its local clients' FedAvg updates (vmap);
the server aggregation (Alg. 1 line 11) becomes a weighted ``psum`` — the
TPU-idiomatic replacement for a parameter server. All of that now lives
behind ``repro.routers.fit_federated(..., mesh=...)``; this driver just
builds the mesh, the data, and the router.

Runs on every device the process has; ``--clients`` must divide by their
count. On CPU, simulate several with XLA_FLAGS:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.fed_train --clients 16 --rounds 10
"""
import argparse

import jax
from jax.sharding import Mesh

from repro import compile_cache, routers, sharding as shd
from repro.config import FedConfig, RouterConfig
from repro.core import policy
from repro.data.partition import federated_split
from repro.data.synthetic import make_eval_corpus


def make_client_mesh():
    return shd.client_mesh()


def fedavg_distributed(key, data, rcfg: RouterConfig, fcfg: FedConfig, *,
                       rounds: int, mesh: Mesh):
    """Sharded Alg. 1 through the unified entry point. Returns
    (fitted MLPRouter, per-round losses)."""
    router = routers.make("mlp", rcfg)
    router, hist = routers.fit_federated(router, data, fcfg, key=key,
                                         rounds=rounds, mesh=mesh)
    return router, hist["loss"]


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--queries", type=int, default=4000)
    args = ap.parse_args()

    key = jax.random.PRNGKey(0)
    corpus = make_eval_corpus(key, n_queries=args.queries, d_emb=64)
    rcfg = RouterConfig(d_emb=64, num_models=11)
    fcfg = FedConfig(num_clients=args.clients)
    split = federated_split(jax.random.PRNGKey(1), corpus, fcfg)

    mesh = make_client_mesh()
    print(f"devices: {len(jax.devices())}, clients: {args.clients}")
    # keep the slab distributed end to end: each device holds its own
    # block of clients, never the full stack
    train = shd.shard_clients(split["train"], mesh)
    router, losses = fedavg_distributed(jax.random.PRNGKey(2),
                                        train, rcfg, fcfg,
                                        rounds=args.rounds, mesh=mesh)
    tg = split["test_global"]
    *_, auc = policy.eval_router(router.predict, tg["x"], tg["acc_table"],
                                 tg["cost_table"])
    print(f"loss: {losses[0]:.4f} → {losses[-1]:.4f}; global-test AUC {auc:.3f}")


if __name__ == "__main__":
    main()
