"""Compile-only checks of the Pallas kernels on the chip's own compiler.

Each kernel of the serving and routing paths is lowered and compiled for a
described (not attached) TPU v5e at the widths the system serves —
qwen2-1.5b's attention heads in bf16, the router at RouterConfig defaults
(hidden 512, d_emb 768) with n >= 1024 rows — with interpretation off.
This is where tiling, layout and VMEM refusals show up that interpret
mode on the CPU cannot see. Nothing runs; a pass says the chip's compiler
accepts the kernel, not that it is fast or right.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU compiler library, so every worker
collects the same tests and only the one that runs this file loads it.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.kmeans_assign import (kmeans_assign_pallas,
                                         kmeans_assign_reduce_pallas)
from repro.kernels.router_utility import router_utility_pallas

# qwen2-1.5b attention: 2 KV heads, 6 query heads per KV head, head_dim 128
HKV, G, HD = 2, 6, 128
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"       # no compiler logs on disk
    cache_on = jax.config.jax_enable_compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without one — keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


@pytest.mark.parametrize("S", [256, 12, 520, 1000])
def test_uniform_decode_compiles(one_chip, S):
    """Slot-pool / grouped-scan decode: a (B,) validity vector in SMEM and
    any cache length up to the seq block or a multiple of 8 beyond it."""
    _compile(lambda q, k, v, nv: decode_attention_pallas(
                 q, k, v, nv, interpret=False), one_chip,
             ((8, HKV, G, HD), BF16), ((8, HKV, S, HD), BF16),
             ((8, HKV, S, HD), BF16), ((8,), jnp.int32))


# the served cells' paged lanes: (rows, KV heads, queries per KV head),
# pool pages (with the trash page) and table width, 16-position pages
PAGED = {"qwen3-8b": (8, 8, 4, 769, 96), "qwen2-1.5b-chat": (32, 2, 6, 1025, 80)}


def _paged_shapes(B, hkv, g, pages, npg):
    return (((B, hkv, g, HD), BF16), ((pages, hkv, 16, HD), BF16),
            ((pages, hkv, 16, HD), BF16), ((B, npg), jnp.int32),
            ((B,), jnp.int32))


@pytest.mark.parametrize("lane", sorted(PAGED))
def test_paged_decode_compiles(one_chip, lane):
    """The engine's decode at the benchmark's sizes: saturate's qwen3 lane
    and chat's qwen2 lane."""
    _compile(lambda q, k, v, pt, nv: paged_decode_attention_pallas(
                 q, k, v, pt, nv, interpret=False), one_chip,
             *_paged_shapes(*PAGED[lane]))


def test_paged_decode_kernel_keeps_its_trace_name(one_chip):
    """The compiled custom call is named with ``paged_decode_attention``:
    the benchmark finds the kernel's device time by that name."""
    hlo = _compile(lambda q, k, v, pt, nv: paged_decode_attention_pallas(
                       q, k, v, pt, nv, interpret=False), one_chip,
                   *_paged_shapes(*PAGED["qwen3-8b"]))
    names = re.findall(r"%(\S+) = \S+ custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    assert names and all("paged_decode_attention" in n for n in names)


@pytest.mark.parametrize("n", [1, 1024])
def test_router_utility_compiles(one_chip, n):
    """The MLP family's route kernel: hidden 512, 2 pool models."""
    _compile(lambda h, aw, ab, cw, cb: router_utility_pallas(
                 h, aw, ab, cw, cb, 0.5, interpret=False), one_chip,
             ((n, 512), jnp.float32), ((512, 2), jnp.float32),
             ((2,), jnp.float32), ((512, 2), jnp.float32),
             ((2,), jnp.float32))


# (n rows, K centroids, block_k, block_d): the fused single pass, the
# K-tiled and d-tiled regimes, and a ragged n that pads to one block
KMEANS = [(1024, 15, 512, 2048), (1024, 300, 128, 2048),
          (1024, 15, 512, 256), (150, 20, 512, 2048)]


@pytest.mark.parametrize("n,K,bk,bd", KMEANS)
def test_kmeans_assign_compiles(one_chip, n, K, bk, bd):
    _compile(lambda x, c: kmeans_assign_pallas(
                 x, c, block_k=bk, block_d=bd, interpret=False), one_chip,
             ((n, 768), jnp.float32), ((K, 768), jnp.float32))


@pytest.mark.parametrize("n,K,bk,bd", KMEANS)
def test_kmeans_assign_reduce_compiles(one_chip, n, K, bk, bd):
    _compile(lambda x, c, w: kmeans_assign_reduce_pallas(
                 x, c, w, block_k=bk, block_d=bd, interpret=False),
             one_chip, ((n, 768), jnp.float32), ((K, 768), jnp.float32),
             ((n,), jnp.float32))


def test_flash_attention_compiles(one_chip):
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v,
                                                    interpret=False),
             one_chip, *[((1, 256, 12, HD), BF16)] * 3)
