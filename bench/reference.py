"""Plain references in float32, written from the published descriptions
and importing nothing of the program: the MLP router here, and each model
family's decoder in its own module under ``bench/models/``.

MLP router (the paper's section 4.1): two hidden layers of Linear,
LayerNorm, GELU (exact, erf), then an accuracy head through a sigmoid and
a cost head; the utility of model m under lambda is A_m - lambda C_m.

The control rounds every matrix product's operands to float8 e4m3 with
one scale per tensor for weights and per row for activations (``_mm``
with ``fp8``), the step below the bfloat16 the models are served in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0


def _q8(a, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``
    (None: one per tensor) and return the dequantised float32 values."""
    amax = (jnp.max(jnp.abs(a)) if axis is None
            else jnp.max(jnp.abs(a), axis=axis, keepdims=True))
    s = jnp.maximum(amax, 1e-30) / _F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    """x (..., k) @ w (k, n) in float32; fp8 rounds activations per row
    and the weight per tensor first."""
    if fp8:
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w, precision=HI)


def frozen(m: dict, keys) -> tuple:
    """A model's ``keys`` as a hashable tuple (a static argument of the
    jitted references)."""
    return tuple((k, tuple(m[k]) if isinstance(m[k], list) else m[k])
                 for k in keys)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / np.sqrt(2.0)))


def router_utility(state, x, lam, *, fp8: bool = False) -> np.ndarray:
    """(n, M) utilities A - lam C of the MLP router over queries x (n, d)
    at per-query ``lam`` (n,)."""
    h = jnp.asarray(x, jnp.float32)
    for lyr in state["trunk"]:
        h = _mm(h, lyr["w"], fp8) + lyr["b"]
        mu = h.mean(-1, keepdims=True)
        var = ((h - mu) ** 2).mean(-1, keepdims=True)
        h = _gelu((h - mu) * jax.lax.rsqrt(var + 1e-5) * lyr["ln_s"]
                  + lyr["ln_b"])
    hd = state["heads"]
    A = jax.nn.sigmoid(_mm(h, hd["acc_w"], fp8) + hd["acc_b"])
    C = _mm(h, hd["cost_w"], fp8) + hd["cost_b"]
    return np.asarray(A - jnp.asarray(lam, jnp.float32)[:, None] * C,
                      np.float64)


def route_gaps(state, x, lam, chosen, *, control=False) -> np.ndarray:
    """Per query: the float32 reference's best utility minus its utility
    of the chosen model; with ``control`` the chosen model is the fp8
    control's pick."""
    U = router_utility(state, x, lam)
    if control:
        chosen = router_utility(state, x, lam, fp8=True).argmax(1)
    return U.max(1) - U[np.arange(len(U)), np.asarray(chosen)]
