"""Plain references: the dense Qwen decoder (Qwen2 / Qwen3 block) and the
MLP router, in float32, written from the published descriptions and
importing nothing of the program.

Qwen block (arXiv 2407.10671; hf:Qwen/Qwen3-8B): pre-norm RMSNorm, GQA
attention with rotary embeddings (rotate-half, base ``rope_theta``), a bias
on the Q/K/V projections (Qwen2) or an RMSNorm over each head's query and
key (Qwen3), a SwiGLU MLP, a final RMSNorm and the output head (the token
table itself where the embeddings are tied). Weights are read from the
stacked tree the benchmark made them in: ``blocks`` leaves carry the layer
as their leading axis, matrices are (in, out).

MLP router (the paper's section 4.1): two hidden layers of Linear,
LayerNorm, GELU (exact, erf), then an accuracy head through a sigmoid and
a cost head; the utility of model m under lambda is A_m - lambda C_m.

``control=True`` is the control: every matrix product takes its
operands rounded to float8 e4m3 with one scale per tensor for weights and
per row for activations, the step below the bfloat16 the models are
served in.
"""
from __future__ import annotations

import functools

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


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (T, H, hd); rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer(x, lp, m: dict, fp8: bool):
    """One decoder layer over a whole sequence x (T, d), causal."""
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    T = x.shape[0]
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps = m["rms_norm_eps"]
    at, ff = lp["mixer"], lp["ffn"]
    h = _rmsnorm(x, f32(lp["norm1"]["scale"]), eps)
    q, k, v = (_mm(h, f32(at[n]), fp8) for n in ("wq", "wk", "wv"))
    if m["attention_bias"]:
        q, k, v = q + f32(at["bq"]), k + f32(at["bk"]), v + f32(at["bv"])
    q, k, v = (q.reshape(T, hq, hd), k.reshape(T, hkv, hd),
               v.reshape(T, hkv, hd))
    if m["qk_norm"]:
        q = _rmsnorm(q, f32(at["q_norm"]["scale"]), eps)
        k = _rmsnorm(k, f32(at["k_norm"]["scale"]), eps)
    pos = jnp.arange(T)
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    g = hq // hkv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)  # head h: kv h//g
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if fp8:
        p = _q8(p, -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(T, hq * hd)
    x = x + _mm(o, f32(at["wo"]), fp8)
    h = _rmsnorm(x, f32(lp["norm2"]["scale"]), eps)
    gate = jax.nn.silu(_mm(h, f32(ff["wg"]), fp8))
    return x + _mm(gate * _mm(h, f32(ff["wu"]), fp8), f32(ff["wd"]), fp8)


def _hidden(params, toks, m: dict, fp8: bool):
    """Final-normed hidden states (T, d) of the whole sequence."""
    x = jnp.take(params["embed"]["tok"], toks, axis=0).astype(jnp.float32)

    def body(i, x):
        lp = jax.tree.map(lambda a: a[i], params["blocks"]["l0"])
        return _layer(x, lp, m, fp8)

    x = jax.lax.fori_loop(0, m["num_hidden_layers"], body, x)
    return _rmsnorm(x, params["final_norm"]["scale"].astype(jnp.float32),
                    m["rms_norm_eps"])


def _head(params, m: dict):
    """The output head (d, V) as stored: the token table's transpose where
    the embeddings are tied."""
    return (params["embed"]["tok"].T if m["tie_word_embeddings"]
            else params["embed"]["unembed"])


def _head_scan(x, head, fp8: bool, n_blocks: int = 8):
    """Max and argmax of x @ head over the vocabulary, one block of
    columns at a time so that no float32 copy of the whole head or of the
    (T, V) logits is live."""
    d, V = head.shape
    nb = next(b for b in range(n_blocks, V + 1) if V % b == 0)
    blocks = jnp.moveaxis(head.reshape(d, nb, V // nb), 1, 0)
    h_amax = jnp.max(jnp.abs(head)).astype(jnp.float32)

    def one(blk):
        w = blk.astype(jnp.float32)
        if fp8:
            s = jnp.maximum(h_amax, 1e-30) / _F8_MAX
            w = (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
            lg = jnp.matmul(_q8(x, -1), w, precision=HI)
        else:
            lg = jnp.matmul(x, w, precision=HI)
        return lg.max(-1), lg.argmax(-1)

    mx, am = jax.lax.map(one, blocks)                 # (nb, T)
    b = jnp.argmax(mx, 0)
    return mx.max(0), b * (V // nb) + jnp.take_along_axis(am, b[None], 0)[0]


def _logit_of(x, head, tok):
    """Float32 logits of the given tokens, one per row of x."""
    cols = jnp.take(head, tok, axis=1).astype(jnp.float32)   # (d, T)
    return jnp.einsum("td,dt->t", x, cols, precision=HI)


@functools.partial(jax.jit, static_argnames=("m_items",))
def _gaps(params, toks, served_at, served, *, m_items):
    """Per served position: the reference's best logit minus its logit of
    the served token (about 0 where the served token is the best)."""
    m = dict(m_items)
    x = _hidden(params, toks, m, False)[served_at]
    head = _head(params, m)
    best, _ = _head_scan(x, head, False)
    return best - _logit_of(x, head, served)


@functools.partial(jax.jit, static_argnames=("m_items",))
def _control_gaps(params, toks, served_at, *, m_items):
    """Per position: the float32 reference's best logit minus its logit of
    the token the fp8 control puts first."""
    m = dict(m_items)
    head = _head(params, m)
    x = _hidden(params, toks, m, False)[served_at]
    best, _ = _head_scan(x, head, False)
    _, pick = _head_scan(_hidden(params, toks, m, True)[served_at], head,
                         True)
    return best - _logit_of(x, head, pick)


#: the configuration keys a model is built from
MODEL_KEYS = ("architectures", "num_hidden_layers", "hidden_size",
              "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "vocab_size", "rope_theta",
              "rms_norm_eps", "attention_bias", "qk_norm",
              "tie_word_embeddings", "torch_dtype")


def _frozen(m: dict):
    """The model's keys as a hashable tuple."""
    return tuple((k, tuple(m[k]) if isinstance(m[k], list) else m[k])
                 for k in MODEL_KEYS)


def _teacher_forced(prompt, served, length):
    """Input ids (prompt + served[:-1], padded to ``length`` so that one
    compiled reference serves every request) and the positions whose
    logits predict each served token."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    T = max(length, len(seq))
    toks = np.zeros((T,), np.int32)
    toks[:len(seq)] = seq
    at = np.arange(len(served), dtype=np.int32) + len(prompt) - 1
    return toks, at


def token_gaps(params, m: dict, prompt, served, *, length: int,
               control=False) -> np.ndarray:
    """Teacher-forced gaps of one request's served tokens (see ``_gaps``),
    or, with ``control``, of the fp8 control's picks at the same
    positions. The sequence is padded to ``length`` positions."""
    toks, at = _teacher_forced(np.asarray(prompt), np.asarray(served),
                               length)
    if control:
        g = _control_gaps(params, jnp.asarray(toks), jnp.asarray(at),
                          m_items=_frozen(m))
    else:
        g = _gaps(params, jnp.asarray(toks), jnp.asarray(at),
                  jnp.asarray(np.asarray(served, np.int32)),
                  m_items=_frozen(m))
    return np.asarray(g, np.float64)


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
