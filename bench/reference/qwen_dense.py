"""Plain reference of the Qwen dense decoder (Qwen1.5, Qwen3) served in HiF4.

Three things live here, all independent of the program under test:

* ``layer_weights`` / ``top_weights``: the model's weights, drawn from the
  run's seed, in a layout of the benchmark's own (Hugging Face names). The
  benchmark makes them, hands them to the program through ``to_program``,
  and draws them again, layer by layer, for the reference.
* ``to_program``: the one place that knows the program's parameter tree.
* ``forward_logits``: the forward pass of the published architecture in
  float32 at ``highest`` matmul precision, with the deployment's number
  formats applied where the configuration states them: HiF4 (the paper's
  Algorithm 1, copied below) on both operands of every linear layer of the
  body and on the keys and values a decode step reads from the cache; the
  stated storage type (bfloat16) for every activation the model stores and
  for the embedding and tied head. ``store`` is that storage type; the
  control of ``bench/check.py`` passes ``float8_e4m3fn`` instead.

Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
GROUP = 64


# ---------------------------------------------------------------------------
# Sizes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The published sizes the reference needs, read from the config file:
    ``published`` holds the keys of the model's own config.json,
    ``architecture`` what its model class fixes (biases, qk-norm)."""

    d: int
    layers: int
    heads: int
    kv_heads: int
    d_head: int
    ff: int
    vocab: int
    theta: float
    eps: float
    bias: bool
    qk_norm: bool
    init_std: float

    def __init__(self, published: dict, architecture: dict):
        hf = dict(published, **architecture)
        if not hf.get("tie_word_embeddings", False):
            raise ValueError("the reference serves tied embeddings only")
        d, heads = hf["hidden_size"], hf["num_attention_heads"]
        for name, value in (
                ("d", d), ("layers", hf["num_hidden_layers"]), ("heads", heads),
                ("kv_heads", hf["num_key_value_heads"]),
                ("d_head", hf.get("head_dim") or d // heads),
                ("ff", hf["intermediate_size"]), ("vocab", hf["vocab_size"]),
                ("theta", float(hf["rope_theta"])),
                ("eps", float(hf["rms_norm_eps"])),
                ("bias", bool(hf.get("attention_bias", False))),
                ("qk_norm", bool(hf.get("qk_norm", False))),
                ("init_std", float(hf["initializer_range"]))):
            object.__setattr__(self, name, value)

    def matmul_params(self) -> int:
        """Weights every token multiplies: the body and the tied head."""
        a = self.d * self.d_head * (2 * self.heads + 2 * self.kv_heads)
        return self.layers * (a + 3 * self.d * self.ff) + self.d * self.vocab


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------

def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _layer_shapes(s: Sizes) -> dict:
    q, kv = s.heads * s.d_head, s.kv_heads * s.d_head
    shapes = {
        "input_layernorm": (s.d,), "q_proj": (s.d, q), "k_proj": (s.d, kv),
        "v_proj": (s.d, kv), "o_proj": (q, s.d),
        "post_attention_layernorm": (s.d,), "gate_proj": (s.d, s.ff),
        "up_proj": (s.d, s.ff), "down_proj": (s.ff, s.d),
    }
    if s.bias:
        shapes.update(q_bias=(q,), k_bias=(kv,), v_bias=(kv,))
    if s.qk_norm:
        shapes.update(q_norm=(s.d_head,), k_norm=(s.d_head,))
    return shapes


def _draw(key, name: str, shape, std: float) -> jax.Array:
    """Matrices and biases normal with the published initializer_range;
    norm weights 1 + 0.1 normal."""
    z = jax.random.normal(key, shape, F32)
    if name.endswith("norm"):
        return (1.0 + 0.1 * z).astype(jnp.bfloat16)
    return (std * z).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_weights(s: Sizes, key, layer):
    shapes = _layer_shapes(s)
    keys = jax.random.split(jax.random.fold_in(key, layer), len(shapes))
    return {n: _draw(k, n, shp, s.init_std)
            for k, (n, shp) in zip(keys, sorted(shapes.items()))}


def layer_weights(s: Sizes, key, layer: int) -> dict:
    """bf16 weights of one decoder layer (x @ W layout, (in, out))."""
    return _layer_weights(s, key, jnp.int32(layer))


@functools.partial(jax.jit, static_argnums=(0,))
def top_weights(s: Sizes, key) -> dict:
    k1, k2 = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return {"embed_tokens": _draw(k1, "embed_tokens", (s.vocab, s.d), s.init_std),
            "norm": _draw(k2, "norm", (s.d,), s.init_std)}


# HF layer name -> program path (two-level), and how the tensor is shaped there
def to_program(s: Sizes, w: dict) -> dict:
    """One layer of bench weights -> the program's block tree (leading L=1)."""
    h, hkv, dh, d = s.heads, s.kv_heads, s.d_head, s.d
    attn = {
        "wq": w["q_proj"].reshape(d, h, dh), "wk": w["k_proj"].reshape(d, hkv, dh),
        "wv": w["v_proj"].reshape(d, hkv, dh), "wo": w["o_proj"].reshape(h, dh, d),
    }
    if s.bias:
        attn.update(bq=w["q_bias"].reshape(h, dh), bk=w["k_bias"].reshape(hkv, dh),
                    bv=w["v_bias"].reshape(hkv, dh))
    if s.qk_norm:
        attn.update(q_norm=w["q_norm"], k_norm=w["k_norm"])
    block = {"norm1": {"w": w["input_layernorm"]}, "attn": attn,
             "norm2": {"w": w["post_attention_layernorm"]},
             "mlp": {"wg": w["gate_proj"], "wu": w["up_proj"], "wo": w["down_proj"]}}
    return jax.tree.map(lambda a: a[None], block)


def top_to_program(w: dict) -> dict:
    return {"embed": w["embed_tokens"], "final_norm": {"w": w["norm"]}}


# ---------------------------------------------------------------------------
# HiF4 (the paper's Algorithm 1), quantize-dequantize along the last axis
# ---------------------------------------------------------------------------


def _bf16(x):
    return jax.lax.reduce_precision(x.astype(F32), exponent_bits=8, mantissa_bits=7)


def _binade(ax):
    return jnp.frexp(ax)[1] - 1


def _round_e6m2(x):
    ax = jnp.maximum(jnp.abs(x), 2.0 ** -48)
    quantum = jnp.ldexp(F32(1.0), jnp.clip(_binade(ax), -48, 15) - 2)
    return jnp.clip(jnp.round(ax / quantum) * quantum, 2.0 ** -48, 2.0 ** 15 * 1.5)


_RECIP7 = float(np.asarray(1.0 / 7.0, jnp.bfloat16))


def hif4_qdq(x):
    """HiF4 quantize-dequantize of (..., K) values in groups of 64 along K.

    One unit: an E6M2 scale, 8 + 16 one-bit micro-exponents and 64 S1P2
    elements; bf16 hardware roundings of Algorithm 1 made explicit."""
    lead, k = x.shape[:-1], x.shape[-1]
    v = x.astype(F32).reshape(lead + (k // GROUP, GROUP))
    av = jnp.abs(v)
    g = v.shape[:-1]
    v16 = jnp.max(av.reshape(g + (16, 4)), -1)
    v8 = jnp.max(v16.reshape(g + (8, 2)), -1)
    vmax = jnp.max(v8, -1)
    e6m2 = _round_e6m2(_bf16(_bf16(vmax) * _RECIP7))
    rec = _bf16(1.0 / e6m2)[..., None]
    e1_8 = (_bf16(v8 * rec) > 4.0).astype(jnp.int32)
    sh2 = jnp.repeat(e1_8, 2, -1)
    e1_16 = (_bf16(v16 * rec) * jnp.ldexp(F32(1.0), -sh2) >= 2.0).astype(jnp.int32)
    shift = jnp.repeat(e1_8, 8, -1) + jnp.repeat(e1_16, 4, -1)
    scaled = _bf16(v * rec) * jnp.ldexp(F32(1.0), -shift)
    s1p2 = jnp.clip(jnp.round(scaled * 4.0) * 0.25, -1.75, 1.75)
    out = e6m2[..., None] * jnp.ldexp(F32(1.0), shift) * s1p2
    return out.reshape(lead + (k,))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _store(x, dtype):
    """``x`` as the model stores it: rounded to bfloat16, or to float8 with
    one scale per row (its largest magnitude at the format's largest
    finite value), the way float8 storage is used. Both roundings are kept
    from being folded away under ``jit``."""
    if dtype == jnp.bfloat16:
        return _bf16(x)
    top = float(jnp.finfo(dtype).max)
    scale = jnp.max(jnp.abs(x), -1, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    low = jax.lax.optimization_barrier((x / scale).astype(dtype))
    return low.astype(F32) * scale


def _mean(x):
    """Mean over the last axis, summed pairwise (halve, add the halves)."""
    n = x.shape[-1]
    width = 1 << (n - 1).bit_length()
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x * (1.0 / n)


def _rms_norm(x, w, eps, dtype):
    x = x.astype(F32)
    y = x * jax.lax.rsqrt(_mean(x * x) + eps)
    return _store(y * w.astype(F32), dtype)


def _linear(x, w, dtype):
    """HiF4 activation x HiF4 weight, f32 products and sums, stored."""
    y = jnp.matmul(hif4_qdq(x), hif4_qdq(w.astype(F32).T).T, precision=HIGHEST)
    return _store(y, dtype)


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[..., None].astype(F32) * inv                  # (n, S, d/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# Attention runs as the deployment computes it: an online softmax over
# tiles of keys whose probabilities are stored (bfloat16) before they
# weight the values. A prompt is read in chunks of PREFILL_KEY_CHUNK keys
# with the running sum divided out at the end; a decode step reads the
# cache page by page and keeps its sum normalized after every page.
PREFILL_KEY_CHUNK = 1024
QUERY_BLOCK = 512
NEG = -1e30


def _scores(qb, kt, D, prefill: bool):
    s = jnp.einsum("nqhd,nkhd->nhqk", qb, kt, precision=HIGHEST)
    return s * (1.0 / D ** 0.5) if prefill else s / (D ** 0.5)


def _prompt_rows(qb, qpos, k, v, dtype):
    """Chunked online softmax over the keys; qb (n, Q, H, D)."""
    n, Q, H, D = qb.shape
    m = jnp.full((n, H, Q), NEG, F32)
    l = jnp.zeros((n, H, Q), F32)
    acc = jnp.zeros((n, H, Q, D), F32)
    for c0 in range(0, k.shape[1], PREFILL_KEY_CHUNK):
        kt, vt = k[:, c0:c0 + PREFILL_KEY_CHUNK], v[:, c0:c0 + PREFILL_KEY_CHUNK]
        kpos = c0 + jnp.arange(kt.shape[1])
        s = _scores(qb, kt, D, True)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None], s, NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "nhqk,nkhd->nhqd", _store(p, dtype), vt, precision=HIGHEST)
        m = m_new
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _decode_rows(qb, qpos, k, v, dtype, page: int):
    """Page-by-page online softmax with a normalized sum; qb (n, Q, H, D)."""
    n, Q, H, D = qb.shape
    S = k.shape[1]
    kp = jnp.pad(k, ((0, 0), (0, (-S) % page), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, (-S) % page), (0, 0), (0, 0)))

    def tile(carry, t):
        m, l, acc = carry
        kt = jax.lax.dynamic_slice_in_dim(kp, t * page, page, 1)
        vt = jax.lax.dynamic_slice_in_dim(vp, t * page, page, 1)
        kpos = t * page + jnp.arange(page)
        s = _scores(qb, kt, D, False)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None], s, NEG)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new)
        l_new = l * corr + e.sum(-1, keepdims=True)
        pv = jnp.einsum("nhqk,nkhd->nhqd", _store(e / l_new, dtype), vt,
                        precision=HIGHEST)
        return (m_new, l_new, acc * (l * corr / l_new) + pv), None

    init = (jnp.full((n, H, Q, 1), NEG, F32), jnp.zeros((n, H, Q, 1), F32),
            jnp.zeros((n, H, Q, D), F32))
    (_, _, acc), _ = jax.lax.scan(tile, init, jnp.arange(kp.shape[1] // page))
    return acc


def _attention(q, k, v, k_dec, v_dec, prompt_len, dtype, page: int):
    """Causal GQA attention. Queries of the prompt read the keys and values
    as computed; queries past it (decode steps) read them as the HiF4 cache
    holds them. q (n, S, H, D); k, v, k_dec, v_dec (n, S, Hkv, D)."""
    n, S, H, D = q.shape
    rep = H // k.shape[2]
    k, v, k_dec, v_dec = (jnp.repeat(t, rep, 2) for t in (k, v, k_dec, v_dec))
    pad = (-S) % QUERY_BLOCK
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, QUERY_BLOCK, 1)
        qpos = start + jnp.arange(QUERY_BLOCK)
        pre = _prompt_rows(qb, qpos, k, v, dtype)
        dec = _decode_rows(qb, qpos, k_dec, v_dec, dtype, page)
        is_dec = (qpos[None, :] >= prompt_len[:, None])[:, None, :, None]
        return jnp.where(is_dec, dec, pre)                  # (n, H, Q, D)

    out = jax.lax.map(block, jnp.arange(0, S + pad, QUERY_BLOCK))
    out = jnp.moveaxis(out, 0, 2).reshape(n, H, -1, D)[:, :, :S]
    return jnp.moveaxis(out, 1, 2)                          # (n, S, H, D)


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _layer(s: Sizes, w, x, pos, prompt_len, dtype, page: int):
    n, S, d = x.shape
    h = _rms_norm(x, w["input_layernorm"], s.eps, dtype)
    q = _linear(h, w["q_proj"], dtype)
    k = _linear(h, w["k_proj"], dtype)
    v = _linear(h, w["v_proj"], dtype)
    if s.bias:
        q = _store(q + w["q_bias"].astype(F32), dtype)
        k = _store(k + w["k_bias"].astype(F32), dtype)
        v = _store(v + w["v_bias"].astype(F32), dtype)
    q = q.reshape(n, S, s.heads, s.d_head)
    k = k.reshape(n, S, s.kv_heads, s.d_head)
    v = v.reshape(n, S, s.kv_heads, s.d_head)
    if s.qk_norm:
        q = _rms_norm(q, w["q_norm"], s.eps, dtype)
        k = _rms_norm(k, w["k_norm"], s.eps, dtype)
    q = _store(_rope(q, pos, s.theta), dtype)
    k = _store(_rope(k, pos, s.theta), dtype)
    cached = lambda t: hif4_qdq(t.reshape(n, S, -1)).reshape(t.shape)
    o = _attention(q, k, v, cached(k), cached(v), prompt_len, dtype, page)
    o = _store(o, dtype).reshape(n, S, -1)
    x = _store(x + _linear(o, w["o_proj"], dtype), dtype)
    h = _rms_norm(x, w["post_attention_layernorm"], s.eps, dtype)
    g = _linear(h, w["gate_proj"], dtype)
    u = _linear(h, w["up_proj"], dtype)
    a = _store(jax.nn.silu(g) * u, dtype)
    return _store(x + _linear(a, w["down_proj"], dtype), dtype)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(s: Sizes, top, x, rows, dtype):
    h = jnp.take_along_axis(x, rows[:, :, None], axis=1)    # (n, T, d)
    h = _rms_norm(h, top["norm"], s.eps, dtype)
    emb = _store(top["embed_tokens"].astype(F32), dtype)
    return jnp.einsum("ntd,vd->ntv", h, emb, precision=HIGHEST)


def forward_logits(s: Sizes, key, tokens, prompt_len, rows, *, page: int,
                   dtype=jnp.bfloat16):
    """Teacher-forced logits at ``rows`` of each sequence.

    tokens (n, S) int32: prompt then served tokens (right-padded); prompt_len
    (n,) int32; rows (n, T) int32: the positions whose next-token logits are
    wanted; ``page``: tokens per KV-cache page. Weights are drawn again from
    ``key`` one layer at a time, so the reference holds one layer and the
    embedding at once. Returns (n, T, V) float32."""
    top = top_weights(s, key)
    emb = _store(top["embed_tokens"].astype(F32), dtype)
    x = jnp.take(emb, tokens, axis=0)
    n, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (n, S))
    for layer in range(s.layers):
        x = _layer(s, layer_weights(s, key, layer), x, pos, prompt_len,
                   jnp.dtype(dtype), page)
    return _head(s, top, x, rows, jnp.dtype(dtype))
