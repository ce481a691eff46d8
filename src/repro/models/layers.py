"""Shared transformer primitives: norms, rotary, attention (GQA + qk-norm),
gated MLPs, embeddings, KV caches.

Conventions
-----------
* activations bf16, matmuls accumulate f32 (``preferred_element_type``),
  norms/softmax/losses in f32;
* weights live in bf16 (the ENEC compression target), optimizer keeps f32
  master copies;
* attention over long sequences uses a *statically unrolled* streaming
  softmax over KV chunks (flash-style) so the dry-run's HLO carries the true
  FLOP/byte counts (while-loop bodies are counted once by cost_analysis) and
  peak memory stays O(T * chunk);
* every function is shape-polymorphic over leading batch dims and jit/pjit
  friendly (no data-dependent shapes).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.runtime.weights import WeightHandle

ACT_DTYPE = jnp.bfloat16
KV_CHUNK = 2048  # flash chunk; statically unrolled (<= 32 iterations at 32k)

import os as _os


def weight_matmul(w, x, eq: str):
    """Contract x's last axis against the (K, N) weight ``w``.

    ``w`` may be a WeightHandle (serve-time weight-execution modes: dense /
    streamed / fused all realize the same canonical tiled contraction, so
    logits are bit-identical across modes) or a plain array, which keeps the
    legacy einsum path — train and raw-params serving are untouched.
    """
    if isinstance(w, WeightHandle):
        lead = x.shape[:-1]
        out = w.matmul(x.reshape(-1, x.shape[-1]))
        return out.reshape(lead + (out.shape[-1],))
    return jnp.einsum(eq, x, w, preferred_element_type=jnp.float32)


def _two_rows(x2d):
    """Pad a one-row (1, K) operand to two rows.  This only matters on the
    CPU: XLA:CPU runs a one-row dot through a matrix-vector kernel whose
    f32 accumulation order differs from the matrix-matrix kernel it uses
    for two or more rows, so without this a request's logits would depend
    on how many rows share its step (batch-1 prefill head vs batched
    decode) and on whether the row was sliced inside or outside the jit.
    Elsewhere it costs one copy of a (1, K) row."""
    if x2d.shape[0] != 1:
        return x2d
    return jnp.concatenate([x2d, x2d], axis=0)


def safe_einsum(eq, a, b):
    """einsum with f32 accumulation.

    XLA:CPU's DotThunk cannot *execute* some batched bf16xbf16->f32 dots
    (compilation/lowering is fine — the dry-run is unaffected).  When running
    on CPU outside the dry-run we up-cast operands; on TPU the native
    mixed-precision dot is used.  Set REPRO_DRYRUN=1 to keep bf16 operands in
    the lowered HLO (exact byte accounting).
    """
    if jax.default_backend() == "cpu" and not _os.environ.get("REPRO_DRYRUN"):
        return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32))
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=ACT_DTYPE):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype=ACT_DTYPE):
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(x, positions, theta: float = 1e6):
    """x: (..., T, H, hd), positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnParamsShape:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False


def init_attention(key, s: AttnParamsShape, dtype=ACT_DTYPE):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (s.d_model, s.n_heads * s.head_dim), 0, dtype),
        "wk": dense_init(ks[1], (s.d_model, s.n_kv_heads * s.head_dim), 0, dtype),
        "wv": dense_init(ks[2], (s.d_model, s.n_kv_heads * s.head_dim), 0, dtype),
        "wo": dense_init(ks[3], (s.n_heads * s.head_dim, s.d_model), 0, dtype),
    }
    if s.qk_norm:
        p["q_norm"] = jnp.zeros((s.head_dim,), dtype)
        p["k_norm"] = jnp.zeros((s.head_dim,), dtype)
    return p


def _project_qkv(p, x, s: AttnParamsShape, positions, theta):
    b, t, _ = x.shape
    q = weight_matmul(p["wq"], x, "btd,dh->bth")
    k = weight_matmul(p["wk"], x, "btd,dh->bth")
    v = weight_matmul(p["wv"], x, "btd,dh->bth")
    q = q.reshape(b, t, s.n_heads, s.head_dim).astype(ACT_DTYPE)
    k = k.reshape(b, t, s.n_kv_heads, s.head_dim).astype(ACT_DTYPE)
    v = v.reshape(b, t, s.n_kv_heads, s.head_dim).astype(ACT_DTYPE)
    if s.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    return q, k, v


def _chunk_scores(q, k, scale):
    """q (B,Tq,H,hd) x k (B,S,KV,hd) -> (B,H,Tq,S) f32, GQA via reshape."""
    b, tq, h, hd = q.shape
    kv = k.shape[2]
    grp = h // kv
    qg = q.reshape(b, tq, kv, grp, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", qg, k,
                   preferred_element_type=jnp.float32) * scale
    return s.reshape(b, kv * grp, tq, s.shape[-1])


def _chunk_out(probs, v, h):
    """probs (B,H,Tq,S) x v (B,S,KV,hd) -> (B,Tq,H,hd)."""
    b, _, tq, s_len = probs.shape
    kv = v.shape[2]
    grp = h // kv
    pg = probs.reshape(b, kv, grp, tq, s_len)
    out = jnp.einsum("bkgts,bskh->btkgh", pg, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, tq, h, v.shape[-1])


def flash_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                    prefix_len: int = 0, chunk: int = KV_CHUNK):
    """Streaming-softmax attention, statically unrolled over KV chunks.

    q: (B, Tq, H, hd); k, v: (B, S, KV, hd).  ``causal`` applies a causal
    mask with the query positions offset by ``q_offset`` relative to keys;
    positions < ``prefix_len`` are always visible (PaliGemma prefix-LM).
    """
    b, tq, h, hd = q.shape
    s_total = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    chunk = min(chunk, s_total)
    n_chunks = (s_total + chunk - 1) // chunk

    m = jnp.full((b, h, tq, 1), -jnp.inf, jnp.float32)
    denom = jnp.zeros((b, h, tq, 1), jnp.float32)
    acc = jnp.zeros((b, tq, h, hd), jnp.float32)
    q_pos = q_offset + jnp.arange(tq)[:, None]

    for c in range(n_chunks):
        lo = c * chunk
        hi = min(lo + chunk, s_total)
        kc, vc = k[:, lo:hi], v[:, lo:hi]
        scores = _chunk_scores(q, kc, scale)  # (B,H,Tq,hi-lo) f32
        if causal:
            k_pos = lo + jnp.arange(hi - lo)[None, :]
            visible = (k_pos <= q_pos) | (k_pos < prefix_len)
            scores = jnp.where(visible[None, None], scores, -jnp.inf)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(scores - m_safe)
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        correction = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        correction = jnp.where(jnp.isfinite(m), correction, 0.0)
        denom = denom * correction + p.sum(axis=-1, keepdims=True)
        acc = acc * correction.squeeze(-1).transpose(0, 2, 1)[..., None] \
            + _chunk_out(p.astype(ACT_DTYPE), vc, h)
        m = m_new
    denom = jnp.maximum(denom, 1e-30)
    out = acc / denom.squeeze(-1).transpose(0, 2, 1)[..., None]
    return out.astype(ACT_DTYPE)


def decode_attention(q, k_cache, v_cache, lengths, *,
                     score_shard: bool = False):
    """Single-token decode: q (B, 1, H, hd) over caches (B, S, KV, hd).

    ``lengths``: (B,) int32 — number of valid cache entries per sequence.
    ``score_shard`` pins the (B, H, 1, S) score chain S-sharded on "model"
    (flash-decoding style): local max/exp/sum + tiny stat all-reduces
    instead of SPMD rematerializing full-length f32 scores (§Perf).
    """
    b, _, h, hd = q.shape
    s_len = k_cache.shape[1]
    scale = 1.0 / math.sqrt(hd)
    scores = _chunk_scores(q, k_cache, scale)  # (B, H, 1, S)
    k_pos = jnp.arange(s_len)[None, None, None, :]
    bias = jnp.where(k_pos < lengths[:, None, None, None], 0.0, -1e30)
    scores = scores + bias  # additive mask: one fused add, no select chain

    def pin(x):
        if not score_shard:
            return x
        from jax.sharding import PartitionSpec as _P
        return jax.lax.with_sharding_constraint(
            x, _P(None, None, None, "model"))

    scores = pin(scores)
    m = pin(jnp.max(scores, axis=-1, keepdims=True))
    p = pin(jnp.exp(scores - jax.lax.stop_gradient(m)))
    denom = jnp.sum(p, axis=-1, keepdims=True)
    probs = p / denom
    return _chunk_out(probs.astype(ACT_DTYPE), v_cache, h)


def attention_block(p, x, s: AttnParamsShape, positions, theta, *,
                    causal=True, prefix_len=0, chunk=KV_CHUNK):
    """Full-sequence self attention (train / prefill). Returns (out, (k, v))."""
    q, k, v = _project_qkv(p, x, s, positions, theta)
    out = flash_attention(q, k, v, causal=causal, prefix_len=prefix_len,
                          chunk=chunk)
    out = weight_matmul(p["wo"], out.reshape(x.shape[0], x.shape[1], -1),
                        "btf,fd->btd").astype(x.dtype)
    return out, (k, v)


def attention_decode_block(p, x, s: AttnParamsShape, cache_kv, lengths,
                           theta, score_shard: bool = False):
    """One-token decode step. x: (B, 1, D). cache_kv: (k, v) (B, S, KV, hd).

    Writes the new k/v at position ``lengths`` per sequence, then attends.
    """
    k_cache, v_cache = cache_kv
    positions = lengths[:, None]  # (B, 1) — rope position of the new token
    q, k_new, v_new = _project_qkv(p, x, s, positions, theta)
    b = x.shape[0]
    bidx = jnp.arange(b)
    k_cache = k_cache.at[bidx, lengths].set(k_new[:, 0])
    v_cache = v_cache.at[bidx, lengths].set(v_new[:, 0])
    out = decode_attention(q, k_cache, v_cache, lengths + 1,
                           score_shard=score_shard)
    out = weight_matmul(p["wo"], out.reshape(b, 1, -1),
                        "btf,fd->btd").astype(x.dtype)
    return out, (k_cache, v_cache)


# ---------------------------------------------------------------------------
# cross attention (whisper decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(key, s: AttnParamsShape, dtype=ACT_DTYPE):
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (s.d_model, s.n_heads * s.head_dim), 0, dtype),
        "wk": dense_init(ks[1], (s.d_model, s.n_kv_heads * s.head_dim), 0, dtype),
        "wv": dense_init(ks[2], (s.d_model, s.n_kv_heads * s.head_dim), 0, dtype),
        "wo": dense_init(ks[3], (s.n_heads * s.head_dim, s.d_model), 0, dtype),
    }


def cross_attention_block(p, x, memory_kv, s: AttnParamsShape):
    """x: (B, T, D) queries over precomputed encoder memory (k, v)."""
    b, t, _ = x.shape
    k, v = memory_kv
    q = jnp.einsum("btd,dh->bth", x, p["wq"],
                   preferred_element_type=jnp.float32)
    q = q.reshape(b, t, s.n_heads, s.head_dim).astype(ACT_DTYPE)
    out = flash_attention(q, k, v, causal=False)
    return jnp.einsum("btf,fd->btd", out.reshape(b, t, -1), p["wo"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def cross_memory(p, enc_out, s: AttnParamsShape):
    """Precompute encoder-side K/V once per sequence."""
    b, t, _ = enc_out.shape
    k = jnp.einsum("btd,dh->bth", enc_out, p["wk"],
                   preferred_element_type=jnp.float32)
    v = jnp.einsum("btd,dh->bth", enc_out, p["wv"],
                   preferred_element_type=jnp.float32)
    k = k.reshape(b, t, s.n_kv_heads, s.head_dim).astype(ACT_DTYPE)
    v = v.reshape(b, t, s.n_kv_heads, s.head_dim).astype(ACT_DTYPE)
    return k, v


# ---------------------------------------------------------------------------
# gated MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype=ACT_DTYPE):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d_model, d_ff), 0, dtype),
        "w_up": dense_init(ks[1], (d_model, d_ff), 0, dtype),
        "w_down": dense_init(ks[2], (d_ff, d_model), 0, dtype),
    }


def mlp_block(p, x, activation: str = "silu"):
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    g = weight_matmul(p["w_gate"], x, "btd,df->btf")
    u = weight_matmul(p["w_up"], x, "btd,df->btf")
    h = (act(g) * u).astype(ACT_DTYPE)
    return weight_matmul(p["w_down"], h, "btf,fd->btd").astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings / head / loss
# ---------------------------------------------------------------------------

def embed_tokens(embedding, tokens):
    return jnp.take(embedding, tokens, axis=0).astype(ACT_DTYPE)


def lm_logits(x, head):
    """x (B, T, D) @ head (D, V) -> f32 logits."""
    return jnp.einsum("btd,dv->btv", x, head,
                      preferred_element_type=jnp.float32)


def serve_logits(x, head):
    """Serving head: x (B, T, D) @ head (D, V) -> f32 logits through the
    canonical tiled serve matmul of every weight mode
    (``runtime.weights.dense_matmul``),
    so the logits are the same bits whether the head is a raw array or was
    just decoded from ENEC streams, and whether it ran alone or in a batch
    (a one-row operand is padded to two, see :func:`_two_rows`)."""
    from repro.runtime.weights import dense_matmul
    b, t, d = x.shape
    x2 = x.reshape(b * t, d)
    out = dense_matmul(_two_rows(x2), head)[:b * t]
    return out.reshape(b, t, -1)


def cross_entropy(logits, targets, mask=None):
    """Mean next-token NLL in f32. logits (B,T,V), targets (B,T) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
