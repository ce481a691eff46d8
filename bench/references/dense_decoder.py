"""Plain float32 reference of a dense decoder as this repository serves it.

Independent of the program: it imports nothing from ``src/`` and builds
its own weights from the seed.  The weights follow the program's
documented initialisation (a truncated normal on [-2, 2], scaled by
``1/sqrt(fan_in)`` for projections and by 0.02 for the embedding, rounded
to bfloat16; norm gains zero) and its key schedule, so the same seed gives
the same bfloat16 weights.  Everything after the weights is float32 at
``highest`` matmul precision: no kernels, no cache, no batching.

Equations (per layer, pre-norm residual; every norm gain is zero at
initialisation, so the program's ``rms(x) * (1 + g)`` is ``rms(x)``)::

  h = rms(x)
  q, k, v = h Wq, h Wk, h Wv            heads of size head_dim; GQA
  q, k = rms(q), rms(k)                 if qk_norm (eps 1e-6)
  q, k = rope(q), rope(k)               all head_dim dims, halves rotated
  x = x + softmax(q k^T / sqrt(head_dim) + causal) v Wo
  h = rms(x)
  x = x + (silu(h Wgate) * (h Wup)) Wdown
  logits = rms(x) Whead

Departures from the published models are the program's, and the
configuration file lists them under ``departures`` (StableLM-3B-4E1T:
LayerNorm with bias and rotary on 25% of each head in the published
model; RMSNorm and full rotary here).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MATMUL = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# largest finite value of 4-exponent, 3-mantissa rounding
FP8_MAX = 240.0
QK_NORM_EPS = 1e-6
HEAD_SLICE = 16384


def sizes(config: dict) -> dict:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    return dict(
        d=d, layers=config["num_hidden_layers"], heads=heads,
        kv=config.get("num_key_value_heads", heads),
        hd=config.get("head_dim") or d // heads,
        ff=config["intermediate_size"], vocab=config["vocab_size"],
        theta=float(config["rope_theta"]),
        eps=float(config.get("rms_norm_eps",
                             config.get("layer_norm_eps"))),
        qk_norm=bool(config["serving"].get("qk_norm", False)),
        tied=bool(config.get("tie_word_embeddings", False)))


def _normal(key, shape, scale):
    w = jax.random.truncated_normal(key, -2, 2, shape, jnp.float32) * scale
    return w.astype(jnp.bfloat16)


def _proj(key, shape):
    return _normal(key, shape, 1.0 / math.sqrt(shape[0]))


def _layer(key, s):
    k_attn, k_mlp = jax.random.split(key, 4)[:2]
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    d, hd = s["d"], s["hd"]
    return {"wq": _proj(ka[0], (d, s["heads"] * hd)),
            "wk": _proj(ka[1], (d, s["kv"] * hd)),
            "wv": _proj(ka[2], (d, s["kv"] * hd)),
            "wo": _proj(ka[3], (s["heads"] * hd, d)),
            "w_gate": _proj(km[0], (d, s["ff"])),
            "w_up": _proj(km[1], (d, s["ff"])),
            "w_down": _proj(km[2], (s["ff"], d))}


def init_weights(seed: int, s: dict) -> dict:
    """bfloat16 weights from the seed: layer ``i`` from
    ``fold_in(split(key, L + 3)[i], 0)``, the head from key ``L + 1`` and
    the embedding from key ``L + 2``.  Norm gains are zero."""
    def make(key):
        ks = jax.random.split(key, s["layers"] + 3)
        layers = [_layer(jax.random.fold_in(ks[i], 0), s)
                  for i in range(s["layers"])]
        w = {"layers": {k: jnp.stack([lw[k] for lw in layers])
                        for k in MATMUL},
             "embed": _normal(ks[-1], (s["vocab"], s["d"]), 0.02)}
        if not s["tied"]:
            w["head"] = _proj(ks[-2], (s["d"], s["vocab"]))
        return w

    return jax.jit(make)(jax.random.key(int(seed) % 2**64))


def fp8_weights(w: dict) -> dict:
    """The control: every weight rounded to float8 e4m3 (4 exponent and 3
    mantissa bits) with one scale per output column (per row of the
    embedding), held as bfloat16.  ``reduce_precision`` does the rounding:
    a round trip through a float8 dtype may be folded away by the compiler
    on a TPU, which keeps excess precision."""
    def q(a, axis):
        a32 = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(a32), axis=axis, keepdims=True) / FP8_MAX
        scale = jnp.where(scale > 0, scale, 1.0)
        r = jax.lax.reduce_precision(a32 / scale, exponent_bits=4,
                                     mantissa_bits=3)
        return (r * scale).astype(jnp.bfloat16)

    def make(w):
        out = {"layers": {k: q(v, -2) for k, v in w["layers"].items()},
               "embed": q(w["embed"], -1)}
        if "head" in w:
            out["head"] = q(w["head"], -2)
        return out

    return jax.jit(make)(w)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x (T, H, hd): rotate the two halves of every head."""
    t, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f32(a):
    return a.astype(jnp.float32)


def forward(w: dict, tokens, s: dict):
    """tokens (T,) int32 -> logits (T, V) float32, causal."""
    t = tokens.shape[0]
    x = _f32(jnp.take(w["embed"], tokens, axis=0))
    causal = jnp.tril(jnp.ones((t, t), bool))
    grp = s["heads"] // s["kv"]

    def layer(x, lw):
        h = _rms(x, s["eps"])
        q = (h @ _f32(lw["wq"])).reshape(t, s["heads"], s["hd"])
        k = (h @ _f32(lw["wk"])).reshape(t, s["kv"], s["hd"])
        v = (h @ _f32(lw["wv"])).reshape(t, s["kv"], s["hd"])
        if s["qk_norm"]:
            q, k = _rms(q, QK_NORM_EPS), _rms(k, QK_NORM_EPS)
        q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
        k, v = jnp.repeat(k, grp, axis=1), jnp.repeat(v, grp, axis=1)
        scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(s["hd"])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        o = jnp.einsum("hts,shd->thd", probs, v).reshape(t, -1)
        x = x + o @ _f32(lw["wo"])
        h = _rms(x, s["eps"])
        x = x + (jax.nn.silu(h @ _f32(lw["w_gate"]))
                 * (h @ _f32(lw["w_up"]))) @ _f32(lw["w_down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms(x, s["eps"])
    head = w["embed"].T if s["tied"] else w["head"]
    # vocabulary in slices, so no float32 copy of the whole head is held
    return jnp.concatenate(
        [x @ _f32(head[:, i:i + HEAD_SLICE])
         for i in range(0, head.shape[1], HEAD_SLICE)], axis=-1)


class Reference:
    """The reference for one configuration and seed, with sequences padded
    to ``max_len`` so that one compiled program serves every request."""

    def __init__(self, config: dict, seed: int, max_len: int):
        self.s = sizes(config)
        self.max_len = int(max_len)
        self.w = init_weights(seed, self.s)

        def gaps(w, w_lo, tokens, targets):
            # at position p the served token is targets[p]; the gap is how
            # far its reference logit lies below the reference's best
            with jax.default_matmul_precision("highest"):
                ref = forward(w, tokens, self.s)
                best = ref.max(-1)
                served = best - jnp.take_along_axis(
                    ref, targets[:, None], -1)[:, 0]
                if w_lo is None:
                    return served, None
                pick = jnp.argmax(forward(w_lo, tokens, self.s), -1)
                lower = best - jnp.take_along_axis(
                    ref, pick[:, None], -1)[:, 0]
                return served, lower

        self._gaps = jax.jit(gaps)
        self._w_lo = None

    def _pad(self, prompt, served):
        """Model input (prompt + served tokens but the last) and the token
        served at each position, padded to ``max_len``."""
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        n = len(seq)
        if n > self.max_len:
            raise ValueError(f"sequence of {n} over max_len {self.max_len}")
        tokens = np.zeros(self.max_len, np.int32)
        tokens[:n] = seq
        targets = np.zeros(self.max_len, np.int32)
        p0 = len(prompt) - 1
        targets[p0:p0 + len(served)] = served
        return tokens, targets, p0, len(served)

    def served_gaps(self, prompt, served) -> np.ndarray:
        """Per served token: reference best logit minus the reference
        logit of the served token (0 where they agree)."""
        tokens, targets, p0, n = self._pad(prompt, served)
        g, _ = self._gaps(self.w, None, tokens, targets)
        return np.asarray(g)[p0:p0 + n]

    def control_gaps(self, prompt, served) -> tuple:
        """``(served, control)``: the served tokens' gaps as above, and at
        the same positions the gap of the token that the float8-weight
        control puts first."""
        if self._w_lo is None:
            self._w_lo = fp8_weights(self.w)
        tokens, targets, p0, n = self._pad(prompt, served)
        g, lo = self._gaps(self.w, self._w_lo, tokens, targets)
        return np.asarray(g)[p0:p0 + n], np.asarray(lo)[p0:p0 + n]
