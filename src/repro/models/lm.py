"""Unified decoder-only LM covering the dense / MoE / SSM-hybrid / xLSTM /
prefix-VLM families via per-period "block programs".

A *block program* is a list of per-layer descriptors, one period long; the
model is ``n_periods`` repetitions of it (Jamba: period 8 with one attention
layer and alternating MoE; xLSTM: period 4 = [m, m, m, s]; dense/MoE
transformers: period 1).  Parameters of each program position are stacked
over periods, so the layer stack runs either as ``lax.scan`` (compact HLO,
fast compile — runtime default) or as a statically unrolled Python loop
(exact cost_analysis — the dry-run's choice for small models, with the
scan-correction protocol of launch/roofline.py for the big ones).

Interface (all pure functions, pjit-ready):
  init(rng) -> params
  loss_fn(params, batch) -> (loss, metrics)
  prefill_fn(params, batch) -> (last_logits, cache)
  decode_fn(params, cache, tokens) -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.runtime.overlap import (build_schedule, overlap_enabled,
                                   pipeline_scan, pipeline_unrolled)
from repro.runtime.weights import is_handle
from repro.runtime.weights import resolve as resolve_weights

from . import moe as moe_lib
from . import ssm as ssm_lib
from . import xlstm as xlstm_lib
from .layers import (ACT_DTYPE, AttnParamsShape, attention_block,
                     attention_decode_block, cross_entropy, dense_init,
                     embed_init, embed_tokens, init_attention, init_mlp,
                     lm_logits, mlp_block, rms_norm, serve_logits)


class BlockDesc(NamedTuple):
    seq: str          # attn | mamba | mlstm | slstm
    ffn: Optional[str]  # mlp | moe | None


def block_program(cfg) -> list:
    """cfg -> list[BlockDesc] (one period)."""
    fam = cfg.family
    if fam in ("dense", "vlm", "audio"):
        return [BlockDesc("attn", "mlp")]
    if fam == "moe":
        return [BlockDesc("attn", "moe")]
    if fam == "ssm":      # xLSTM 3:1 mLSTM:sLSTM
        return [BlockDesc("mlstm", None), BlockDesc("mlstm", None),
                BlockDesc("mlstm", None), BlockDesc("slstm", None)]
    if fam == "hybrid":   # Jamba: attn 1-of-8, MoE every other layer
        out = []
        for i in range(8):
            seq = "attn" if i == 4 else "mamba"
            ffn = "moe" if i % 2 == 1 else "mlp"
            out.append(BlockDesc(seq, ffn))
        return out
    raise ValueError(fam)


def attn_shape(cfg) -> AttnParamsShape:
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    return AttnParamsShape(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
                           cfg.qk_norm)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_position(key, desc: BlockDesc, cfg):
    ks = jax.random.split(key, 4)
    p = {"pre_norm": jnp.zeros((cfg.d_model,), ACT_DTYPE)}
    if desc.seq == "attn":
        p["attn"] = init_attention(ks[0], attn_shape(cfg))
    elif desc.seq == "mamba":
        p["mamba"] = ssm_lib.init_mamba(ks[0], cfg.d_model, cfg.ssm_state,
                                        cfg.conv_dim)
    elif desc.seq == "mlstm":
        p["mlstm"] = xlstm_lib.init_mlstm(ks[0], cfg.d_model, cfg.n_heads)
    elif desc.seq == "slstm":
        p["slstm"] = xlstm_lib.init_slstm(ks[0], cfg.d_model, cfg.n_heads)
    if desc.ffn is not None:
        p["post_norm"] = jnp.zeros((cfg.d_model,), ACT_DTYPE)
    if desc.ffn == "mlp":
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff)
    elif desc.ffn == "moe":
        p["moe"] = moe_lib.init_moe(ks[1], cfg.d_model, cfg.moe_d_ff,
                                    cfg.n_experts)
    return p


def init_params(key, cfg):
    program = block_program(cfg)
    n_periods = cfg.n_layers // len(program)
    ks = jax.random.split(key, n_periods + 3)
    period = []
    for pos, desc in enumerate(program) if n_periods else []:
        stacks = [
            _init_position(jax.random.fold_in(ks[i], pos), desc, cfg)
            for i in range(n_periods)
        ]
        period.append(jax.tree.map(lambda *xs: jnp.stack(xs), *stacks))
    params = {
        "embed": embed_init(ks[-1], (cfg.vocab_size, cfg.d_model)),
        "period": period,
        "final_norm": jnp.zeros((cfg.d_model,), ACT_DTYPE),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(ks[-2], (cfg.d_model, cfg.vocab_size))
    return params


# ---------------------------------------------------------------------------
# forward blocks
# ---------------------------------------------------------------------------

def _apply_position(p, desc: BlockDesc, cfg, x, positions, *,
                    prefix_len: int = 0):
    """Full-sequence forward of one block. Returns (x, cache_entry, aux)."""
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    cache_entry = None
    if desc.seq == "attn":
        out, kv = attention_block(p["attn"], h, attn_shape(cfg), positions,
                                  cfg.rope_theta, causal=True,
                                  prefix_len=prefix_len,
                                  chunk=cfg.attn_chunk)
        cache_entry = {"k": kv[0], "v": kv[1]}
    elif desc.seq == "mamba":
        out, cache_entry = ssm_lib.mamba_forward(p["mamba"], h, cfg.ssm_state,
                                                 cfg.conv_dim)
    elif desc.seq == "mlstm":
        out, cache_entry = xlstm_lib.mlstm_forward(p["mlstm"], h, cfg.n_heads)
    elif desc.seq == "slstm":
        out, cache_entry = xlstm_lib.slstm_forward(p["slstm"], h)
    x = x + out
    aux = {"lb_loss": jnp.float32(0), "z_loss": jnp.float32(0)}
    if desc.ffn is not None:
        h = rms_norm(x, p["post_norm"], cfg.norm_eps)
        if desc.ffn == "mlp":
            x = x + mlp_block(p["mlp"], h)
        else:
            out, aux = moe_lib.moe_block(p["moe"], h, cfg.experts_per_token,
                                         cfg.moe_combine_dtype,
                                         cfg.moe_dispatch_a2a)
            x = x + out
    return x, cache_entry, aux


def _apply_position_step(p, desc: BlockDesc, cfg, x, cache, lengths):
    """One-token decode of one block, reading its cache entry.  Returns
    (x, step): an attention block's new token ``{"k", "v"}`` (B, KV, hd),
    which :func:`_write_step` stores, or a recurrent block's new state."""
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if desc.seq == "attn":
        out, kv = attention_decode_block(
            p["attn"], h, attn_shape(cfg), (cache["k"], cache["v"]),
            lengths, cfg.rope_theta, score_shard=cfg.decode_score_shard)
        step = {"k": kv[0], "v": kv[1]}
    elif desc.seq == "mamba":
        out, step = ssm_lib.mamba_step(p["mamba"], h, cache, cfg.ssm_state)
    elif desc.seq == "mlstm":
        out, step = xlstm_lib.mlstm_step(p["mlstm"], h, cache, cfg.n_heads)
    elif desc.seq == "slstm":
        out, step = xlstm_lib.slstm_step(p["slstm"], h, cache)
    x = x + out
    if desc.ffn == "mlp":
        x = x + mlp_block(p["mlp"], rms_norm(x, p["post_norm"], cfg.norm_eps))
    elif desc.ffn == "moe":
        out, _ = moe_lib.moe_block(p["moe"], rms_norm(x, p["post_norm"],
                                                      cfg.norm_eps),
                                   cfg.experts_per_token,
                                   cfg.moe_combine_dtype,
                                   cfg.moe_dispatch_a2a)
        x = x + out
    return x, step


def _write_step(program, entries, steps, lengths):
    """Store one decode step's per-layer outputs (stacked over periods) in
    the cache, touching rows [0, B) only (B = ``lengths``' size).
    Attention entries take each row's new K and V, for every layer at
    once, at position ``lengths[row]``: one in-place
    ``dynamic_update_slice`` per row.  (A single scatter would do the same
    work, but XLA:TPU runs a scatter only in the default layout, so it
    would copy the whole cache out of its compact one and back.)
    Recurrent states are small and are replaced over those rows whole."""
    out = []
    for desc, entry, step in zip(program, entries, steps):
        if desc.seq == "attn":
            new = {}
            for n in ("k", "v"):
                ring = entry[n]
                upd = step[n].astype(ring.dtype)[:, :, None]
                for row in range(lengths.shape[0]):
                    ring = jax.lax.dynamic_update_slice(
                        ring, upd[:, row:row + 1],
                        (0, row, lengths[row], 0, 0))
                new[n] = ring
            out.append(new)
        else:
            out.append(jax.tree.map(
                lambda full, part: jax.lax.dynamic_update_slice_in_dim(
                    full, part.astype(full.dtype), 0, axis=1),
                entry, step))
    return out


# ---------------------------------------------------------------------------
# layer-stack drivers (scan or unrolled)
# ---------------------------------------------------------------------------

def _remat_policy(cfg):
    return {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }[cfg.remat_policy]


def _dense_leaf(leaf):
    """Materialize a top-level weight handle (the policy may stream even
    non-stacked 2-D leaves like ``embed``/``head`` as L=1 stacks); plain
    arrays pass through."""
    return leaf.materialize() if is_handle(leaf) else leaf


def _wrap_body(cfg, body):
    return jax.checkpoint(body, prevent_cse=False,
                          policy=_remat_policy(cfg)) if cfg.remat else body


def _run_stack(params, cfg, x, positions, *, prefix_len=0, want_cache=False):
    """Forward through all periods. Returns (x, caches, aux_sum).

    Weight-execution handles (runtime/weights.py) in the period stack are
    resolved per layer slice.  When the overlap policy is active
    (``cfg.overlap`` + streamed leaves present), the loop runs as the
    double-buffered prefetch pipeline of ``runtime.overlap`` — layer l+1's
    batched decode is issued before layer l's matmuls; otherwise streams
    decode serially inside their own layer.  Logits are bit-identical
    either way (only scheduling moves).
    """
    program = block_program(cfg)
    n_periods = cfg.n_layers // len(program)
    period = params["period"]
    if n_periods == 0:  # 0-layer variant used by the dry-run cost protocol
        return x, None, jnp.float32(0)

    def period_body(x, sliced):
        aux_sum = jnp.float32(0)
        caches = []
        for pos, desc in enumerate(program):
            p = resolve_weights(sliced[pos])
            x, cache_entry, aux = _apply_position(
                p, desc, cfg, x, positions, prefix_len=prefix_len)
            caches.append(cache_entry)
            aux_sum = aux_sum + aux["lb_loss"] + 1e-3 * aux["z_loss"]
        return x, caches, aux_sum

    if overlap_enabled(getattr(cfg, "overlap", "auto"), period):
        schedule = build_schedule(period, n_periods)

        def apply_fn(carry, sliced, _extra, _i):
            x, aux_acc = carry
            x, caches, aux = period_body(x, sliced)
            out = [c for c in caches if c is not None] if want_cache else None
            return (x, aux_acc + aux), out

        if cfg.scan_layers:
            (x, aux_sum), caches = pipeline_scan(
                schedule, apply_fn, (x, jnp.float32(0)),
                wrap=partial(_wrap_body, cfg))
            return x, caches, aux_sum
        (x, aux_sum), cache_list = pipeline_unrolled(
            schedule, apply_fn, (x, jnp.float32(0)),
            wrap=partial(_wrap_body, cfg))
        if want_cache and cache_list and cache_list[0]:
            caches = jax.tree.map(lambda *xs: jnp.stack(xs), *cache_list)
        else:
            caches = None
        return x, caches, aux_sum

    if cfg.scan_layers:
        def scan_body(carry, sliced):
            x, aux_acc = carry
            x, caches, aux = period_body(x, sliced)
            out = [c for c in caches if c is not None] if want_cache else None
            return (x, aux_acc + aux), out

        body = _wrap_body(cfg, scan_body)
        (x, aux_sum), stacked = jax.lax.scan(body, (x, jnp.float32(0)), period)
        caches = stacked
    else:
        aux_sum = jnp.float32(0)
        cache_list = []
        body = _wrap_body(cfg, period_body)
        for i in range(n_periods):
            sliced = jax.tree.map(lambda a: a[i], period)
            x, caches_i, aux = body(x, sliced)
            cache_list.append([c for c in caches_i if c is not None])
            aux_sum = aux_sum + aux
        if want_cache and cache_list and cache_list[0]:
            caches = jax.tree.map(lambda *xs: jnp.stack(xs), *cache_list)
        else:
            caches = None
    return x, caches, aux_sum


def _assemble_inputs(params, cfg, batch):
    """tokens (+ optional modality prefix embeddings) -> (x, positions,
    prefix_len)."""
    x = embed_tokens(_dense_leaf(params["embed"]), batch["tokens"])
    prefix_len = 0
    if cfg.prefix_embed and "prefix_embeds" in batch:
        pe = batch["prefix_embeds"].astype(ACT_DTYPE)
        x = jnp.concatenate([pe, x], axis=1)
        prefix_len = pe.shape[1]
    positions = jnp.arange(x.shape[1])[None, :]
    return x, positions, prefix_len


def forward(params, cfg, batch, *, want_cache=False):
    x, positions, prefix_len = _assemble_inputs(params, cfg, batch)
    x, caches, aux = _run_stack(params, cfg, x, positions,
                                prefix_len=prefix_len, want_cache=want_cache)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = (_dense_leaf(params["embed"]).T if cfg.tie_embeddings
            else _dense_leaf(params["head"]))
    return x, caches, aux, head, prefix_len


def loss_fn(params, cfg, batch):
    x, _, aux, head, prefix_len = forward(params, cfg, batch)
    logits = lm_logits(x[:, prefix_len:], head)
    targets = batch["targets"]
    mask = batch.get("loss_mask")
    loss = cross_entropy(logits[:, :-1], targets[:, 1:],
                         None if mask is None else mask[:, 1:])
    total = loss + 1e-2 * aux
    return total, {"nll": loss, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int):
    """Abstract-friendly cache init (all zeros; shapes static)."""
    program = block_program(cfg)
    n_periods = cfg.n_layers // len(program)
    s = attn_shape(cfg)
    entries = []
    for desc in program:
        if desc.seq == "attn":
            e = {"k": jnp.zeros((n_periods, batch, max_len, s.n_kv_heads,
                                 s.head_dim), ACT_DTYPE),
                 "v": jnp.zeros((n_periods, batch, max_len, s.n_kv_heads,
                                 s.head_dim), ACT_DTYPE)}
        elif desc.seq == "mamba":
            c = ssm_lib.init_mamba_cache(cfg.d_model, cfg.ssm_state,
                                         cfg.conv_dim, batch)
            e = jax.tree.map(lambda a: jnp.stack([a] * n_periods), c)
        elif desc.seq == "mlstm":
            c = xlstm_lib.init_mlstm_cache(cfg.d_model, cfg.n_heads, batch)
            e = jax.tree.map(lambda a: jnp.stack([a] * n_periods), c)
        elif desc.seq == "slstm":
            c = xlstm_lib.init_slstm_cache(cfg.d_model, batch)
            e = jax.tree.map(lambda a: jnp.stack([a] * n_periods), c)
        entries.append(e)
    return {"entries": entries, "lengths": jnp.zeros((batch,), jnp.int32)}


def prefill_fn(params, cfg, batch, max_len: int):
    """Run the prompt, build the cache. Returns (last_token_logits, cache)."""
    x, caches, _, head, prefix_len = forward(params, cfg, batch,
                                             want_cache=True)
    b, t = x.shape[0], x.shape[1]
    logits = serve_logits(x[:, -1:], head)[:, 0]  # forward() normed x
    cache = init_cache(cfg, b, max_len)
    # install prefill state: attn K/V into the cache prefix, SSM/xLSTM final
    # states wholesale
    if caches is not None:
        for pos, desc in enumerate(block_program(cfg)):
            entry = cache["entries"][pos]
            got = caches[pos]
            if desc.seq == "attn":
                entry["k"] = jax.lax.dynamic_update_slice_in_dim(
                    entry["k"], got["k"].astype(ACT_DTYPE), 0, axis=2)
                entry["v"] = jax.lax.dynamic_update_slice_in_dim(
                    entry["v"], got["v"].astype(ACT_DTYPE), 0, axis=2)
            else:
                cache["entries"][pos] = jax.tree.map(
                    lambda new, old: new.astype(old.dtype), got, entry)
    cache["lengths"] = jnp.full((b,), t, jnp.int32)
    return logits, cache


def decode_fn(params, cfg, cache, tokens):
    """One decode step. tokens: (B,) int32. Returns (logits (B, V), cache).

    The cache's entries may hold more rows than the batch (a serving
    engine's slot ring): the step reads and writes rows [0, B) only, and
    writes each attention layer one position per row (:func:`_write_step`).
    ``cache["lengths"]`` is (B,)."""
    program = block_program(cfg)
    n_periods = cfg.n_layers // len(program)
    embed = _dense_leaf(params["embed"])
    x = embed_tokens(embed, tokens[:, None])
    lengths = cache["lengths"]
    period = params["period"]
    entries = cache["entries"]
    if n_periods == 0:  # 0-layer variant used by the dry-run cost protocol
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = embed.T if cfg.tie_embeddings else _dense_leaf(params["head"])
        return serve_logits(x, head)[:, 0], dict(cache, lengths=lengths + 1)

    def period_body(x, sliced_params, sliced_cache):
        steps = []
        for pos, desc in enumerate(program):
            p = resolve_weights(sliced_params[pos])
            rows = jax.tree.map(lambda a: a[:tokens.shape[0]],
                                sliced_cache[pos])
            x, step = _apply_position_step(p, desc, cfg, x, rows, lengths)
            steps.append(step)
        return x, steps

    if overlap_enabled(getattr(cfg, "overlap", "auto"), period):
        schedule = build_schedule(period, n_periods)

        def apply_fn(x, sliced, sliced_cache, _i):
            return period_body(x, sliced, sliced_cache)

        if cfg.scan_layers:
            x, steps = pipeline_scan(schedule, apply_fn, x, xs_extra=entries)
        else:
            x, outs = pipeline_unrolled(schedule, apply_fn, x,
                                        xs_extra=entries)
            steps = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    elif cfg.scan_layers:
        def scan_body(x, sl):
            sp, sc = sl
            return period_body(x, sp, sc)

        x, steps = jax.lax.scan(scan_body, x, (period, entries))
    else:
        outs = []
        for i in range(n_periods):
            sp = jax.tree.map(lambda a: a[i], period)
            sc = jax.tree.map(lambda a: a[i], entries)
            x, step = period_body(x, sp, sc)
            outs.append(step)
        steps = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    cache = {"entries": _write_step(program, entries, steps, lengths),
             "lengths": lengths + 1}
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = embed.T if cfg.tie_embeddings else _dense_leaf(params["head"])
    return serve_logits(x, head)[:, 0], cache
