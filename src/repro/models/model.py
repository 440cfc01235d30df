"""Model assembly: layer planning, scan-over-layers, train/prefill/decode.

One code path serves all 10 assigned architectures; an ``ArchConfig`` fully
determines block flavours.  Layers are planned into homogeneous *segments*
(cyclic pattern units or maximal runs) so parameters stack and
``lax.scan`` runs one compiled block body per segment — this is what keeps
61-layer/46-layer archs compilable and is remat-friendly.

Batch contracts (see launch/specs.py):
  train:   {"tokens": (B,T) i32, "labels": (B,T) i32, ["positions"],
            ["patch_embeds" (B,P,d) for vlm], ["frames" (B,F,d) audio]}
  prefill: same minus labels → returns (last-position logits, cache)
  decode:  tokens (B,1) + cache + pos scalar → (logits, new cache)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import ops as fa_ops
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.models.layers import (apply_norm, apply_ffn, dtype_of, embed_init,
                                 init_ffn, init_norm, softcap)
from repro.obs import scopes

# --------------------------------------------------------------------------
# layer planning
# --------------------------------------------------------------------------

Kind = Tuple[str, str]  # (flavour: g|l|r|m|s, ffn: d|e|n)


def layer_kinds(cfg) -> List[Kind]:
    kinds = []
    for l in range(cfg.n_layers):
        fl = cfg.pattern_at(l)
        if cfg.moe_at(l):
            f = "e"
        elif cfg.d_ff and cfg.d_ff > 0:
            f = "d"
        else:
            f = "n"
        kinds.append((fl, f))
    return kinds


def plan_segments(kinds: List[Kind]) -> List[Tuple[Tuple[Kind, ...], int]]:
    """Segment layers into (unit, count) scans: cyclic unit detection first,
    maximal identical runs as fallback."""
    n = len(kinds)
    for ulen in range(1, 9):
        cnt = n // ulen
        if cnt < 2:
            break
        if all(kinds[i] == kinds[i % ulen] for i in range(cnt * ulen)):
            segs = [(tuple(kinds[:ulen]), cnt)]
            if n % ulen:
                segs.append((tuple(kinds[cnt * ulen:]), 1))
            return segs
    segs: List[Tuple[Tuple[Kind, ...], int]] = []
    i = 0
    while i < n:
        j = i
        while j < n and kinds[j] == kinds[i]:
            j += 1
        segs.append(((kinds[i],), j - i))
        i = j
    return segs


# --------------------------------------------------------------------------
# block init/apply
# --------------------------------------------------------------------------

def _init_mixer(cfg, flavour: str, key):
    if flavour in ("g", "l"):
        return attn.init_attention(cfg, key)
    if flavour == "r":
        return rec.init_rglru(cfg, key)
    if flavour == "m":
        return rec.init_mlstm(cfg, key)
    if flavour == "s":
        return rec.init_slstm(cfg, key)
    raise ValueError(flavour)


def _mixer_scope(flavour: str):
    """The device-trace scope of a block's mixer (``repro.obs.scopes``)."""
    return jax.named_scope(scopes.ATTENTION if flavour in ("g", "l")
                           else scopes.RECURRENT)


def _ffn_scope(p):
    return jax.named_scope(scopes.MOE if "moe" in p else scopes.FFN)


def init_block(cfg, kind: Kind, key, cross: bool = False):
    fl, ff = kind
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {
        "norm1": init_norm(cfg),
        "mixer": _init_mixer(cfg, fl, ks[0]),
    }
    if cfg.post_norm:
        p["norm1_post"] = init_norm(cfg)
    if cross:
        p["norm_x"] = init_norm(cfg)
        p["cross"] = attn.init_attention(cfg, ks[1])
    if ff == "d":
        p["norm2"] = init_norm(cfg)
        p["ffn"] = init_ffn(cfg, ks[2])
        if cfg.post_norm:
            p["norm2_post"] = init_norm(cfg)
    elif ff == "e":
        p["norm2"] = init_norm(cfg)
        p["moe"] = moe_mod.init_moe(cfg, ks[3])
        if cfg.post_norm:
            p["norm2_post"] = init_norm(cfg)
    return p


def _mixer_train(cfg, kind, p, x, positions):
    fl = kind[0]
    if fl in ("g", "l"):
        window = cfg.window if fl == "l" else None
        if cfg.mla is not None:
            return attn.mla_train(cfg, p["mixer"], x, positions)
        return attn.attention_train(cfg, p["mixer"], x, positions,
                                    window=window)
    if fl == "r":
        return rec.rglru_train(cfg, p["mixer"], x)
    if fl == "m":
        return rec.mlstm_train(cfg, p["mixer"], x)
    return rec.slstm_train(cfg, p["mixer"], x)


def apply_block_train(cfg, kind, p, x, positions, enc_out=None,
                      enc_positions=None, train=False):
    h = apply_norm(cfg, p["norm1"], x)
    with _mixer_scope(kind[0]):
        h = _mixer_train(cfg, kind, p, h, positions)
    if cfg.post_norm:
        h = apply_norm(cfg, p["norm1_post"], h)
    x = x + h
    if "cross" in p:
        h = apply_norm(cfg, p["norm_x"], x)
        h = _cross_attend(cfg, p["cross"], h, enc_out, positions, enc_positions)
        x = x + h
    aux = jnp.zeros((), jnp.float32)
    if "ffn" in p or "moe" in p:
        h = apply_norm(cfg, p["norm2"], x)
        with _ffn_scope(p):
            if "moe" in p:
                h, aux = moe_mod.apply_moe(cfg, p["moe"], h, train=train)
            else:
                h = apply_ffn(cfg, p["ffn"], h)
        if cfg.post_norm:
            h = apply_norm(cfg, p["norm2_post"], h)
        x = x + h
    return x, aux


@jax.named_scope(scopes.ATTENTION)
def _cross_attend(cfg, p, x, enc_out, positions, enc_positions):
    """Encoder-decoder cross attention (whisper); no causal mask."""
    dt = x.dtype
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"].astype(dt)).reshape(B, T, cfg.n_heads, hd)
    S = enc_out.shape[1]
    k = (enc_out @ p["wk"].astype(dt)).reshape(B, S, cfg.n_kv_heads, hd)
    v = (enc_out @ p["wv"].astype(dt)).reshape(B, S, cfg.n_kv_heads, hd)
    bias = jnp.zeros((B, T, S), jnp.float32)
    o = attn._attend_full(q, k, v, bias, hd ** -0.5, None)
    return o.reshape(B, T, -1) @ p["wo"].astype(dt)


# --- decode ----------------------------------------------------------------

def init_layer_cache(cfg, kind: Kind, batch: int, max_len: int,
                     cross_len: int = 0):
    fl = kind[0]
    c: Dict[str, Any] = {}
    if fl in ("g", "l"):
        window = cfg.window if fl == "l" else None
        c.update(attn.init_cache(cfg, batch, max_len, window=window))
    elif fl == "r":
        c.update(rec.rglru_init_state(cfg, batch))
    elif fl == "m":
        c.update(rec.mlstm_init_state(cfg, batch))
    elif fl == "s":
        c.update(rec.slstm_init_state(cfg, batch))
    if cross_len:
        hd = cfg.resolved_head_dim
        dt = dtype_of(cfg.dtype)
        c["xk"] = jnp.zeros((batch, cross_len, cfg.n_kv_heads, hd), dt)
        c["xv"] = jnp.zeros((batch, cross_len, cfg.n_kv_heads, hd), dt)
    return c


def apply_block_decode(cfg, kind, p, x, cache, pos):
    fl = kind[0]
    h = apply_norm(cfg, p["norm1"], x)
    new_cache = dict(cache)
    if fl in ("g", "l"):
        window = cfg.window if fl == "l" else None
        if cfg.mla is not None:
            h, upd = attn.mla_decode(cfg, p["mixer"], h,
                                     {k: cache[k] for k in ("c_kv", "k_pe")},
                                     pos)
        else:
            h, upd = attn.attention_decode(cfg, p["mixer"], h,
                                           {k: cache[k] for k in ("k", "v")},
                                           pos, window=window)
        new_cache.update(upd)
    elif fl == "r":
        h, upd = rec.rglru_decode(cfg, p["mixer"], h,
                                  {k: cache[k] for k in ("h", "conv")})
        new_cache.update(upd)
    elif fl == "m":
        h, upd = rec.mlstm_decode(cfg, p["mixer"], h,
                                  {k: cache[k] for k in ("C", "n", "m")})
        new_cache.update(upd)
    else:
        h, upd = rec.slstm_decode(cfg, p["mixer"], h,
                                  {k: cache[k] for k in ("c", "n", "m", "h")})
        new_cache.update(upd)
    if cfg.post_norm:
        h = apply_norm(cfg, p["norm1_post"], h)
    x = x + h
    if "cross" in p:
        h = apply_norm(cfg, p["norm_x"], x)
        B = x.shape[0]
        hd = cfg.resolved_head_dim
        q = (h @ p["cross"]["wq"].astype(h.dtype)).reshape(B, 1, cfg.n_heads, hd)
        bias = jnp.zeros((B, 1, cache["xk"].shape[1]), jnp.float32)
        o = attn._attend_full(q, cache["xk"], cache["xv"], bias, hd ** -0.5, None)
        x = x + o.reshape(B, 1, -1) @ p["cross"]["wo"].astype(h.dtype)
    if "ffn" in p or "moe" in p:
        h = apply_norm(cfg, p["norm2"], x)
        if "moe" in p:
            h, _ = moe_mod.apply_moe(cfg, p["moe"], h)
        else:
            h = apply_ffn(cfg, p["ffn"], h)
        if cfg.post_norm:
            h = apply_norm(cfg, p["norm2_post"], h)
        x = x + h
    return x, new_cache


# --------------------------------------------------------------------------
# whole-model init
# --------------------------------------------------------------------------

def init_params(cfg, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    pdt = dtype_of(cfg.param_dtype)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model, pdt),
        "final_norm": init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(ks[1], cfg.vocab, cfg.d_model, pdt).T

    segs = plan_segments(layer_kinds(cfg))
    cross = cfg.enc_dec
    seg_params = {}
    for si, (unit, count) in enumerate(segs):
        def init_one(k, unit=unit):
            uks = jax.random.split(k, len(unit))
            return {f"u{ui}": init_block(cfg, kind, uks[ui], cross=cross)
                    for ui, kind in enumerate(unit)}

        keys = jax.random.split(jax.random.fold_in(ks[2], si), count)
        seg_params[f"seg{si}"] = jax.vmap(init_one)(keys)
    params["segments"] = seg_params

    if cfg.enc_dec:
        enc_kinds = [("g", "d")] * cfg.n_encoder_layers

        def init_enc(k):
            return {"u0": init_block(cfg, ("g", "d"), k, cross=False)}

        keys = jax.random.split(ks[3], cfg.n_encoder_layers)
        params["encoder"] = {
            "blocks": jax.vmap(init_enc)(keys),
            "final_norm": init_norm(cfg),
        }
    return params


# --------------------------------------------------------------------------
# embeddings / positions
# --------------------------------------------------------------------------

def _embed_tokens(cfg, params, tokens):
    x = params["embed"][tokens].astype(dtype_of(cfg.dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x


@jax.named_scope(scopes.EMBED)
def _input_sequence(cfg, params, batch):
    """tokens (+ modality stubs) → (x, positions, text_offset)."""
    x = _embed_tokens(cfg, params, batch["tokens"])
    B, T = batch["tokens"].shape
    offset = 0
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([pe, x], axis=1)
        offset = pe.shape[1]
    L = x.shape[1]
    if "positions" in batch:
        positions = batch["positions"]
    else:
        positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    return x, positions, offset


# --------------------------------------------------------------------------
# forward: train loss
# --------------------------------------------------------------------------

_LOSS_CHUNK = 512


def lm_head_logits(cfg, params, h):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ w.astype(h.dtype)
    return softcap(logits, cfg.logit_softcap)


def _chunked_loss(cfg, params, h, labels, mask):
    """Cross-entropy without materializing (B, T, V) at once."""
    B, T, d = h.shape
    c = min(_LOSS_CHUNK, T)
    n = -(-T // c)
    pad = n * c - T
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hc = h.reshape(B, n, c, d).swapaxes(0, 1)
    lc = labels.reshape(B, n, c).swapaxes(0, 1)
    mc = mask.reshape(B, n, c).swapaxes(0, 1)

    def step(tot, inp):
        hh, ll, mm = inp
        logits = lm_head_logits(cfg, params, hh).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mm
        return tot + nll.sum(), None

    tot, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (hc, lc, mc))
    return tot / jnp.maximum(mask.sum(), 1.0)


def _run_encoder(cfg, params, frames):
    x = frames.astype(dtype_of(cfg.dtype))
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(carry, p_l):
        h, _ = apply_block_train(cfg, ("g", "d"), p_l["u0"], carry, positions)
        # encoder is bidirectional: rerun mixer non-causally is handled by
        # attention flavour below — see note.
        return h, None

    # Bidirectional: temporarily run attention without the causal mask by
    # passing causal=False through a local closure.
    def enc_block(x, p_l):
        h = apply_norm(cfg, p_l["norm1"], x)
        with jax.named_scope(scopes.ATTENTION):
            h = attn.attention_train(cfg, p_l["mixer"], h, positions,
                                     causal=False)
        x = x + h
        h = apply_norm(cfg, p_l["norm2"], x)
        with jax.named_scope(scopes.FFN):
            h = apply_ffn(cfg, p_l["ffn"], h)
        return x + h

    def scan_body(carry, p_l):
        return enc_block(carry, p_l["u0"]), None

    x, _ = jax.lax.scan(scan_body, x, params["encoder"]["blocks"])
    return apply_norm(cfg, params["encoder"]["final_norm"], x), positions


_SEQ_SHARD_RESIDUAL = False  # perf knob: Megatron-style sequence parallelism


def set_seq_shard_residual(on: bool) -> None:
    global _SEQ_SHARD_RESIDUAL
    _SEQ_SHARD_RESIDUAL = on


def _sp_constraint(h):
    """Shard the residual stream's sequence dim over the model axis between
    blocks (norms/elementwise run on T/tp, converts XLA's per-layer
    all-reduce into reduce-scatter + all-gather)."""
    if not _SEQ_SHARD_RESIDUAL:
        return h
    try:
        from jax.sharding import PartitionSpec as P
        return jax.lax.with_sharding_constraint(h, P(None, "model", None))
    except Exception:  # no mesh in scope (single-device tests)
        return h


def _run_segments(cfg, params, x, positions, enc_out=None, enc_positions=None,
                  remat=None, train=False):
    segs = plan_segments(layer_kinds(cfg))
    aux_total = jnp.zeros((), jnp.float32)
    use_remat = cfg.remat if remat is None else remat
    for si, (unit, count) in enumerate(segs):
        stacked = params["segments"][f"seg{si}"]

        def body(carry, p_l, unit=unit):
            h, aux = carry
            for ui, kind in enumerate(unit):
                h = _sp_constraint(h)
                h, a = apply_block_train(cfg, kind, p_l[f"u{ui}"], h,
                                         positions, enc_out, enc_positions,
                                         train=train)
                aux = aux + a
            return (h, aux), None

        if use_remat:
            # Perf iteration 2 (EXPERIMENTS.md §Perf/phi4): saving matmul
            # outputs means the backward pass does not replay the forward's
            # row-parallel all-reduces (TP collectives) or the matmul FLOPs;
            # only cheap elementwise work is recomputed.  The flash kernel's
            # output and log-sum-exp are saved too, so the replay does not
            # rerun the forward kernel.
            policy = (None if _REMAT_POLICY == "full" else _DOTS_POLICY)
            body = jax.checkpoint(body, prevent_cse=False, policy=policy)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), stacked)
    return x, aux_total


_REMAT_POLICY = "dots"  # dots (optimized) | full (baseline everything-remat)
_DOTS_POLICY = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(*fa_ops.RESIDUALS))


def set_remat_policy(mode: str) -> None:
    global _REMAT_POLICY
    assert mode in ("dots", "full")
    _REMAT_POLICY = mode


def loss_fn(cfg, params, batch):
    """Mean next-token cross-entropy (+ MoE aux)."""
    x, positions, offset = _input_sequence(cfg, params, batch)
    enc_out = enc_pos = None
    if cfg.enc_dec:
        enc_out, enc_pos = _run_encoder(cfg, params, batch["frames"])
    # train=True turns on MoE capacity dropping (a throughput device that is
    # row-length dependent, so eval/prefill/decode paths run dropless).
    x, aux = _run_segments(cfg, params, x, positions, enc_out, enc_pos,
                           train=True)
    with jax.named_scope(scopes.LOSS):
        x = apply_norm(cfg, params["final_norm"], x)
        if offset:
            x = x[:, offset:]
        labels = batch["labels"]
        mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
        loss = _chunked_loss(cfg, params, x, labels, mask.astype(jnp.float32))
    return loss + aux


# --------------------------------------------------------------------------
# forward: prefill & decode
# --------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int):
    segs = plan_segments(layer_kinds(cfg))
    cross_len = cfg.encoder_len if cfg.enc_dec else 0
    caches = {}
    for si, (unit, count) in enumerate(segs):
        def one(_, unit=unit):
            return {f"u{ui}": init_layer_cache(cfg, kind, batch, max_len,
                                               cross_len)
                    for ui, kind in enumerate(unit)}

        caches[f"seg{si}"] = jax.vmap(one)(jnp.arange(count))
    return caches


def prefill(cfg, params, batch, max_len: int):
    """Run the prompt through the model; return (last logits, cache at
    position T).  Implemented as train-mode forward + cache capture."""
    x, positions, offset = _input_sequence(cfg, params, batch)
    enc_out = enc_pos = None
    if cfg.enc_dec:
        enc_out, enc_pos = _run_encoder(cfg, params, batch["frames"])
    B, T = x.shape[0], x.shape[1]
    max_len = max(max_len, T)  # modality stubs may extend the sequence

    segs = plan_segments(layer_kinds(cfg))
    caches = {}
    for si, (unit, count) in enumerate(segs):
        stacked = params["segments"][f"seg{si}"]

        def body(h, p_l, unit=unit):
            cache_l = {}
            for ui, kind in enumerate(unit):
                h, c = _prefill_block(cfg, kind, p_l[f"u{ui}"], h, positions,
                                      max_len, enc_out, enc_pos)
                cache_l[f"u{ui}"] = c
            return h, cache_l

        x, caches[f"seg{si}"] = jax.lax.scan(body, x, stacked)
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_head_logits(cfg, params, x[:, -1:])
    return logits[:, 0], caches


def _prefill_block(cfg, kind, p, x, positions, max_len, enc_out, enc_pos):
    """Block forward that also captures the decode cache."""
    fl = kind[0]
    h = apply_norm(cfg, p["norm1"], x)
    cache: Dict[str, Any] = {}
    B, T = x.shape[:2]
    dt = dtype_of(cfg.dtype)
    if fl in ("g", "l"):
        window = cfg.window if fl == "l" else None
        if cfg.mla is not None:
            h2, cache = _mla_prefill(cfg, p["mixer"], h, positions, max_len)
        else:
            q, k, v = attn._project_qkv(cfg, p["mixer"], h, positions)
            o = attn._dispatch_attend(q, k, v, positions, positions, window,
                                      True, cfg.resolved_head_dim ** -0.5,
                                      cfg.attn_softcap)
            h2 = o.reshape(B, T, -1) @ p["mixer"]["wo"].astype(h.dtype)
            S = min(window, max_len) if window else max_len
            if window and T >= S:
                ck = jnp.roll(k[:, T - S:], shift=T % S, axis=1)
                cv = jnp.roll(v[:, T - S:], shift=T % S, axis=1)
            else:
                ck = jnp.zeros((B, S) + k.shape[2:], dt)
                ck = jax.lax.dynamic_update_slice(
                    ck, k.astype(dt), (0, 0, 0, 0))
                cv = jnp.zeros((B, S) + v.shape[2:], dt)
                cv = jax.lax.dynamic_update_slice(
                    cv, v.astype(dt), (0, 0, 0, 0))
            cache = {"k": ck, "v": cv}
        h = h2
    elif fl == "r":
        u = h @ p["mixer"]["w_in"].astype(h.dtype)
        h2 = rec.rglru_train(cfg, p["mixer"], h)
        # recurrent state at T: recompute last hidden via scan tail
        conv_state = u[:, -(rec._CONV_W - 1):, :].astype(dt)
        full = _rglru_hidden(cfg, p["mixer"], h)
        cache = {"h": full[:, -1].astype(jnp.float32), "conv": conv_state}
        h = h2
    elif fl == "m":
        h2, state = _mlstm_prefill(cfg, p["mixer"], h)
        cache = state
        h = h2
    else:
        h2, state = _slstm_prefill(cfg, p["mixer"], h)
        cache = state
        h = h2
    if cfg.post_norm:
        h = apply_norm(cfg, p["norm1_post"], h)
    x = x + h
    if "cross" in p:
        hx = apply_norm(cfg, p["norm_x"], x)
        hx2 = _cross_attend(cfg, p["cross"], hx, enc_out, positions, enc_pos)
        x = x + hx2
        hd = cfg.resolved_head_dim
        S = enc_out.shape[1]
        cache["xk"] = (enc_out @ p["cross"]["wk"].astype(x.dtype)).reshape(
            B, S, cfg.n_kv_heads, hd).astype(dt)
        cache["xv"] = (enc_out @ p["cross"]["wv"].astype(x.dtype)).reshape(
            B, S, cfg.n_kv_heads, hd).astype(dt)
    if "ffn" in p or "moe" in p:
        hh = apply_norm(cfg, p["norm2"], x)
        if "moe" in p:
            hh, _ = moe_mod.apply_moe(cfg, p["moe"], hh)
        else:
            hh = apply_ffn(cfg, p["ffn"], hh)
        if cfg.post_norm:
            hh = apply_norm(cfg, p["norm2_post"], hh)
        x = x + hh
    return x, cache


def _mla_prefill(cfg, p, x, positions, max_len):
    m = cfg.mla
    dt_s = dtype_of(cfg.dtype)
    B, T, _ = x.shape
    out = attn.mla_train(cfg, p, x, positions)
    kv_a = x @ p["wkv_a"].astype(x.dtype)
    c_kv, k_pe = kv_a[..., :m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    pos = positions[..., 0] if positions.ndim == 3 else positions
    k_pe = attn.apply_rope(k_pe[:, :, None, :], pos, cfg.rope_theta)[:, :, 0]
    cc = jnp.zeros((B, max_len, m.kv_lora_rank), dt_s)
    cc = jax.lax.dynamic_update_slice(cc, c_kv.astype(dt_s), (0, 0, 0))
    cp = jnp.zeros((B, max_len, m.qk_rope_head_dim), dt_s)
    cp = jax.lax.dynamic_update_slice(cp, k_pe.astype(dt_s), (0, 0, 0))
    return out, {"c_kv": cc, "k_pe": cp}


def _rglru_hidden(cfg, p, x):
    dt = x.dtype
    u = x @ p["w_in"].astype(dt)
    u = rec._causal_conv(u, p["conv"].astype(dt))
    r_gate = jax.nn.sigmoid((u @ p["w_a"].astype(dt)).astype(jnp.float32))
    i_gate = jax.nn.sigmoid((u @ p["w_x"].astype(dt)).astype(jnp.float32))
    log_a = -rec._LRU_C * jax.nn.softplus(
        p["lambda"].astype(jnp.float32)) * r_gate
    a2 = jnp.exp(2.0 * log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-9)) * (i_gate * u.astype(jnp.float32))
    return rec._lru_scan_assoc(log_a, b)


def _mlstm_prefill(cfg, p, x):
    B, T, d = x.shape
    H = cfg.n_heads
    hd = (cfg.lru_dim or d) // H
    q, k, v, logi, logf = rec._mlstm_qkvif(cfg, p, x)
    init = (jnp.zeros((B, H, hd, hd), jnp.float32),
            jnp.zeros((B, H, hd), jnp.float32),
            jnp.full((B, H), -1e30, jnp.float32))
    xs = tuple(a.swapaxes(0, 1) for a in (q, k, v, logi, logf))
    carry, hs = jax.lax.scan(rec._mlstm_step, init, xs)
    h = hs.swapaxes(0, 1).reshape(B, T, H * hd).astype(x.dtype)
    z = jax.nn.silu(x @ p["wz"].astype(x.dtype))
    out = (h * z) @ p["wo"].astype(x.dtype)
    return out, {"C": carry[0], "n": carry[1], "m": carry[2]}


def _slstm_prefill(cfg, p, x):
    B, T, d = x.shape
    H = cfg.n_heads
    hd = (cfg.lru_dim or d) // H
    xz, xi, xf, xo = rec._slstm_inputs(cfg, p, x)
    p32 = {k: p[k].astype(jnp.float32) for k in ("rz", "ri", "rf", "ro")}
    z = jnp.zeros((B, H, hd), jnp.float32)
    init = (z, z, jnp.full((B, H, hd), -1e30, jnp.float32), z)
    xs = tuple(a.swapaxes(0, 1) for a in (xz, xi, xf, xo))
    carry, hs = jax.lax.scan(lambda c, i: rec._slstm_step(p32, c, i), init, xs)
    h = hs.swapaxes(0, 1).reshape(B, T, H * hd).astype(x.dtype)
    out = h @ p["wo"].astype(x.dtype)
    return out, {"c": carry[0], "n": carry[1], "m": carry[2], "h": carry[3]}


def decode_step(cfg, params, cache, tokens, pos):
    """One decode step.  tokens: (B, 1) i32; pos: scalar i32 position.
    Returns (logits (B, V), new cache)."""
    x = _embed_tokens(cfg, params, tokens)
    segs = plan_segments(layer_kinds(cfg))
    new_caches = {}
    for si, (unit, count) in enumerate(segs):
        stacked = params["segments"][f"seg{si}"]
        seg_cache = cache[f"seg{si}"]

        def body(h, inp, unit=unit):
            p_l, c_l = inp
            new_c = {}
            for ui, kind in enumerate(unit):
                h, nc = apply_block_decode(cfg, kind, p_l[f"u{ui}"], h,
                                           c_l[f"u{ui}"], pos)
                new_c[f"u{ui}"] = nc
            return h, new_c

        x, new_caches[f"seg{si}"] = jax.lax.scan(body, x, (stacked, seg_cache))
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_head_logits(cfg, params, x)
    return logits[:, 0], new_caches


def count_params(params) -> int:
    return sum(int(np.prod(l.shape))
               for l in jax.tree_util.tree_leaves(params))
