"""Plain reference of a Llama-architecture language model and its AdamW
training step (the configuration of ``smollm2_135m.json``).

Straight ``jax.numpy`` in float32 at the highest matmul precision, with no
kernels, no cache and nothing imported from the program under test.  It
reads the weights by their names in the tree the benchmark made.  The
equations are those of the configuration as it is run:

- pre-norm residual blocks: RMSNorm ``y = x / sqrt(mean(x^2) + eps) *
  (1 + scale)``, then causal self-attention, then RMSNorm and a SwiGLU
  feed-forward ``(silu(x Wg) * (x Wi)) Wo``; a final RMSNorm and a head
  tied to the embedding;
- attention: grouped queries (each key/value head serves
  ``n_heads / n_kv_heads`` query heads, query head ``h`` reading key head
  ``h // group``), rotary positions on queries and keys with the two
  halves of each head rotated together (``rope_interleaved`` false),
  scores scaled by ``1 / sqrt(head_dim)``;
- next-token cross-entropy on ``labels = roll(tokens, -1)``, the mean over
  every position;
- global-norm clipping, then AdamW with bias correction, decoupled weight
  decay and the learning rate ``lr * min(1, (t + 1) / warmup) *
  (0.1 + 0.9 * (1 + cos(pi * clip((t - warmup) / (decay - warmup), 0,
  1))) / 2)`` at step ``t`` (from 1).

The loss and its gradient are summed over a few rows at a time, so that
the float32 scores and logits of the whole batch are never live at once;
the mean is taken once over all the rows.

``quant`` rounds, in the forward pass, every value that the configuration
keeps in its compute dtype (embeddings, norm outputs, every projection's
operands and output, rotated queries and keys, the attention output, the
residual stream, the gated product, the logits) to a lower precision:
with ``float8_e4m3fn`` where the configuration computes in bfloat16, it is
the control that the check must refuse.  The rounding keeps float32's
exponent range and takes e4m3's 3 mantissa bits.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6                 # the configuration's rms_norm_eps as run
ROWS_PER_PASS = 4

#: mantissa bits of each lower precision the control may round to
MANTISSA = {"float8_e4m3fn": 3, "bfloat16": 7}


def _round_mantissa(a, bits: int):
    """Round float32 ``a`` to ``bits`` mantissa bits, to nearest even, on
    the raw words: a convert round trip (f32 -> f8 -> f32) may be folded
    away by the compiler, an integer operation is not."""
    drop = 23 - bits
    w = jax.lax.bitcast_convert_type(a, jnp.uint32)
    half = jnp.uint32((1 << (drop - 1)) - 1)
    odd = (w >> drop) & jnp.uint32(1)
    w = (w + half + odd) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(w, jnp.float32)


def _q(quant: Optional[str]) -> Callable:
    """Round a value to the mantissa of ``quant`` in the forward pass (the
    exponent range stays float32's); the gradient passes straight through
    (the backward pass is not rounded)."""
    if quant is None:
        return lambda a: a.astype(jnp.float32)
    bits = MANTISSA[quant]

    def q(a):
        a = a.astype(jnp.float32)
        return a + jax.lax.stop_gradient(_round_mantissa(a, bits) - a)

    return q


def rmsnorm(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * (1.0 + scale.astype(jnp.float32))


def _mm(q, a, b):
    return q(jnp.matmul(q(a), q(b), precision=HIGHEST))


def rope(x, theta: float):
    """x: (B, T, heads, D); positions 0..T-1."""
    T, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs      # (T, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, model, q):
    B, T, _ = x.shape
    H, K, D = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    theta = model["rope_theta"]
    qh = q(rope(_mm(q, x, p["wq"]).reshape(B, T, H, D), theta))
    kh = q(rope(_mm(q, x, p["wk"]).reshape(B, T, K, D), theta))
    vh = _mm(q, x, p["wv"]).reshape(B, T, K, D)
    group = H // K
    kh = jnp.repeat(kh, group, axis=2)          # query head h reads h // group
    vh = jnp.repeat(vh, group, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", qh, kh, precision=HIGHEST) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), vh,
                   precision=HIGHEST)
    return _mm(q, q(o.reshape(B, T, H * D)), p["wo"])


def ffn(p, x, q):
    gated = q(q(jax.nn.silu(_mm(q, x, p["wg"]))) * _mm(q, x, p["wi"]))
    return _mm(q, gated, p["wo"])


def block(x, blk, model, q):
    h = q(rmsnorm(x, blk["norm1"]["scale"]))
    x = q(x + attention(blk["mixer"], h, model, q))
    h = q(rmsnorm(x, blk["norm2"]["scale"]))
    return q(x + ffn(blk["ffn"], h, q))


def stacks(w) -> List[Dict[str, Any]]:
    """The stacked block weights in depth order: segment by segment, each
    a stack of repeats of its units ``u0, u1, ...``; one stack per unit
    when a segment has one unit."""
    out = []
    for si in range(len(w["segments"])):
        seg = w["segments"][f"seg{si}"]
        if len(seg) != 1:
            raise ValueError("expected one block kind per segment")
        out.append(seg["u0"])
    return out


def loss_sum(w, tokens, model: Dict[str, Any], quant: Optional[str] = None):
    """Summed next-token cross-entropy of ``tokens`` (rows, T)."""
    q = _q(quant)
    x = q(w["embed"][tokens])

    def body(x, blk):
        return jax.checkpoint(lambda x, b: block(x, b, model, q))(x, blk), None

    for stack in stacks(w):
        x, _ = jax.lax.scan(body, x, stack)
    x = q(rmsnorm(x, w["final_norm"]["scale"]))
    logits = _mm(q, x, w["embed"].T)
    labels = jnp.roll(tokens, -1, axis=1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


def loss_and_grad(params, tokens, model, quant):
    """Mean loss over every position of the batch and its gradient, summed
    ``ROWS_PER_PASS`` rows at a time."""
    B, T = tokens.shape
    c = math.gcd(B, ROWS_PER_PASS)
    chunks = tokens.reshape(B // c, c, T)
    vg = jax.value_and_grad(lambda p, t: loss_sum(p, t, model, quant))

    def one(carry, tok):
        tot, g = carry
        v, gi = vg(params, tok)
        return (tot + v, jax.tree_util.tree_map(jnp.add, g, gi)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    (tot, g), _ = jax.lax.scan(one, zero, chunks)
    n = jnp.float32(B * T)
    return tot / n, jax.tree_util.tree_map(lambda a: a / n, g)


def lr_at(opt: Dict[str, Any], t):
    t = jnp.asarray(t, jnp.float32)
    warm = jnp.minimum(1.0, (t + 1) / max(1, opt["warmup"]))
    prog = jnp.clip((t - opt["warmup"])
                    / max(1, opt["decay_steps"] - opt["warmup"]), 0, 1)
    return opt["lr"] * warm * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(math.pi * prog)))


def make_step(model: Dict[str, Any], opt: Dict[str, Any],
              quant: Optional[str] = None):
    """(params, mu, nu, t, tokens) -> (params, mu, nu, loss, clipped grads)"""

    def step(params, mu, nu, t, tokens):
        val, g = loss_and_grad(params, tokens, model, quant)
        leaves = jax.tree_util.tree_leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in leaves))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
        g = jax.tree_util.tree_map(lambda a: a * scale, g)
        b1, b2 = opt["b1"], opt["b2"]
        tf = jnp.asarray(t, jnp.float32)
        bc1, bc2 = 1 - b1 ** tf, 1 - b2 ** tf
        lr = lr_at(opt, t)
        mu = jax.tree_util.tree_map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
        nu = jax.tree_util.tree_map(lambda v, a: b2 * v + (1 - b2) * a * a,
                                    nu, g)
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2)
                                                   + opt["eps"])
                                      + opt["weight_decay"] * p),
            params, mu, nu)
        return params, mu, nu, val, g

    return jax.jit(step)


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))) for a in jax.tree_util.tree_leaves(t)])(tree)
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, norms)}


def train(weights, batches, step) -> Dict[str, Any]:
    """Run ``step`` (from :func:`make_step`) over ``batches`` from
    ``weights``; returns each step's loss, the clipped gradient's leaf
    norms at step 1 and the leaf norms of the parameters' change over all
    the steps."""
    p0 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), weights)
    params = p0
    mu = jax.tree_util.tree_map(jnp.zeros_like, p0)
    nu = jax.tree_util.tree_map(jnp.zeros_like, p0)
    losses, grad_norms = [], None
    for t, tokens in enumerate(batches, start=1):
        params, mu, nu, val, g = step(params, mu, nu, t, tokens)
        losses.append(float(val))
        if t == 1:
            grad_norms = leaf_norms(g)
        del g
    change = jax.tree_util.tree_map(lambda a, b: a - b, params, p0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": leaf_norms(change)}
