"""Operations of each model step, from shapes alone.

Model FLOPs count the work the algorithm needs, once: a multiply-add is
two operations, recomputation (remat) does not count, causal attention
counts only the keys at or before each query, and a training step is the
forward pass plus a backward pass of twice its work.
"""

from __future__ import annotations

from typing import Any, Dict


def _attention_layer_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward FLOPs of one grouped-query attention layer with a SwiGLU
    feed-forward, per token, averaged over the positions of a sequence."""
    d, ff = m["d_model"], m["d_ff"]
    hd = m.get("head_dim") or d // m["n_heads"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    proj = 2 * (d * q + 2 * d * kv + q * d)
    # scores and the weighted sum, each 2 * hd per key and head; a query
    # at position t sees t + 1 keys, (seq + 1) / 2 on average
    attend = 2 * 2 * m["n_heads"] * hd * (seq + 1) / 2
    mlp = 2 * 3 * d * ff
    return proj + attend + mlp


def train_step_flops(m: Dict[str, Any], batch: int, seq: int) -> float:
    """Model FLOPs of one train step (forward + backward) of a model whose
    layers are all global attention with a dense feed-forward."""
    if set(m.get("layer_pattern", "g")) != {"g"} or m.get("moe"):
        raise ValueError("no FLOP count for layer pattern "
                         f"{m.get('layer_pattern')!r}")
    layers = m["n_layers"] * _attention_layer_flops_per_token(m, seq)
    head = 2 * m["d_model"] * m["vocab"]
    return 3.0 * batch * seq * (layers + head)
