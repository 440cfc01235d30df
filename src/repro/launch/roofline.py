"""Three-term roofline from a compiled dry-run artifact.

    compute    = HLO_FLOPs / (chips × 197 TFLOP/s)
    memory     = HLO_bytes / (chips × 819 GB/s)
    collective = collective_bytes / (chips × 50 GB/s/link)

with the published peaks of the production mesh's chip (TPU v5e,
``launch.mesh.PEAKS``).

FLOPs/bytes come from ``compiled.cost_analysis()``; collective bytes are
parsed from the HLO text (all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute operand sizes).  MODEL_FLOPS = 6·N·D
(6·N_active·D for MoE) bounds how much of the compiled compute is useful.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

import numpy as np

from repro.launch.mesh import PRODUCTION_KIND, peaks

_PEAK = peaks(PRODUCTION_KIND)
PEAK_FLOPS, HBM_BW, ICI_BW = _PEAK["flops"], _PEAK["hbm_bw"], _PEAK["ici_bw"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16|c64|c128)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*\(?((?:pred|[suf]\d+|bf16|c64|c128)\[[\d,]*\][^)]*?|\([^)]*\))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes of every collective op in the HLO."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.search(
            r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(-start|-done)?\b", s)
        if not m or "=" not in s:
            continue
        if m.group(2) == "-done":
            continue  # counted at -start
        lhs = s.split("=", 1)[0]
        b = _shape_bytes(lhs)
        kind = m.group(1)
        out[kind] = out.get(kind, 0) + b
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: Dict[str, int]
    model_flops: float
    bytes_per_device: Optional[float] = None

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return sum(self.coll_bytes.values()) / (self.chips * ICI_BW)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_fraction(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline this step achieves, assuming the
        dominant term sets wall-clock: t_model_compute / max(all terms)."""
        t_model = self.model_flops / (self.chips * PEAK_FLOPS)
        t_bound = max(self.t_compute, self.t_memory, self.t_collective)
        return t_model / t_bound if t_bound else 0.0

    def row(self) -> str:
        cb = sum(self.coll_bytes.values())
        return (f"| {self.arch} | {self.shape} | {self.mesh} "
                f"| {self.t_compute*1e3:.2f} | {self.t_memory*1e3:.2f} "
                f"| {self.t_collective*1e3:.2f} | {self.dominant} "
                f"| {self.useful_fraction*100:.0f}% "
                f"| {self.roofline_fraction*100:.1f}% |")


def model_flops(cfg, cell) -> float:
    """6·N_active·D (+ attention QKᵀ/PV term) per step.

    train: fwd+bwd (3× fwd); prefill: fwd; decode: one token per sequence.
    The attention term uses the causal-effective context (T/2, or the
    window for local layers) — without it, small-d archs at long T report
    misleadingly low useful fractions."""
    n_active = _active_params(cfg)
    B, T = cell.global_batch, cell.seq_len
    hd = cfg.resolved_head_dim
    attn_fwd = 0.0
    for l in range(cfg.n_layers):
        fl = cfg.pattern_at(l)
        if fl == "g":
            ctx = T / 2
        elif fl == "l":
            ctx = min(cfg.window or T, T)
        else:
            continue
        # QKᵀ + PV: 2 matmuls × 2 flops/MAC over (T × ctx × H × hd)
        attn_fwd += 4.0 * B * T * ctx * cfg.n_heads * hd
    if cfg.enc_dec:
        attn_fwd += 4.0 * B * T * cfg.encoder_len * cfg.n_heads * hd

    if cell.kind == "train":
        return (6.0 * n_active * B * T) + 3.0 * attn_fwd
    if cell.kind == "prefill":
        return (2.0 * n_active * B * T) + attn_fwd
    # decode: one new token attends to the whole context
    dec_attn = 0.0
    for l in range(cfg.n_layers):
        fl = cfg.pattern_at(l)
        if fl == "g":
            dec_attn += 4.0 * B * T * cfg.n_heads * hd
        elif fl == "l":
            dec_attn += 4.0 * B * min(cfg.window or T, T) * cfg.n_heads * hd
    return 2.0 * n_active * B + dec_attn


def _active_params(cfg) -> float:
    d, L, V = cfg.d_model, cfg.n_layers, cfg.vocab
    hd = cfg.resolved_head_dim
    total = V * d * (1 if cfg.tie_embeddings else 2)
    for l in range(L):
        fl = cfg.pattern_at(l)
        if fl in ("g", "l"):
            if cfg.mla is not None:
                m = cfg.mla
                qk = m.qk_nope_head_dim + m.qk_rope_head_dim
                total += (d * m.q_lora_rank + m.q_lora_rank * cfg.n_heads * qk
                          + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                          + m.kv_lora_rank * cfg.n_heads *
                          (m.qk_nope_head_dim + m.v_head_dim)
                          + cfg.n_heads * m.v_head_dim * d)
            else:
                total += d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) \
                    + cfg.n_heads * hd * d
        else:
            r = cfg.lru_dim or d
            total += 4 * d * r  # in/gate/out + gates (approx.)
        if cfg.moe_at(l):
            m = cfg.moe
            total += 3 * (m.top_k + m.num_shared) * d * m.d_expert \
                + d * m.num_experts
        elif cfg.d_ff:
            mult = 3 if cfg.ffn in ("swiglu", "geglu") else 2
            total += mult * d * cfg.d_ff
    if cfg.enc_dec:
        total += cfg.n_encoder_layers * (4 * d * hd * cfg.n_heads // max(
            1, cfg.n_heads) * cfg.n_heads // max(1, cfg.n_heads)
            + 2 * d * cfg.d_ff)
        total += cfg.n_layers * 2 * d * hd * (cfg.n_heads + cfg.n_kv_heads)
    return float(total)


TABLE_HEADER = (
    "| arch | shape | mesh | t_comp ms | t_mem ms | t_coll ms "
    "| dominant | useful | roofline |\n"
    "|---|---|---|---|---|---|---|---|---|")
