"""Pallas TPU kernels for the system's compute hot-spots.

Each kernel package ships kernel.py (pl.pallas_call + BlockSpec VMEM
tiling), ops.py (jit'd public entry with interpret-mode fallback off-TPU),
and ref.py (pure-jnp oracle used by the shape/dtype sweep tests).

- mask_pack/        checkpoint compaction/restore: per-tile 0/1 permutation
                    matmul on the MXU (TPUs have no scatter unit) — the
                    paper's pack/unpack hot path at pod scale
- flash_attention/  online-softmax attention (GQA + sliding window +
                    logit softcap, masks from positions) with its own
                    backward kernels; the TPU train and prefill path
- lru_scan/         blocked diagonal linear recurrence (RG-LRU hot path)
"""
