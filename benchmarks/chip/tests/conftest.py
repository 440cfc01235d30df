"""Shared set-up of the chip benchmark's CPU tests: the benchmark's own
modules on the path, and a tiny cut of a cell that a test run can hold."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

#: the training cell cut to CPU size: the same kinds of layer, tiny widths.
#: The cell's limits are set from chip readings at its own size; this cut
#: has its own, from CPU readings of it (see test_faults.py).
TINY_TRAIN = {"model": dict(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                            head_dim=8, d_ff=64, vocab=128),
              "workload": dict(batch=2, seq=16, save_every=4,
                               ahead_steps=2, trace_steps=3,
                               limits={"loss_gap": 3e-4,
                                       "grad_gap": 0.016,
                                       "change_gap": 0.005})}


@pytest.fixture
def tiny_run(tmp_path):
    """``tiny_run(cell, trace)``: a ``harness.Run`` of that cell on the CPU
    at test size (no look for a chip), its files under ``tmp_path``."""
    import jax

    import harness

    # interpret-mode and CPU executables are not for the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def make(cell_name: str, trace: bool = False, seed: int = 3,
             seconds: float = 1.0):
        spec = harness.benchmark_spec()
        cell = next(c for c in spec["workloads"] if c["name"] == cell_name)
        run = harness.Run(cell, spec, seed, seconds, trace,
                          jax.devices()[:1], work=str(tmp_path))
        run.config["model"].update(TINY_TRAIN["model"])
        run.workload.update(TINY_TRAIN["workload"])
        return run

    try:
        yield make
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
