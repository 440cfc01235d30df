"""Which attention path a call takes, what it counts, and the scrutiny of a
train step that runs through the flash kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ArchConfig
from repro.core import ScrutinyConfig, participation, scrutinize
from repro.models import attention as A
from repro.models import init_params, loss_fn, set_attn_impl
from repro.train.optim import OptConfig, init_opt
from repro.train.step import make_train_step


def _qkv(Tq, Tk, D=64, Dv=None, H=6, K=2):
    q = jnp.zeros((1, Tq, H, D), jnp.bfloat16)
    k = jnp.zeros((1, Tk, K, D), jnp.bfloat16)
    v = jnp.zeros((1, Tk, K, Dv or D), jnp.bfloat16)
    return q, k, v


SHAPES = [(16, 16, 64, None), (512, 512, 64, None), (2048, 2048, 64, None),
          (4096, 4096, 64, None), (2048, 4096, 64, None), (1, 4096, 64, None),
          (2048, 2048, 192, 128), (2048, 2048, 80, None)]


@pytest.mark.parametrize("Tq,Tk,D,Dv", SHAPES)
def test_cpu_auto_dispatch_unchanged(Tq, Tk, D, Dv):
    """Off the TPU, ``auto`` keeps the einsum path up to the threshold and
    the chunked one past it, whatever the kernel would serve."""
    assert jax.default_backend() == "cpu"
    q, k, v = _qkv(Tq, Tk, D, Dv)
    want = "full" if Tq * Tk <= A._FULL_THRESHOLD ** 2 else "chunked"
    assert A._attend_impl(q, k, v, mrope=False) == want


@pytest.mark.parametrize("Tq,Tk,D,Dv,mrope,want", [
    (2048, 2048, 64, None, False, "flash"),      # SmolLM2's train step
    (4096, 4096, 64, None, False, "flash"),
    (512, 512, 64, None, False, "flash"),        # one block
    (2048, 2048, 192, 128, False, "flash"),      # MLA
    (256, 256, 64, None, False, "full"),         # shorter than a block
    (2048, 4096, 64, None, False, "chunked"),    # not self-attention shaped
    (1, 4096, 64, None, False, "full"),
    (2048, 2048, 80, None, False, "full"),       # head dim off the MXU grid
    (2048, 2048, 64, None, True, "full"),        # M-RoPE positions
    (8192, 8192, 80, None, False, "chunked"),
])
def test_tpu_auto_takes_flash_where_it_serves(monkeypatch, Tq, Tk, D, Dv,
                                              mrope, want):
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A.jax, "device_count", lambda: 1)
    q, k, v = _qkv(Tq, Tk, D, Dv)
    assert A._attend_impl(q, k, v, mrope=mrope) == want


def test_tpu_auto_keeps_einsum_across_chips(monkeypatch):
    """The kernel cannot be partitioned: on four chips ``auto`` keeps the
    einsum path even where the kernel would serve."""
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A.jax, "device_count", lambda: 4)
    q, k, v = _qkv(2048, 2048)
    assert A._attend_impl(q, k, v, mrope=False) == "full"


@pytest.mark.parametrize("impl,want", [("auto", "full"), ("chunked", "chunked"),
                                       ("pallas", "flash")])
def test_dispatch_counts_its_choice(impl, want):
    """Each traced call counts the path it took as
    ``attention.impl.<flash|full|chunked>``."""
    q, k, v = _qkv(128, 128)
    pos = jnp.broadcast_to(jnp.arange(128), (1, 128))
    obs.reset()
    obs.enable()
    set_attn_impl(impl)
    try:
        jax.make_jaxpr(lambda q, k, v: A._dispatch_attend(
            q, k, v, pos, pos, None, True, 0.125, None))(q, k, v)
        counters = obs.get_obs().registry.to_dict()["counters"]
    finally:
        set_attn_impl("auto")
        obs.disable()
        obs.reset()
    assert counters == {f"attention.impl.{want}": 1}


def _llama_tiny():
    # float32 compute: the AD masks are exact zeros of gradients, which
    # bfloat16 rounding of the two paths' different sums can flip
    return ArchConfig(name="llama-tiny", family="dense", n_layers=2,
                      d_model=64, n_heads=6, n_kv_heads=2, d_ff=128,
                      vocab=128, head_dim=64, layer_pattern="g",
                      ffn="swiglu", norm="rmsnorm", tie_embeddings=False,
                      dtype="float32", param_dtype="float32", remat=True,
                      scan_layers=True)


def _masks(rep):
    return {name: np.asarray(rep[name].mask) for name in rep.leaves}


def test_scrutiny_through_flash_kernel():
    """``participation`` (taint) and ``scrutinize`` (AD, reverse over the
    step's own reverse) give the same masks when the train step runs
    through the flash kernels (interpret mode) as on the einsum path.  The
    rest of the program is the loss after one AdamW step, so the embedding
    rows the batch never names are uncritical."""
    cfg = _llama_tiny()
    oc = OptConfig(kind="adamw", lr=1e-3, warmup=1)
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = {"params": params, "opt": init_opt(oc, params)}
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 129), 0, 64)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    masks = {}
    for impl in ("full", "pallas"):
        set_attn_impl(impl)
        try:
            step = make_train_step(cfg, oc)

            def resume(s):
                p, _, _ = step(s["params"], s["opt"], batch)
                return {"loss": loss_fn(cfg, p, batch)}

            masks[impl] = (_masks(participation(resume, state)),
                           _masks(scrutinize(resume, state,
                                             config=ScrutinyConfig(probes=2))))
        finally:
            set_attn_impl("auto")
    for got, want in zip(masks["pallas"], masks["full"]):
        assert got.keys() == want.keys()
        for name in want:
            np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    embed = masks["full"][0]["params/embed"]
    assert 0 < embed.mean() < 1, "no embedding row was uncritical"
