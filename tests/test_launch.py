"""Launch-layer tests: loop-aware HLO accounting, sharding-rule fitting,
input specs, roofline arithmetic.  (The 512-device dry-run itself runs via
`python -m repro.launch.dryrun`; it cannot run under pytest because jax is
already initialized with 1 device.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import all_arch_names, get_config
from repro.launch.hlo_analysis import analyze
from repro.launch.roofline import Roofline, model_flops
from repro.launch.specs import SHAPES, batch_specs, input_specs


def test_hlo_flops_exact_through_scan():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, None, length=10)[0]

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    res = analyze(jax.jit(f).lower(x, w).compile().as_text())
    assert res["flops"] == 2.0 * 128 * 256 * 256 * 10
    assert res["n_whiles"] == 1


def test_hlo_flops_nested_scan():
    def g(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            return jax.lax.scan(inner, c, None, length=5)[0], None
        return jax.lax.scan(outer, x, None, length=3)[0]

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    res = analyze(jax.jit(g).lower(x, w).compile().as_text())
    assert res["flops"] == 2.0 * 64 * 128 * 128 * 15


def test_hbm_scan_slicing_not_multiplied():
    # reading one slice per iteration must not charge the full stack × trip
    def f(xs):
        def body(c, x):
            return c + x.sum(), None
        return jax.lax.scan(body, 0.0, xs)[0]

    xs = jax.ShapeDtypeStruct((1024, 128), jnp.float32)
    res = analyze(jax.jit(f).lower(xs).compile().as_text())
    full = 1024 * 128 * 4
    assert res["hbm_bytes"] < 20 * full, (
        f"scan slicing overcounted: {res['hbm_bytes']} vs stack {full}")


def test_fit_spec_drops_nondivisible():
    from repro.distributed.sharding import fit_spec

    devs = np.array(jax.devices()[:1] * 1).reshape(1, 1)
    mesh = Mesh(devs, ("data", "model"))
    # 1-sized mesh axes always divide
    assert fit_spec(mesh, P("data", "model"), (4, 4)) == P("data", "model")

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    assert fit_spec(FakeMesh(), P("data", "model"), (4, 64)) == P(None, "model")
    assert fit_spec(FakeMesh(), P(("data", "model"), None), (64, 3)) == \
        P("data", None)
    assert fit_spec(FakeMesh(), P(("data", "model"), None), (256, 3)) == \
        P(("data", "model"), None)


@pytest.mark.parametrize("arch", all_arch_names())
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_consistent(arch, shape):
    cfg = get_config(arch)
    if shape in cfg.skip_shapes:
        pytest.skip("assigned skip")
    cell = SHAPES[shape]
    specs = batch_specs(cfg, cell)
    assert specs["tokens"].shape[0] == cell.global_batch
    if cell.kind != "decode":
        assert specs["tokens"].shape[1] == cell.seq_len
    if cfg.family == "vlm" and cell.kind != "decode":
        assert specs["positions"].shape[-1] == 3
    mf = model_flops(cfg, cell)
    assert mf > 0


def test_roofline_terms():
    rl = Roofline(arch="a", shape="s", mesh="m", chips=256,
                  hlo_flops=256 * 197e12 * 0.01,        # 10 ms compute
                  hlo_bytes=256 * 819e9 * 0.02,         # 20 ms memory
                  coll_bytes={"all-reduce": int(256 * 50e9 * 0.005)},
                  model_flops=256 * 197e12 * 0.008)
    assert abs(rl.t_compute - 0.01) < 1e-9
    assert abs(rl.t_memory - 0.02) < 1e-9
    assert abs(rl.t_collective - 0.005) < 1e-9
    assert rl.dominant == "memory"
    assert abs(rl.roofline_fraction - 0.4) < 1e-9


def test_peaks_keyed_by_device_kind():
    from repro.launch.mesh import PEAKS, PRODUCTION_KIND, peaks

    v5e = peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    assert PRODUCTION_KIND in PEAKS
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


def test_compile_cache_dir(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.setenv(compile_cache.ENV, "/some/cache")
    assert compile_cache.cache_dir() == "/some/cache"
    monkeypatch.delenv(compile_cache.ENV)
    d = compile_cache.cache_dir()
    assert d == compile_cache.REPO_CACHE_DIR
    assert d.endswith(".jax_cache")
    # inside the checkout, next to src/
    import os
    assert os.path.isdir(os.path.join(os.path.dirname(d), "src", "repro"))
