"""The check must refuse a broken timed path: a run on the CPU at a tiny
size with a fault planted under the training cell comes out not correct,
and the control (the plain reference rounded to float8's mantissa, put in
the program's place) fails the limits where the program passes them, at
the tiny cut's own limits (conftest.TINY_TRAIN).

The tiny cut's limits come from CPU readings of it over 8 seeds: the
program reads at most 1.5e-4 (loss), 0.0084 (gradient) and 0.0030
(change); the control at least 4.9e-4, 0.026 and 0.0069; the half-batch
fault at least 3.4e-4, 0.11 and 0.026."""

import json
import os

import pytest

CELL = "smollm2_135m.train_ckpt"


def unchanged_state(make):
    """A step that returns its state unchanged (the loss still computed)."""
    def wrapped(cfg, oc, **kw):
        real = make(cfg, oc, **kw)

        def step(params, opt_state, batch):
            _, _, metrics = real(params, opt_state, batch)
            return params, opt_state, metrics
        return step
    return wrapped


def half_batch(make):
    """Half of the batch left out, the mean taken over the rest."""
    def wrapped(cfg, oc, **kw):
        real = make(cfg, oc, **kw)

        def step(params, opt_state, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return real(params, opt_state, half)
        return step
    return wrapped


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def test_step_fault_is_refused(tiny_run, monkeypatch, fault):
    import run as runmod
    from repro.train import step as step_mod

    monkeypatch.setattr(step_mod, "make_train_step",
                        fault(step_mod.make_train_step))
    line = json.loads(runmod.execute(tiny_run(CELL)))
    assert line["correct"] is False, line["checks"]


def _alter_first_leaf(got):
    import jax

    if got is None:
        return got
    leaves, tdef = jax.tree_util.tree_flatten(got[1])
    leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(1)
    return got[0], jax.tree_util.tree_unflatten(tdef, leaves)


def test_restored_value_altered_is_refused(tiny_run, monkeypatch):
    """One element of the restored checkpoint altered where the program's
    restore (the newest step over both levels) produces it."""
    import run as runmod
    from repro.checkpoint import coordinator

    real = coordinator.CoordinatedCheckpointManager.restore

    def restore(self, *a, **kw):
        return _alter_first_leaf(real(self, *a, **kw))

    monkeypatch.setattr(coordinator.CoordinatedCheckpointManager, "restore",
                        restore)
    line = json.loads(runmod.execute(tiny_run(CELL)))
    assert line["checks"]["restore_leaves_differing"]["value"] >= 1
    assert line["checks"]["disk_restore_leaves_differing"]["value"] == 0
    assert line["correct"] is False


def _disk_only(mgr) -> bool:
    return all(os.path.basename(lv.directory) == "disk" for lv in mgr.levels)


def test_disk_read_altered_is_refused(tiny_run, monkeypatch):
    """One element altered where a restore from the disk level alone
    produces it: the newest restore, read from the ram level, is sound."""
    import run as runmod
    from repro.checkpoint import manager

    real = manager.CheckpointManager.restore

    def restore(self, *a, **kw):
        got = real(self, *a, **kw)
        return _alter_first_leaf(got) if _disk_only(self) else got

    monkeypatch.setattr(manager.CheckpointManager, "restore", restore)
    line = json.loads(runmod.execute(tiny_run(CELL)))
    assert line["checks"]["restore_leaves_differing"]["value"] == 0
    assert line["checks"]["disk_restore_leaves_differing"]["value"] >= 1
    assert line["correct"] is False


def test_torn_disk_step_is_refused(tiny_run, monkeypatch):
    """Every file of the newest disk-level step, shards and parity alike,
    cut to nothing before it is read back."""
    import run as runmod
    from repro.checkpoint import manager, store

    real = manager.CheckpointManager.restore
    torn = []

    def restore(self, *a, **kw):
        if _disk_only(self):
            root = self.levels[0].directory
            newest = store.committed_steps(root)[-1]
            for dirpath, _, files in os.walk(os.path.join(
                    root, f"step_{newest}")):
                for f in files:
                    open(os.path.join(dirpath, f), "w").close()
                    torn.append(f)
        return real(self, *a, **kw)

    monkeypatch.setattr(manager.CheckpointManager, "restore", restore)
    line = json.loads(runmod.execute(tiny_run(CELL)))
    assert torn
    assert line["checks"]["restore_leaves_differing"]["value"] == 0
    assert line["checks"]["disk_restore_leaves_differing"]["value"] >= 1
    assert line["correct"] is False


def test_control_fails_where_program_passes(tiny_run):
    import calibrate
    import harness

    r = tiny_run(CELL)
    drv = harness.load_module("loops", r.workload["loop"] + ".py")
    job = drv.Job(r.config, r.workload, r.seed)
    got = calibrate.readings(job, r.workload, r.seed, control=True)
    lim = r.workload["limits"]

    def checks(g):
        return {k: {"value": g[k], "limit": lim[k]} for k in lim}

    assert harness.judge(checks(got["program"])), got["program"]
    assert not harness.judge(checks(got["control"])), got["control"]
