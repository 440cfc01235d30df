"""Training with scrutinized, multi-level checkpoints: the calls that
``launch/train.py`` composes, driven for a timed window.

Set-up builds one state (the benchmark's weights from the seed, AdamW
state, the data pipeline's state with the benchmark's token rows), one
compiled train step and one ``CoordinatedCheckpointManager`` with
train.py's two levels, ``ram`` every ``save_every`` steps and ``disk`` (2
shards with parity) every second save, and ``participation`` scrutiny.
It drives that state through its first steps, reading what the check
compares, and makes the first save at step ``save_every`` (``ram`` only),
which runs the scrutiny; it waits for that save to land.

The window then runs the same loop: the data pipeline's next batch, the
step, a save every ``save_every`` steps.  Steps are dispatched ahead of
the chip, at most ``ahead_steps`` in flight, and each step's loss is
waited for that many steps later (``Feed``), so a host that stands still
for a while leaves the chip busy; the queue is drained before each save,
so a save snapshots a ready state.  The window runs whole save intervals:
once the chip has finished ``--seconds`` of steps, it ends at the step
before a save falls due, and holds at least the save at step
``2 * save_every`` (both levels) with the steps after it that its writes
overlap.  So which saves a window holds does not move with small changes
of the step's speed.  Its clock stops after every step sent has finished.

The check compares the first three steps with the plain reference (each
loss, the first gradient as AdamW got it, the change of the parameters
over the three), and the window's newest checkpoint, restored twice, with
a fingerprint of the state taken when it was saved: once as the program
restores (the newest step over both levels) and once from the ``disk``
level alone.
"""

from __future__ import annotations

import collections
import os
import shutil
import time
from typing import Any, Dict

import harness


def _leaf_norms(tree, scale: float = 1.0) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))) for a in jax.tree_util.tree_leaves(t)])(tree)
    return {jax.tree_util.keystr(p): float(n) * scale
            for (p, _), n in zip(flat, norms)}


def _fingerprint_fn():
    """Per-leaf (wrapping sum, xor) of the raw 32-bit words, on device."""
    import jax
    import jax.numpy as jnp

    def words(a):
        a = jnp.ravel(a)
        if a.dtype.itemsize == 4:
            return jax.lax.bitcast_convert_type(a, jnp.uint32)
        return a.astype(jnp.uint32)

    @jax.jit
    def fp(tree):
        return [jnp.stack([jnp.sum(words(a), dtype=jnp.uint32),
                           jax.lax.reduce(words(a), jnp.uint32(0),
                                          jax.lax.bitwise_xor, (0,))])
                for a in jax.tree_util.tree_leaves(tree)]

    return fp


class Job:
    """The program's training job as launch/train.py composes it: config,
    optimizer, one compiled step, and the state made from the seed."""

    def __init__(self, config: Dict[str, Any], workload: Dict[str, Any],
                 seed: int):
        import jax

        from repro.configs.base import ArchConfig
        from repro.data import pipeline as data_pipeline
        from repro.models import init_params
        from repro.train.optim import OptConfig, init_opt
        from repro.train.step import make_train_step

        self.model, self.opt = config["model"], config["optimizer"]
        self.cfg = ArchConfig(**self.model)
        self.oc = OptConfig(**self.opt)
        self.batch, self.seq = workload["batch"], workload["seq"]
        self.pipeline = data_pipeline
        self.init_opt = init_opt
        self.shapes = jax.eval_shape(
            lambda: init_params(self.cfg, jax.random.PRNGKey(0)))
        self.step_fn = jax.jit(make_train_step(self.cfg, self.oc))
        self.ref_steps: Dict[Any, Any] = {}
        self.reset(seed)

    def reset(self, seed: int) -> None:
        """A fresh state from ``seed``: weights, AdamW state, data."""
        import jax.numpy as jnp

        self.seed = seed
        self.state = None
        params = self.weights()
        self.state = {
            "params": params, "opt": self.init_opt(self.oc, params),
            "data": {"key": harness.seed_key(seed, 2),
                     "step": jnp.zeros((), jnp.int32),
                     "buffer": self.rows(),
                     "cursor": jnp.zeros((), jnp.int32)},
            "step": jnp.zeros((), jnp.int32)}

    def rows(self):
        """The token rows the data pipeline starts with: one batch per
        prefetch slot, every row different, uniform over the vocabulary."""
        import jax
        import jax.numpy as jnp

        return jax.random.randint(
            harness.seed_key(self.seed, 1),
            (self.pipeline.PREFETCH, self.batch, self.seq), 0,
            self.cfg.vocab, jnp.int32)

    def weights(self):
        import weights as bench_weights

        return bench_weights.make(self.shapes, harness.seed_key(self.seed))

    def train_one(self, step: int):
        """One pass of train.py's loop body, dispatched; returns the step's
        loss on the device (not read)."""
        import jax.numpy as jnp

        s = self.state
        batch, s["data"] = self.pipeline.next_batch(self.cfg, s["data"])
        s["params"], s["opt"], metrics = self.step_fn(s["params"], s["opt"],
                                                      batch)
        s["step"] = jnp.asarray(step, jnp.int32)
        return metrics["loss"]

    def checked_steps(self, n: int) -> Dict[str, Any]:
        """Steps 1..n through the window's own call, reading each loss, the
        gradient AdamW got at step 1 (its first moment over 1 - b1) and the
        parameters' change over the n steps."""
        import jax
        import jax.numpy as jnp

        p0 = jax.tree_util.tree_map(jnp.copy, self.state["params"])
        losses = []
        for step in range(1, n + 1):
            losses.append(float(self.train_one(step)))
            if step == 1:
                grads = _leaf_norms(self.state["opt"]["mu"],
                                    1.0 / (1.0 - self.oc.b1))
        change = _leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, self.state["params"], p0))
        return {"losses": losses, "grad_norms": grads, "change_norms": change}


class Feed:
    """The job's steps dispatched ahead of the chip: at most ``ahead`` in
    flight, each step's loss waited for (not read) once ``ahead`` more
    have been sent.  ``done`` notes when each step was seen finished."""

    def __init__(self, job: Job, r, ahead: int):
        if ahead < 1:
            raise ValueError("ahead_steps must be at least 1")
        self.job, self.r, self.ahead = job, r, ahead
        self.pending: collections.deque = collections.deque()
        self.done: list = []            # (step, host time seen finished)

    def step(self, step: int) -> None:
        with self.r.span("train.step"):
            self.pending.append((step, self.job.train_one(step)))
        if len(self.pending) > self.ahead:
            with self.r.span("train.wait"):
                self._finish_oldest()

    def drain(self) -> None:
        """Wait until every step sent has finished."""
        with self.r.span("train.drain"):
            while self.pending:
                self._finish_oldest()

    def _finish_oldest(self) -> None:
        step, loss = self.pending.popleft()
        loss.block_until_ready()
        self.done.append((step, time.perf_counter()))


def reference(job: Job, workload: Dict[str, Any], n: int,
              quant=None, rows_of=None) -> Dict[str, Any]:
    """The plain reference's first ``n`` steps on the job's weights and
    rows (``rows_of`` may alter each batch, to plant a fault); its
    compiled step is kept on the job, one per precision."""
    ref_mod = harness.load_module("configs", workload["reference"])
    if quant not in job.ref_steps:
        job.ref_steps[quant] = ref_mod.make_step(job.model, job.opt, quant)
    w = job.weights()
    rows = job.rows()
    batches = [rows[i] if rows_of is None else rows_of(rows[i])
               for i in range(n)]
    del rows
    return ref_mod.train(w, batches, job.ref_steps[quant])


def gaps(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers compared: the worst step's loss, the worst leaf of the
    first gradient, the worst leaf of the parameters' change."""
    import compare

    grad, grad_leaf = compare.worst_leaf_gap(got["grad_norms"],
                                             want["grad_norms"])
    change, change_leaf = compare.worst_leaf_gap(
        got["change_norms"], want["change_norms"], want["grad_norms"])
    return {"loss_gap": max(compare.rel_gap(a, b) for a, b in
                            zip(got["losses"], want["losses"])),
            "grad_gap": grad, "change_gap": change,
            "grad_leaf": grad_leaf, "change_leaf": change_leaf}


def _leaves_differing(fingerprint, prints, got, want_step: int,
                      n_leaves: int) -> int:
    """Leaves of a restored ``(step, state)`` whose fingerprint differs
    from the state's when ``want_step`` was saved; every leaf when nothing
    or another step came back."""
    import numpy as np

    if got is None or got[0] != want_step:
        harness.log(f"restore gave step {None if got is None else got[0]}, "
                    f"not {want_step}")
        return n_leaves
    want = [np.asarray(x) for x in prints[want_step]]
    have = [np.asarray(x) for x in fingerprint(got[1])]
    return sum(not np.array_equal(a, b) for a, b in zip(want, have))


def run(r) -> Dict[str, Any]:
    import jax

    import flops
    from repro.checkpoint import (CheckpointManager,
                                  CoordinatedCheckpointManager, Level)
    from repro.core import ScrutinyConfig, participation
    from repro.distributed.collective import get_collective

    wl = r.workload
    every, n_check = wl["save_every"], wl["checked_steps"]
    if every <= n_check:
        raise ValueError("save_every must exceed checked_steps: the first "
                         "save follows the checked steps")
    shutil.rmtree(r.work, ignore_errors=True)
    ckpt = os.path.join(r.work, "ckpt")
    os.makedirs(ckpt)

    # --- set-up: state, compiled step, manager -------------------------
    with r.span("setup.state"):
        job = Job(r.config, wl, r.seed)
    cfg, pipeline, step_fn = job.cfg, job.pipeline, job.step_fn

    def resume(s):
        """train.py's "rest of the program": the next step's outputs."""
        batch, data = pipeline.next_batch(cfg, s["data"])
        p, o, metrics = step_fn(s["params"], s["opt"], batch)
        return {"loss": metrics["loss"], "params": p, "opt": o, "data": data}

    def scrutiny_fn(host_state):
        with r.span("scrutiny.participation"):
            return participation(resume, host_state, config=ScrutinyConfig())

    disk = Level(os.path.join(ckpt, "disk"), interval=every * 2, keep_n=2,
                 shards=2, parity=True)
    mgr = CoordinatedCheckpointManager(
        [Level(os.path.join(ckpt, "ram"), interval=every, keep_n=2), disk],
        collective=get_collective(coord_dir=os.path.join(ckpt, "coord")),
        scrutiny_fn=scrutiny_fn)
    # one process: the coordinator delegates to its pipelined manager
    stats_of = getattr(mgr, "_inner", None) or mgr
    fingerprint = _fingerprint_fn()
    prints: Dict[int, Any] = {}

    def save(step: int) -> float:
        with r.span("save.dispatch"):
            mgr.save(step, job.state)
        prints[step] = fingerprint(job.state)
        return float(stats_of.last_save_stats["blocked_s"])

    with r.span("setup.checked_steps"):
        prog = job.checked_steps(n_check)
    feed = Feed(job, r, wl["ahead_steps"])
    with r.span("setup.first_save"):
        for step in range(n_check + 1, every + 1):
            feed.step(step)
        feed.drain()
        save(every)
        mgr.wait()
    state_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(job.state))

    # --- the window: whole save intervals --------------------------------
    step, blocked, saved = every, [], []
    first = len(feed.done)
    t0 = time.perf_counter()
    wchar0 = harness.write_counters()
    while True:
        step += 1
        feed.step(step)
        if step % every == 0:
            feed.drain()                 # the save snapshots a ready state
            blocked.append(save(step))
            saved.append(step)
        if ((step + 1) % every == 0 and step > 2 * every
                and feed.done[-1][1] >= t0 + r.seconds):
            break
    feed.drain()
    t1 = time.perf_counter()
    n_steps = step - every
    mgr.wait()
    stored = (harness.write_counters() - wchar0) / (len(saved) * state_bytes)

    if r.trace:
        # a short traced segment after the window, with no saves: the
        # profiler's stop costs minutes for long traces
        with r.profiled() as traced_window:
            step += 1
            feed.step(step)              # one step: the profiler settles
            feed.drain()
            with traced_window():
                for _ in range(wl["trace_steps"]):
                    step += 1
                    feed.step(step)
                feed.drain()
        r.read["traced_steps"] = wl["trace_steps"]
    r.read.update(save_blocked_s=blocked, stored_bytes_frac=stored,
                  step_flops=flops.train_step_flops(job.model, job.batch,
                                                    job.seq),
                  window_compiles=r.compiles.between(t0, t1))
    # when each window step was seen finished, less the one before: the
    # step's time once the host waits on a full queue; the first step
    # after the window opens and after the save's drain takes the time of
    # the steps sent at once with it, which then read about 0
    seen = [(every, t0)] + feed.done[first:first + n_steps]
    step_s = [(b[0], b[1] - a[1]) for a, b in zip(seen, seen[1:])]
    times = sorted(s for _, s in step_s)
    slow = sorted(step_s, key=lambda x: -x[1])[:4]
    harness.log(f"window: {n_steps} steps, saves at {saved}, "
                f"{r.read['window_compiles']} compiles, "
                f"{(t1 - t0) / n_steps * 1e3:.1f} ms/step; step "
                f"median {times[len(times) // 2] * 1e3:.1f} ms, slowest "
                f"{[(n, round(s * 1e3, 1)) for n, s in slow]}; blocked "
                f"{[round(b * 1e3, 1) for b in blocked]} ms; stored "
                f"{stored:.6f} of the saves' live bytes")
    device = harness.device_info(r.devices)

    # --- the window's newest checkpoint, restored twice -------------------
    n_leaves = len(jax.tree_util.tree_leaves(job.state))
    newest = _leaves_differing(fingerprint, prints, mgr.restore(job.state),
                               saved[-1], n_leaves)
    mgr.close()
    disk_mgr = CheckpointManager([disk])
    disk_step = max(s for s in saved if s % disk.interval == 0)
    from_disk = _leaves_differing(fingerprint, prints,
                                  disk_mgr.restore(job.state), disk_step,
                                  n_leaves)
    disk_mgr.close()
    harness.log(f"restored step {saved[-1]}: {newest} leaves differ; from "
                f"the disk level alone, step {disk_step}: {from_disk}")
    job.state = None
    prints.clear()

    # --- the plain reference, on the same weights and rows ----------------
    with r.span("check.reference"):
        ref = reference(job, wl, n_check)
    g = gaps(prog, ref)
    harness.log(f"losses {prog['losses']} reference {ref['losses']}; worst "
                f"grad leaf {g['grad_leaf']}, change leaf {g['change_leaf']}")
    checks = {name: {"value": g[name], "limit": limit}
              for name, limit in wl["limits"].items()}
    checks["restore_leaves_differing"] = {"value": float(newest),
                                          "limit": 0.0}
    checks["disk_restore_leaves_differing"] = {"value": float(from_disk),
                                               "limit": 0.0}
    e2e = {"train_step_ms": (t1 - t0) / n_steps * 1e3,
           "setup_s": t0 - r.t_start}
    return {"attempted": n_steps, "failed": 0, "e2e": e2e, "checks": checks,
            "device": device}
