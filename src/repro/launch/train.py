"""End-to-end training driver with scrutinized checkpointing + restart.

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
        --steps 200 --batch 8 --seq 256 [--preset smoke] [--resume]

The loop wires every substrate together: data pipeline (resumable, its
state checkpointed), train step, async multi-level CheckpointManager with
the AD-scrutinized reduction, and crash-equivalent restart (the integration
test kills and resumes mid-run and checks loss-curve continuation).

Multi-host runs (``jax.process_count() > 1``, the ``REPRO_PROCESS_*``
simulation env, or ``--coordinated``) go through the
``CoordinatedCheckpointManager``: every host writes only the shards it
owns, the step commits via the collective two-phase protocol, and
``--resume`` restores elastically onto whatever process count is alive.
On a single process the coordinator delegates to the pipelined async
manager, so the wiring is unconditional.

``--preset smoke`` shrinks the model (CPU CI); ``--preset full`` runs the
published widths (``chip_smoke.py`` drives it on one TPU chip).

With ``--scrutinize`` / ``--verify-static`` "the rest of the program" is
the next train step, and its output is everything the run after that
reads: the loss *and* the next params, optimizer state and data state.
Only then does a resume from the reduced checkpoint reproduce the
uninterrupted losses; an output of the loss alone marks the AdamW moments
uncritical (the loss never reads them) and the resumed run diverges.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CoordinatedCheckpointManager, Level
from repro.configs import get_config
from repro.launch.compile_cache import enable_persistent_cache
from repro.distributed.collective import current_context, get_collective
from repro.core import ScrutinyConfig, participation
from repro.data import pipeline as data_pipeline
from repro.models import init_params, count_params
from repro.train.optim import OptConfig, init_opt
from repro.train.step import make_train_step


def build_state(cfg, oc, batch, seq, seed=0):
    params = init_params(cfg, jax.random.PRNGKey(seed))
    opt_state = init_opt(oc, params)
    data_state = data_pipeline.init_state(cfg, batch, seq, seed=seed)
    return {"params": params, "opt": opt_state, "data": data_state,
            "step": jnp.zeros((), jnp.int32)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--scrutinize", action="store_true",
                    help="reduce checkpoints with participation analysis")
    ap.add_argument("--verify-static", action="store_true",
                    help="scrutinize with the AD probe engine, prune the "
                         "sweep with the static analyzer, and gate every "
                         "report on the AD⊆static soundness check "
                         "(repro.analysis)")
    ap.add_argument("--coordinated", action="store_true",
                    help="force the multi-host coordinated save path even "
                         "on one process (it is automatic when "
                         "jax.process_count() > 1 or REPRO_PROCESS_COUNT "
                         "is set)")
    ap.add_argument("--coord-dir", default=None,
                    help="shared rendezvous dir for the filesystem-barrier "
                         "fallback (default: <ckpt-dir>/coord)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--task", default="lm", choices=["lm", "copy"],
                    help="lm: next-token; copy: identity (fast smoke signal)")
    ap.add_argument("--lr", type=float, default=None)
    args = ap.parse_args(argv)

    # persistent XLA cache: relaunches (and --resume restarts) skip the
    # multi-second train-step + scrutiny-sweep compiles
    cache = enable_persistent_cache()
    if cache:
        print(f"compilation cache: {cache}")

    cfg = get_config(args.arch)
    if args.preset == "smoke":
        cfg = cfg.reduced()
    smoke = args.preset == "smoke"
    lr = args.lr if args.lr is not None else (3e-3 if smoke else 3e-4)
    oc = OptConfig(kind="adamw", lr=lr, warmup=5 if smoke else 100,
                   clip_norm=10.0 if smoke else 1.0, decay_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, oc))

    state = build_state(cfg, oc, args.batch, args.seq)
    print(f"arch={cfg.name} params={count_params(state['params'])/1e6:.1f}M "
          f"batch={args.batch} seq={args.seq}")

    scrutiny_fn = None
    soundness_check = None
    if args.scrutinize or args.verify_static:
        # "the rest of the program" for a train checkpoint: the next train
        # step from the data pipeline's next batch, returning the state the
        # step after it reads.  One stable fn object, so the shared jaxpr
        # trace cache hits across scrutiny/static/lint.
        def resume(s):
            batch, data = data_pipeline.next_batch(cfg, s["data"])
            params, opt, metrics = step_fn(s["params"], s["opt"], batch)
            return {"loss": metrics["loss"], "params": params, "opt": opt,
                    "data": data}

        if args.verify_static:
            from repro.analysis import soundness_checker
            from repro.core import scrutinize

            scfg = ScrutinyConfig(static_prune=True)

            def scrutiny_fn(host_state):
                return scrutinize(resume, host_state, config=scfg)

            soundness_check = soundness_checker(resume)
            print("static verification: soundness gate + probe-sweep "
                  "pruning enabled")
        else:
            def scrutiny_fn(host_state):
                return participation(resume, host_state,
                                     config=ScrutinyConfig())

    # Coordinated when the job spans processes (real multi-controller or
    # the REPRO_PROCESS_* simulation); single-process jobs delegate to the
    # pipelined async manager inside, so the wiring is unconditional.
    ctx = current_context()
    coordinated = args.coordinated or ctx.count > 1
    collective = get_collective(
        coord_dir=args.coord_dir or os.path.join(args.ckpt_dir, "coord"))
    parity = not coordinated             # per-host parity: future level
    mgr = CoordinatedCheckpointManager(
        [Level(os.path.join(args.ckpt_dir, "ram"), interval=args.ckpt_every,
               keep_n=2),
         Level(os.path.join(args.ckpt_dir, "disk"),
               interval=args.ckpt_every * 4, keep_n=2, shards=2,
               parity=parity)],
        collective=collective, scrutiny_fn=scrutiny_fn,
        soundness_check=soundness_check,
        force_coordinated=args.coordinated)
    if coordinated:
        print(f"coordinated checkpointing: process {ctx.index} of "
              f"{ctx.count}")

    start = 0
    if args.resume:
        got = mgr.restore(state)
        if got is not None:
            start, state = got
            print(f"resumed from step {start}")

    losses = []
    t0 = time.time()
    for step in range(start + 1, args.steps + 1):
        batch, state["data"] = data_pipeline.next_batch(cfg, state["data"])
        if args.task == "copy":
            batch = {"tokens": batch["tokens"], "labels": batch["tokens"]}
        state["params"], state["opt"], metrics = step_fn(
            state["params"], state["opt"], batch)
        state["step"] = jnp.asarray(step, jnp.int32)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0:
            dt = (time.time() - t0) / args.log_every
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f} ms/step")
            t0 = time.time()
        if step % args.ckpt_every == 0:
            mgr.save(step, state)
    mgr.wait()
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    main()
