"""End-to-end GNN training through the ``repro.train`` orchestration API
(ISSUE 7): DatasetProvider → Task → Trainer on the planned-Pallas models.

The example *asserts the training contract itself*:

  * loss decreases for every trained family (gcn homogeneous + rgcn
    relational by default);
  * the jitted train step compiles **exactly once per graph shape
    bucket** — the provider's plan memo plus the task's per-bucket plan
    canonicalization mean steps never re-plan and never retrace
    (``FitResult.traces == len(FitResult.buckets)``);
  * a mid-run kill (``--kill-at``, exercised via a subprocess) followed
    by ``fit(resume=True)`` restores from the checkpoint to a loss
    trajectory identical (≤ 1e-6, in practice bitwise) to the
    uninterrupted run — providers are deterministic in the step index
    and the PRNG key rides the checkpointed TrainState.

The uninterrupted and the killed run are both child processes, started
before this process touches a device: on a TPU the chip belongs to one
process at a time.

Usage:
  python examples/gnn_training.py                  # full smoke (CI default)
  python examples/gnn_training.py --models gcn --steps 60
  python examples/gnn_training.py --resume --ckpt-dir /tmp/d   # resume leg
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

from repro import fit  # the facade export — the acceptance criterion
from repro.optim import adamw
from repro.train import (GraphEpochProvider, NodeClassification, Trainer,
                         TrainerConfig)

SHAPES = ((96, 384), (128, 512))


def build(model: str, args, ckpt_dir=None):
    typed = model in ("rgcn", "rgat")
    data = GraphEpochProvider(
        shapes=SHAPES, graphs_per_shape=2, feat=args.feat,
        num_classes=args.classes, typed=typed, num_relations=4,
        seed=args.seed)
    task = NodeClassification.from_provider(data, model=model,
                                            hidden=args.hidden,
                                            impl=args.impl)
    cfg = TrainerConfig(
        steps=args.steps, warmup_steps=4,
        opt=adamw.AdamWConfig(lr=args.lr, weight_decay=0.0),
        seed=args.seed, ckpt_dir=ckpt_dir, ckpt_every=args.ckpt_every)
    return task, data, cfg


def train_full(model: str, args):
    task, data, cfg = build(model, args)
    trainer = Trainer(task, data, cfg)
    res = trainer.fit()
    n_buckets = len(SHAPES)
    assert res.losses[-1] < res.losses[0], (
        f"{model}: loss did not decrease "
        f"({res.losses[0]:.4f} -> {res.losses[-1]:.4f})")
    assert res.traces == len(res.buckets) == n_buckets, (
        f"{model}: expected exactly one trace per shape bucket "
        f"({n_buckets}), got traces={res.traces} buckets={res.buckets}")
    print(f"[{model}] loss {res.losses[0]:.4f} -> {res.losses[-1]:.4f}  "
          f"traces={res.traces} buckets={len(res.buckets)}  OK")
    return res


def kill_and_resume(args):
    """Two child runs of gcn — one uninterrupted (its losses to a file),
    one killed mid-run with checkpoints — then the resume in this process,
    whose trajectory must match the uninterrupted one to <= 1e-6."""
    kill_at = args.steps // 2 - 1
    with tempfile.TemporaryDirectory(prefix="repro_train_ckpt_") as d:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--models", "gcn", "--steps", str(args.steps),
               "--lr", str(args.lr), "--seed", str(args.seed),
               "--hidden", str(args.hidden), "--impl", args.impl]
        losses_path = os.path.join(d, "losses.json")
        ckpt_dir = os.path.join(d, "ckpt")
        for leg in (["--losses-out", losses_path],
                    ["--ckpt-dir", ckpt_dir, "--ckpt-every",
                     str(args.ckpt_every), "--kill-at", str(kill_at)]):
            proc = subprocess.run(cmd + leg, capture_output=True, text=True)
            assert proc.returncode == 0, proc.stderr
            print(proc.stdout, end="")
        with open(losses_path) as f:
            full_losses = json.load(f)
        task, data, cfg = build("gcn", args, ckpt_dir=ckpt_dir)
        res = Trainer(task, data, cfg).fit(resume=True)
        expect_start = (kill_at // args.ckpt_every) * args.ckpt_every
        assert res.start_step == expect_start > 0, (
            res.start_step, expect_start)
        tail = full_losses[res.start_step:]
        assert len(tail) == len(res.losses)
        worst = max(abs(a - b) for a, b in zip(tail, res.losses))
        assert worst <= 1e-6, (
            f"resumed trajectory diverged: max |Δloss| = {worst:.2e}")
        print(f"[resume] killed at step {kill_at}, restored step "
              f"{res.start_step}, max |Δloss| vs uninterrupted run "
              f"{worst:.2e}  OK")


def run_killed(model: str, args):
    """The subprocess leg: train with checkpoints, hard-exit mid-run."""
    task, data, cfg = build(model, args, ckpt_dir=args.ckpt_dir)

    def cb(step, metrics, verdict):
        if step >= args.kill_at:
            # simulate a hard crash: no cleanup, no final checkpoint
            os._exit(0)

    Trainer(task, data, cfg).fit(metrics_cb=cb)
    raise SystemExit(f"kill at step {args.kill_at} never happened")


def run_resume(args):
    """Explicit --resume leg: continue a run from --ckpt-dir."""
    model = args.models.split(",")[0]
    task, data, cfg = build(model, args, ckpt_dir=args.ckpt_dir)
    res = Trainer(task, data, cfg).fit(resume=True)
    assert res.start_step > 0, "nothing to resume from"
    # the epoch cycles through several distinct graphs, so compare
    # epoch-mean losses, not raw endpoints (different graphs)
    n = len(data)
    assert len(res.losses) >= 2 * n, "resumed run too short to judge"
    first = sum(res.losses[:n]) / n
    last = sum(res.losses[-n:]) / n
    assert last < first, (first, last)
    print(f"[{model}] resumed from step {res.start_step}, "
          f"epoch-mean loss {first:.4f} -> {last:.4f}  OK")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="gcn,rgcn",
                    help="comma-separated: gcn gin sage gat rgcn rgat")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--feat", type=int, default=16)
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", default="pallas", choices=["ref", "pallas"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=8)
    ap.add_argument("--kill-at", type=int, default=None,
                    help="(internal) hard-exit at this step")
    ap.add_argument("--resume", action="store_true",
                    help="resume the first of --models from --ckpt-dir")
    ap.add_argument("--skip-kill-test", action="store_true")
    ap.add_argument("--losses-out", default=None,
                    help="(internal) train the first of --models and "
                         "write its per-step losses to this JSON file")
    args = ap.parse_args(argv)

    if args.losses_out:
        res = train_full(args.models.split(",")[0], args)
        with open(args.losses_out, "w") as f:
            json.dump(res.losses, f)
        return
    if args.kill_at is not None:
        run_killed(args.models.split(",")[0], args)
        return
    if args.resume:
        if not args.ckpt_dir:
            raise SystemExit("--resume needs --ckpt-dir")
        run_resume(args)
        return

    models = [m for m in args.models.split(",") if m]
    if not args.skip_kill_test and "gcn" in models:
        kill_and_resume(args)       # first: its children need the device
    for model in models:
        if model != "gcn" or args.skip_kill_test:
            train_full(model, args)
    print("all training checks passed")


if __name__ == "__main__":
    main()
