#!/usr/bin/env python
"""End-to-end driver: train the RESPECT agent with REINFORCE (paper §III-B).

The paper's pipeline on the unified padded batch stack: synthetic DAG
sampler (fixed |V| = 30 or a mixed-size range) -> exact labels (vmapped DP,
on-disk cache) -> LSTM-PtrNet + rollout-baseline REINFORCE -> deployable
scheduler checkpoint.  Training consumes the SAME pad-aware
`PaddedGraphBatch` representation the serving engine runs on, so mixed-size
curriculum streams, data-parallel sharding and checkpoint resume all ride
the one batch contract.

Defaults are scaled for this single-CPU-core container (hidden 128,
batch 64, a few hundred steps — minutes); ``--paper-scale`` selects the
paper's setup (hidden 256, batch 128, lr 1e-4 Adam).

    PYTHONPATH=src python examples/train_respect.py --steps 300
    # mixed-size curriculum (transfers to larger real DNN graphs):
    PYTHONPATH=src python examples/train_respect.py --n-min 10 --n-max 50
    # data parallel over forced host devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/train_respect.py --devices 8

Outputs: artifacts/respect_agent (checkpoint-manager format; load it with
``RespectScheduler.load``) + metrics JSONL + periodic trainer checkpoints under
--ckpt-dir (resumable: kill and re-run to continue).
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402

from repro.core import DagSampler, PipelineSystem, RespectScheduler, prefetch  # noqa: E402
from repro.core.rl import RLTrainer  # noqa: E402
from repro.runtime.metrics import MetricsLogger  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--n-min", type=int, default=30,
                    help="smallest sampled graph size")
    ap.add_argument("--n-max", type=int, default=30,
                    help="largest sampled graph size (n-min < n-max turns "
                         "on the mixed-size curriculum stream)")
    ap.add_argument("--no-curriculum", action="store_true",
                    help="mixed sizes without the small-first ramp")
    ap.add_argument("--devices", type=int, default=None,
                    help="data-parallel device count (shard_map over the "
                         "batch axis; global batch must divide it)")
    ap.add_argument("--label-method", choices=("dp", "bb"), default="dp")
    ap.add_argument("--label-cache", default="artifacts/label_cache")
    ap.add_argument("--ckpt-dir", default="artifacts/respect_ckpt")
    ap.add_argument("--save-every", type=int, default=100)
    ap.add_argument("--paper-scale", action="store_true",
                    help="hidden 256, batch 128, lr 1e-4 (paper setup)")
    ap.add_argument("--out", default="artifacts/respect_agent")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.paper_scale:
        args.hidden, args.batch, args.lr = 256, 128, 1e-4

    system = PipelineSystem(n_stages=args.stages)
    n_spec = (args.n_min, args.n_max) if args.n_min < args.n_max else args.n_min
    sampler = DagSampler(seed=args.seed, n=n_spec,
                         label_cache_dir=args.label_cache)
    eval_sampler = DagSampler(seed=args.seed + 10**6, n=n_spec,
                              label_cache_dir=args.label_cache)
    eval_batch = eval_sampler.next_packed_batch(
        128, args.stages, system, label_method=args.label_method)

    trainer = RLTrainer(n_stages=args.stages, system=system,
                        hidden=args.hidden, lr=args.lr, seed=args.seed,
                        n_devices=args.devices)
    sampler_state_path = Path(args.ckpt_dir) / "sampler_state.json"

    def save_all(blocking: bool = True) -> None:
        trainer.save(args.ckpt_dir, blocking=blocking)
        # the prefetch thread may have drawn up to `depth` batches ahead of
        # the trainer, so a resume continues from the saved counter: it
        # never REPLAYS consumed data (the failure that degrades training),
        # at worst it skips the few prefetched-but-unconsumed draws.
        sampler_state_path.write_text(json.dumps(sampler.state()))

    resumed = trainer.restore(args.ckpt_dir)
    if resumed is not None:
        if sampler_state_path.exists():
            sampler.restore(json.loads(sampler_state_path.read_text()))
        print(f"[resume] restored trainer checkpoint at step {resumed} "
              f"(sampler counter {sampler.state()['count']})")
    logger = MetricsLogger("artifacts/respect_train_metrics.jsonl",
                           print_every=10)
    key = jax.random.PRNGKey(args.seed)

    r0 = trainer.evaluate(eval_batch)
    print(f"[init] greedy reward {r0['reward_greedy']:.4f} "
          f"exact-match {r0['exact_match']:.3f}")

    # labeled per-bucket packs stream from a background thread while the
    # device runs the current step; batch dims stay divisible by the
    # device count, and the restored (seed, counter) state makes a
    # resumed stream continue exactly where the killed run stopped
    stream = prefetch(sampler.packed_stream(
        args.batch, args.stages, system, label_method=args.label_method,
        curriculum=not args.no_curriculum,
        batch_divisor=args.devices or 1), depth=2)

    t0 = time.time()
    step = trainer.step_count
    while step < args.steps:
        batch = next(stream)
        # per-step key by fold_in: resuming at step k reproduces the key
        # stream a never-interrupted run would have used
        k = jax.random.fold_in(key, step)
        metrics = trainer.train_step(batch, k)
        step = trainer.step_count
        logger.log(step, metrics)
        if step % args.eval_every == 0:
            updated = trainer.maybe_update_baseline(eval_batch)
            ev = trainer.evaluate(eval_batch)
            print(f"[eval step {step}] greedy={ev['reward_greedy']:.4f} "
                  f"exact-match={ev['exact_match']:.3f} "
                  f"baseline-updated={updated} "
                  f"({(time.time()-t0)/max(step,1):.2f}s/step)")
        if step % args.save_every == 0:
            save_all(blocking=False)

    save_all()
    ev = trainer.evaluate(eval_batch)
    print(f"[final] greedy reward {ev['reward_greedy']:.4f} "
          f"(start {r0['reward_greedy']:.4f}) "
          f"exact-match {ev['exact_match']:.3f}")

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    RespectScheduler(trainer.params).save(out)
    print(f"[saved] {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
