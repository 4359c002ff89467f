"""Training launcher: topic ``articles`` → StreamingDataLoader → Trainer.

The launcher exits non-zero when it trained fewer steps than it was asked
for.

  PYTHONPATH=src python -m repro.launch.train --steps 20 --workdir /tmp/run1
  PYTHONPATH=src python -m repro.launch.train --resume --workdir /tmp/run1
"""
from __future__ import annotations

import argparse
import tempfile
from pathlib import Path

from .. import configs
from ..core import LogStore, PartitionedLog, make_flowfile
from ..core.sources import corpus_documents
from ..data.pipeline import attach_training_loader
from ..models import ArchConfig, Model
from ..optim import OptConfig
from ..runtime import Trainer, TrainerConfig
from .compile_cache import use_compile_cache


def corpus_log(root: Path, docs: int) -> PartitionedLog:
    """Open ``root/log``, filling topic ``articles`` with ``docs`` corpus
    documents the first time."""
    log = PartitionedLog(root / "log")
    if "articles" not in log.topics():
        log.create_topic("articles", partitions=8)
        batch = [make_flowfile(doc, text=doc).to_record()
                 for doc in corpus_documents(docs)]
        for p in range(8):
            log.append_batch("articles", batch[p::8], partition=p)
        log.flush(fsync=False)
    return log


def train(cfg: ArchConfig, workdir: str | Path | None = None, *,
          log: LogStore | None = None, steps: int = 50, batch: int = 8,
          seq: int = 256, docs: int = 30_000, ckpt_every: int = 25,
          resume: bool = False, fail_at: int = -1,
          log_every: int = 10) -> Trainer:
    """Train ``cfg`` for ``steps`` steps on topic ``articles`` of ``log``
    (default: a corpus log under ``workdir``), checkpointing under
    ``workdir/ckpt``. Returns the trainer; raises ``RuntimeError`` when
    the stream ran dry before ``steps`` steps."""
    use_compile_cache()
    root = Path(workdir or tempfile.mkdtemp(prefix="train_"))
    root.mkdir(parents=True, exist_ok=True)
    own_log = log is None
    if own_log:
        log = corpus_log(root, docs)
    try:
        _, loader = attach_training_loader(log, batch_size=batch,
                                           seq_len=seq)
        trainer = Trainer(
            Model(cfg), loader,
            OptConfig(peak_lr=1e-3, warmup_steps=20, total_steps=steps),
            TrainerConfig(steps=steps, ckpt_every=ckpt_every,
                          ckpt_dir=str(root / "ckpt"), log_every=log_every,
                          fail_at_step=fail_at))
        if resume:
            resumed = trainer.resume()
            print(f"resume: {'ok, at step ' + str(trainer.step_idx) if resumed else 'no checkpoint found'}")
        out = trainer.run()
        for h in trainer.history[-5:]:
            print(h)
        print(out)
    finally:
        if own_log:
            log.close()
    if out["steps"] != steps:
        raise RuntimeError(f"trained {out['steps']} of {steps} steps: "
                           f"the stream ran dry")
    return trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m",
                    choices=list(configs.ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="the arch's toy-width config (CPU smoke runs)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--docs", type=int, default=30_000)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a failure at this step (recovery drills)")
    args = ap.parse_args()
    train(configs.get_reduced(args.arch) if args.reduced
          else configs.get(args.arch),
          args.workdir, steps=args.steps, batch=args.batch, seq=args.seq,
          docs=args.docs, ckpt_every=args.ckpt_every, resume=args.resume,
          fail_at=args.fail_at)


if __name__ == "__main__":
    main()
