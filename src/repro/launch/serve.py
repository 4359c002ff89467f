"""Serving launcher: consume topic ``requests``, publish ``completions``.

Each request's prompt is a corpus document at least ``prompt_len`` bytes
long, so that it fills the prefill window. The launcher exits non-zero
unless every request it sent came back as a completion.

  PYTHONPATH=src python -m repro.launch.serve --reduced --requests 16
"""
from __future__ import annotations

import argparse
import json
import tempfile
from itertools import islice
from pathlib import Path

import jax

from .. import configs
from ..core import ConsumerGroup, LogStore, PartitionedLog
from ..core.sources import corpus_documents
from ..models import ArchConfig, Model
from ..runtime import ServeConfig, Server
from .compile_cache import use_compile_cache


def serve(cfg: ArchConfig, workdir: str | Path | None = None, *,
          log: LogStore | None = None, requests: int = 8, batch: int = 4,
          prompt_len: int = 32, max_new: int = 16) -> Server:
    """Put ``requests`` prompts on topic ``requests`` of ``log`` (default:
    a fresh log under ``workdir``) and serve ``cfg``, with random weights,
    until the topic is drained. Returns the server; raises
    ``RuntimeError`` unless every request id landed on ``completions``."""
    use_compile_cache()
    own_log = log is None
    if own_log:
        root = Path(workdir or tempfile.mkdtemp(prefix="serve_"))
        log = PartitionedLog(root / "log")
    try:
        log.create_topic("requests", partitions=4)
        log.create_topic("completions", partitions=4)
        docs = (d for d in corpus_documents(100 * requests, seed=11)
                if len(d.encode()) >= prompt_len)
        sent = [str(i) for i in range(requests)]
        log.append_batch("requests", [
            (rid.encode(), json.dumps({"id": rid, "prompt": doc}).encode())
            for rid, doc in zip(sent, islice(docs, requests), strict=True)])

        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        grp = ConsumerGroup(log, "requests", "servers")
        server = Server(model, params, grp.add_member("srv0"), log,
                        ServeConfig(batch_size=batch, prompt_len=prompt_len,
                                    max_new_tokens=max_new))
        while server.serve_once():
            pass
        landed = sorted(json.loads(r.value)["id"]
                        for p in range(log.num_partitions("completions"))
                        for r in log.iter_records("completions", p))
        print(f"served {server.served}, completions landed: {len(landed)}")
    finally:
        if own_log:
            log.close()
    if landed != sorted(sent):
        raise RuntimeError(f"{len(landed)} completions landed for "
                           f"{requests} requests")
    return server


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hymba-1.5b",
                    choices=list(configs.ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="the arch's toy-width config (CPU smoke runs)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--workdir", default=None)
    args = ap.parse_args()
    serve(configs.get_reduced(args.arch) if args.reduced
          else configs.get(args.arch),
          args.workdir, requests=args.requests, batch=args.batch,
          prompt_len=args.prompt_len, max_new=args.max_new)


if __name__ == "__main__":
    main()
