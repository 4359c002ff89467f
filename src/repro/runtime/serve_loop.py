"""Serving runtime: batched prefill+decode driven by the ingestion fabric.

Requests arrive as FlowFiles on a 'requests' topic (any producer — REST
bridge, another pipeline); the server consumes them as a consumer group
member, forms fixed-size batches, runs prefill + greedy decode, and
publishes completions to a 'completions' topic. Adding more servers =
adding group members (the paper's elastic-consumer property applied to
inference).
"""
from __future__ import annotations

import json
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core import LogStore
from ..core.delivery import Consumer
from ..core.flowfile import FlowFile
from ..data.tokenizer import ByteTokenizer
from ..models import Model


@dataclass
class ServeConfig:
    batch_size: int = 4
    prompt_len: int = 64          # fixed prefill window (pad/truncate)
    max_new_tokens: int = 32
    eos_id: int = ByteTokenizer.EOS


def make_decode_fn(model: Model):
    return jax.jit(model.decode_step)


def make_prefill_fn(model: Model, max_len: int):
    def fn(params, batch):
        return model.prefill(params, batch, max_len=max_len)
    return jax.jit(fn)


class Server:
    def __init__(self, model: Model, params, consumer: Consumer,
                 out_log: LogStore, scfg: ServeConfig) -> None:
        self.model = model
        self.params = params
        self.consumer = consumer
        self.out_log = out_log
        self.scfg = scfg
        self.tok = ByteTokenizer()
        max_len = scfg.prompt_len + scfg.max_new_tokens
        self._prefill = make_prefill_fn(model, max_len)
        self._decode = make_decode_fn(model)
        self.served = 0

    def batch_prompts(self, ffs) -> tuple[np.ndarray, list[str]]:
        s = self.scfg
        toks = np.full((len(ffs), s.prompt_len), self.tok.PAD, np.int32)
        ids = []
        for i, ff in enumerate(ffs):
            req = json.loads(ff.value) if hasattr(ff, "value") else ff.json()
            ids.append(str(req.get("id", i)))
            enc = self.tok.encode(req.get("prompt", ""), add_eos=False)
            enc = enc[-s.prompt_len:]
            toks[i, :len(enc)] = enc       # left-aligned, right-padded
        return toks, ids

    def serve_once(self) -> int:
        """Poll one batch of requests, decode, publish. Returns #served."""
        s = self.scfg
        recs = self.consumer.poll(max_records=s.batch_size)
        if not recs:
            return 0
        while len(recs) < s.batch_size:   # pad batch with a copy (masked out)
            recs.append(recs[0])
        toks, req_ids = self.batch_prompts(recs)
        batch = {"tokens": jnp.asarray(toks)}
        logits, cache = self._prefill(self.params, batch)
        out_tokens = np.zeros((toks.shape[0], s.max_new_tokens), np.int32)
        cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        for t in range(s.max_new_tokens):
            out_tokens[:, t] = np.asarray(cur)[:, 0]
            logits, cache = self._decode(self.params, cache, cur)
            cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        n = 0
        for i, rid in enumerate(req_ids[:len(set(req_ids))]):
            text = self.tok.decode(out_tokens[i].tolist())
            payload = json.dumps({"id": rid, "completion_ids":
                                  out_tokens[i].tolist(), "text": text})
            self.out_log.append("completions", rid.encode(), payload.encode())
            n += 1
        self.consumer.commit()            # at-least-once for serving
        self.served += n
        return n
