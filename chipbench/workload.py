"""What a traffic file asks for, made from ``--seed``: documents, prompts
and the open-loop schedule of their due times. Imports no JAX: the
generator process and the device process both call it, and get the same
inputs from the same seed.

Every seed gets the same amount of work: the number of requests due in
each segment of a run (lead, window, tail) is the rate times the
segment's length, placed as uniform points (a Poisson process conditioned
on its count), and sizes drawn from a distribution come from one fixed
draw that the seed only permutes.
"""
from __future__ import annotations

import numpy as np

BOS, EOS, PAD = 257, 258, 256          # the byte tokenizer's special ids
_FIXED = 0x5EED                        # stream for draws the seed only orders


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one purpose of one seed; any non-negative seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


# -- text --------------------------------------------------------------------
def _vocabulary(n: int = 4096) -> tuple[list[str], np.ndarray]:
    """Fixed pseudo-words and Zipf(1.1) weights, the same for every seed."""
    g = rng(_FIXED, 1)
    onsets = "b c d f g h j k l m n p r s t v w z br ch cr dr fl gr pl sh st th tr".split()
    vowels = "a e i o u ai ea ou io".split()
    words = []
    for _ in range(n):
        syl = int(g.integers(1, 4))
        words.append("".join(onsets[g.integers(len(onsets))]
                             + vowels[g.integers(len(vowels))]
                             for _ in range(syl)))
    w = 1.0 / np.arange(1, n + 1) ** 1.1
    return words, w / w.sum()


_VOCAB = None


def words(g: np.random.Generator, n: int) -> str:
    global _VOCAB
    if _VOCAB is None:
        _VOCAB = _vocabulary()
    vocab, p = _VOCAB
    idx = g.choice(len(vocab), size=n, p=p)
    return " ".join(vocab[i] for i in idx)


def document(seed: int, i: int, lo: int, hi: int) -> str:
    """Document ``i`` of a seed's stream: ``lo``..``hi`` words."""
    g = rng(seed, 2, i)
    return words(g, int(g.integers(lo, hi + 1))) + "."


def doc_tokens(text: str) -> int:
    """Tokens the byte tokenizer makes of a document: BOS, bytes, EOS."""
    return len(text.encode()) + 2


def prompt(seed: int, i: int, nbytes: int, stream: int = 3) -> str:
    """An ASCII prompt of exactly ``nbytes`` bytes."""
    g = rng(seed, stream, i)
    text = ""
    while len(text) < nbytes:
        text += words(g, max(8, nbytes // 4)) + " "
    return text[:nbytes]


def prompt_sizes(spec, n: int, seed: int) -> list[int]:
    """Prompt byte lengths of ``n`` requests: a fixed size, or a lognormal
    draw made once for all seeds and put in the seed's order."""
    if isinstance(spec, int):
        return [spec] * n
    ln = spec["lognormal"]
    g = rng(_FIXED, 4, n)
    sizes = np.exp(np.log(ln["median"]) + ln["sigma"] * g.standard_normal(n))
    sizes = np.clip(np.round(sizes), ln["min"], ln["max"]).astype(int)
    return [int(s) for s in rng(seed, 4).permutation(sizes)]


def pack(docs, batch: int, seq: int, n_batches: int):
    """``n_batches`` batches of ``batch`` rows of ``seq + 1`` tokens, packed
    as the program's loader packs a stream: ``[BOS] bytes [EOS]`` for each
    document (bytes) in order, cut into rows. ``None`` where the documents
    run out first."""
    need = n_batches * batch * (seq + 1)
    ids, have = [], 0
    for value in docs:
        doc = np.concatenate([[BOS], np.frombuffer(value, np.uint8),
                              [EOS]]).astype(np.int32)
        ids.append(doc)
        have += len(doc)
        if have >= need:
            break
    if have < need:
        return None
    return np.concatenate(ids)[:need].reshape(n_batches, batch, seq + 1)


# -- schedules -----------------------------------------------------------------
def arrivals(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times of requests, in seconds after the schedule's start, sorted.
    Segments: ``lead_s`` before the window, the window, ``tail_s`` after."""
    rate = float(traffic["rate_per_s"])
    lead, tail = float(traffic.get("lead_s", 0.0)), float(traffic["tail_s"])
    out, t0 = [], 0.0
    for k, length in enumerate((lead, seconds, tail)):
        n = int(round(rate * length))
        out.append(t0 + np.sort(rng(seed, 5, k).uniform(0.0, length, n)))
        t0 += length
    return np.concatenate(out)


def requests(traffic: dict, seed: int, seconds: float) -> list[dict]:
    """Every request of a run: id, due offset, prompt."""
    due = arrivals(traffic, seed, seconds)
    sizes = prompt_sizes(traffic["prompt_bytes"], len(due), seed)
    return [{"id": f"r{i}", "due": float(d), "prompt": prompt(seed, i, sizes[i])}
            for i, d in enumerate(due)]


def doc_schedule(traffic: dict, seed: int):
    """Yields (i, due offset, text) for the document stream without end:
    open loop at ``tokens_per_s``."""
    lo, hi = traffic["doc_words"]
    rate = float(traffic["tokens_per_s"])
    sent = 0
    for i in range(1 << 62):
        text = document(seed, i, lo, hi)
        yield i, sent / rate, text
        sent += doc_tokens(text)
