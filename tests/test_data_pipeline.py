"""Tokenizer, packing, streaming loader: determinism + exactly-once."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import ConsumerGroup, PartitionedLog, make_flowfile
from repro.data import (ByteTokenizer, SequencePacker, StreamingDataLoader,
                        attach_training_loader, build_news_pipeline)


def test_tokenizer_roundtrip():
    tok = ByteTokenizer()
    ids = tok.encode("hello stream")
    assert ids[0] == tok.BOS and ids[-1] == tok.EOS
    assert tok.decode(ids) == "hello stream"


@given(st.text(max_size=400))
@settings(deadline=None, max_examples=50)
def test_tokenizer_roundtrip_property(s):
    tok = ByteTokenizer()
    assert tok.decode(tok.encode(s)) == s


def test_packer_emits_full_rows_only():
    p = SequencePacker(seq_len=7, pad_id=256)
    rows = p.add_document(list(range(20)))
    assert len(rows) == 2 and all(len(r) == 8 for r in rows)
    assert rows[0].tolist() == list(range(8))
    tail = p.flush()
    assert tail is not None and tail[:4].tolist() == [16, 17, 18, 19]
    assert (tail[4:] == 256).all()


@given(st.lists(st.integers(1, 50), min_size=1, max_size=40),
       st.integers(4, 64))
@settings(deadline=None, max_examples=40)
def test_packer_conserves_tokens(doc_lens, seq_len):
    """No token lost, no token duplicated, order preserved."""
    p = SequencePacker(seq_len=seq_len, pad_id=0)
    stream, emitted = [], []
    tok = 1
    for n in doc_lens:
        doc = list(range(tok, tok + n)); tok += n
        stream.extend(doc)
        for row in p.add_document(doc):
            emitted.extend(row.tolist())
    emitted.extend(p.state()["carry"])
    assert emitted == stream


def _fill_log(tmp_path, n_docs=200, partitions=4):
    log = PartitionedLog(tmp_path / "log")
    log.create_topic("docs", partitions=partitions)
    for i in range(n_docs):
        ff = make_flowfile(f"document number {i} " + "tok " * (i % 37))
        k, v = ff.to_record()
        log.append("docs", k, v, partition=i % partitions)
    return log


def _make_loader(log, member="m0", group="g", batch_size=4, seq_len=64):
    grp = ConsumerGroup(log, "docs", group)
    c = grp.add_member(member)
    return StreamingDataLoader(c, batch_size=batch_size, seq_len=seq_len)


def test_loader_produces_batches(tmp_path):
    log = _fill_log(tmp_path)
    loader = _make_loader(log)
    b = loader.next_batch()
    assert b.shape == (4, 65) and b.dtype == np.int32
    assert loader.batches_emitted == 1
    log.close()


def test_loader_exactly_once_restore(tmp_path):
    """The core guarantee: after restoring loader state, the continuation of
    the batch stream is byte-identical to the uninterrupted run."""
    log = _fill_log(tmp_path)
    loader = _make_loader(log)
    for _ in range(3):
        loader.next_batch()
    ckpt = loader.state()
    expected = [loader.next_batch() for _ in range(4)]

    log2 = PartitionedLog(tmp_path / "log")       # fresh process
    loader2 = _make_loader(log2, group="g2")
    loader2.restore(ckpt)
    got = [loader2.next_batch() for _ in range(4)]
    for e, g in zip(expected, got):
        np.testing.assert_array_equal(e, g)
    log.close(); log2.close()


def test_loader_returns_none_when_exhausted(tmp_path):
    log = _fill_log(tmp_path, n_docs=2)
    loader = _make_loader(log, batch_size=512, seq_len=512)
    assert loader.next_batch(timeout_polls=3) is None
    log.close()


def test_loader_prefetch_thread(tmp_path):
    log = _fill_log(tmp_path)
    loader = _make_loader(log)
    loader.start()
    b = loader.get_prefetched(timeout=10)
    assert b is not None and b.shape == (4, 65)
    loader.stop()
    log.close()


def test_news_pipeline_end_to_end(tmp_path):
    flow, log = build_news_pipeline(tmp_path, n_rss=300, n_firehose=300,
                                    n_ws=50, partitions=4)
    flow.run_to_completion(timeout=120)
    assert sum(log.end_offsets("articles")) > 300   # most records survive
    assert sum(log.end_offsets("events")) == 50
    grp, loader = attach_training_loader(log, batch_size=2, seq_len=128)
    b = loader.next_batch()
    assert b.shape == (2, 129)
    # two consumers (training + eval) attach independently — the paper's
    # add-consumers-without-changing-the-pipeline property
    grp2, loader2 = attach_training_loader(log, group="eval", batch_size=2,
                                           seq_len=128)
    b2 = loader2.next_batch()
    np.testing.assert_array_equal(b, b2)            # same stream, same bytes
    log.close()
