"""Hypothesis property tests on the ingestion fabric's invariants."""
import json

from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core import (Connection, DetectDuplicate, OffsetStore,
                        PartitionedLog, make_flowfile, range_assign)

_SETTINGS = dict(deadline=None, max_examples=40,
                 suppress_health_check=[HealthCheck.function_scoped_fixture])


@given(records=st.lists(st.binary(min_size=0, max_size=200), max_size=200),
       partitions=st.integers(min_value=1, max_value=8))
@settings(**_SETTINGS)
def test_log_read_after_write_exact(tmp_path_factory, records, partitions):
    """Everything appended is read back, in order, byte-identical."""
    root = tmp_path_factory.mktemp("log")
    log = PartitionedLog(root, segment_bytes=512)
    log.create_topic("t", partitions=partitions)
    placed: dict[int, list[bytes]] = {p: [] for p in range(partitions)}
    for i, v in enumerate(records):
        p = i % partitions
        log.append("t", f"{i}".encode(), v, partition=p)
        placed[p].append(v)
    for p in range(partitions):
        got = [r.value for r in log.read("t", p, 0, max_records=len(records) + 1)]
        assert got == placed[p]
    log.close()


@given(keys=st.lists(st.text(max_size=20), min_size=1, max_size=300))
@settings(**_SETTINGS)
def test_dedup_exact_set_semantics(keys):
    """Exact dedup: 'unique' outputs == set of inputs; every repeat flagged."""
    d = DetectDuplicate(mode="exact", key_fn=lambda ff: ff.content)
    uniques, dups = [], []
    for k in keys:
        for rel, ff in d.process(make_flowfile(k)):
            (uniques if rel == "unique" else dups).append(ff.content)
    assert sorted(set(uniques)) == sorted(set(k.encode() for k in keys))
    assert len(uniques) + len(dups) == len(keys)
    assert len(uniques) == len(set(k.encode() for k in keys))


@given(ops=st.lists(st.tuples(st.booleans(), st.integers(1, 50)), max_size=200),
       threshold=st.integers(min_value=1, max_value=20))
@settings(**_SETTINGS)
def test_backpressure_invariant_never_exceeds_threshold(ops, threshold):
    """Queue depth never exceeds the object threshold; accepted == drained +
    still queued (no loss, no duplication)."""
    c = Connection("c", object_threshold=threshold)
    accepted = drained = 0
    for is_offer, size in ops:
        if is_offer:
            if c.offer(make_flowfile(b"x" * size), block=False):
                accepted += 1
        else:
            if c.poll(block=False) is not None:
                drained += 1
        assert len(c) <= threshold
    assert accepted == drained + len(c)


@given(partitions=st.integers(0, 64),
       members=st.lists(st.text(min_size=1, max_size=5), min_size=1,
                        max_size=10, unique=True))
@settings(**_SETTINGS)
def test_range_assign_partition_exactly_once(partitions, members):
    a = range_assign(partitions, members)
    got = sorted(p for ps in a.values() for p in ps)
    assert got == list(range(partitions))
    sizes = [len(v) for v in a.values()]
    assert max(sizes) - min(sizes) <= 1          # balanced


@given(commits=st.lists(st.tuples(st.integers(0, 3), st.integers(0, 10_000)),
                        max_size=50))
@settings(**_SETTINGS)
def test_offset_store_last_write_wins(tmp_path_factory, commits):
    root = tmp_path_factory.mktemp("off")
    s = OffsetStore(root / "o.json")
    last: dict[int, int] = {}
    for p, off in commits:
        s.commit("g", "t", {p: off})
        last[p] = off
    s2 = OffsetStore(root / "o.json")            # reload from disk
    for p, off in last.items():
        assert s2.get("g", "t", p) == off
