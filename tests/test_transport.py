"""Wire protocol + RemoteLogStore tests: framed codec round-trips, torn and
oversized frames, fencing, and cross-process replay determinism."""
from __future__ import annotations

import multiprocessing as mp
import socket
import struct
import threading

import pytest

from repro.core import PartitionedLog
from repro.core.delivery import ConsumerGroup
from repro.core.transport import (FencedError, FenceTable, FrameTooLarge,
                                  LogServer, MAX_FRAME, OP_PING,
                                  RemoteLogStore, TransportError, _Reader,
                                  decode_records, encode_records, recv_ctrl,
                                  recv_exact, send_ctrl, send_frame,
                                  serve_store)

#: fast concurrency-layer module: CI re-runs it under the
#: REPRO_LOCK_ORDER=1 lock-order detector (scripts/ci.sh)
pytestmark = pytest.mark.lockorder


@pytest.fixture()
def remote(tmp_path):
    """A LogServer over a PartitionedLog plus a connected RemoteLogStore."""
    store = PartitionedLog(tmp_path / "server")
    server = LogServer(store).start()
    client = RemoteLogStore(server.address, tmp_path / "client")
    yield client, store, server
    client.close()
    server.stop()
    store.close()


# -- codec -------------------------------------------------------------------

def test_records_codec_roundtrip_deterministic():
    records = [(b"", b""), (b"k", b"v" * 100), (b"\x00\xff", bytes(range(256))),
               (b"key-3", b"")]
    buf = encode_records(records)
    assert decode_records(_Reader(buf)) == records
    assert encode_records(records) == buf          # canonical encoding


def test_records_codec_roundtrip_hypothesis():
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=200, deadline=None)
    @given(st.lists(st.tuples(st.binary(max_size=64),
                              st.binary(max_size=256)), max_size=32))
    def check(records):
        r = _Reader(encode_records(records))
        assert decode_records(r) == records
        r.done()

    check()


def test_reader_rejects_truncated_body():
    records = [(b"key", b"value")]
    buf = encode_records(records)
    with pytest.raises(TransportError):
        decode_records(_Reader(buf[:-1]))          # torn inside last field


def test_recv_exact_distinguishes_eof_from_torn_frame():
    a, b = socket.socketpair()
    try:
        b.sendall(b"abc")
        b.close()
        assert recv_exact(a, 3) == b"abc"
        with pytest.raises(TransportError, match="connection closed"):
            recv_exact(a, 1)                       # clean EOF at boundary
    finally:
        a.close()
    a2, b2 = socket.socketpair()
    try:
        b2.sendall(b"ab")
        b2.close()
        with pytest.raises(TransportError, match="torn frame"):
            recv_exact(a2, 5)                      # EOF mid-frame
    finally:
        a2.close()


def test_oversized_frame_rejected_on_send_and_recv():
    with pytest.raises(FrameTooLarge):
        send_frame(socket.socket(), OP_PING, b"x" * (MAX_FRAME + 1))
    a, b = socket.socketpair()
    try:
        # hand-craft a header claiming a body larger than the cap: the
        # reader must refuse before allocating/reading the body
        b.sendall(struct.pack("<I", MAX_FRAME + 1))
        with pytest.raises(FrameTooLarge):
            from repro.core.transport import recv_frame
            recv_frame(a)
    finally:
        a.close()
        b.close()


def test_ctrl_frames_roundtrip_json():
    a, b = socket.socketpair()
    try:
        msg = {"t": "assign", "spec": {"group": "g0", "epoch": 3,
                                       "partitions": {"articles": [0, 2]}}}
        send_ctrl(a, msg)
        assert recv_ctrl(b) == msg
    finally:
        a.close()
        b.close()


# -- client/server surface ---------------------------------------------------

def test_remote_store_matches_local_logstore_surface(remote, tmp_path):
    client, store, _ = remote
    local = PartitionedLog(tmp_path / "local")
    for log in (client, local):
        log.create_topic("t", partitions=2)
        log.append_batch("t", [(b"a", b"1"), (b"b", b"2"), (b"c", b"3")],
                         partition=0)
        log.append("t", b"k", b"solo", partition=1)
    assert client.topics() == local.topics()
    assert client.num_partitions("t") == local.num_partitions("t")
    assert client.end_offsets("t") == local.end_offsets("t")
    got_c = [(r.offset, r.key, r.value) for r in client.iter_records("t", 0)]
    got_l = [(r.offset, r.key, r.value) for r in local.iter_records("t", 0)]
    assert got_c == got_l
    assert client.begin_offset("t", 0) == local.begin_offset("t", 0)
    local.close()


def test_remote_store_propagates_key_errors(remote):
    client, _, _ = remote
    with pytest.raises(KeyError):
        client.num_partitions("nope")
    with pytest.raises(KeyError):
        client.read("nope", 0, 0, 10)


def test_remote_append_fenced_by_server_epoch(tmp_path):
    store = PartitionedLog(tmp_path / "srv")
    fences = FenceTable()
    server = LogServer(store, fences=fences).start()
    stale = RemoteLogStore(server.address, tmp_path / "stale")
    fresh = RemoteLogStore(server.address, tmp_path / "fresh")
    try:
        stale.create_topic("t", partitions=1)
        stale.set_fence_epoch(1)
        fresh.set_fence_epoch(2)
        stale.append("t", b"k", b"before", partition=0)
        fences.advance("t", 0, 2)                  # takeover: epoch 2
        with pytest.raises(FencedError):
            stale.append("t", b"k", b"zombie", partition=0)
        fresh.append("t", b"k", b"after", partition=0)
        vals = [r.value for r in fresh.iter_records("t", 0)]
        assert vals == [b"before", b"after"]       # zombie write rejected
    finally:
        stale.close()
        fresh.close()
        server.stop()
        store.close()


def test_remote_store_reconnects_after_connection_drop(remote):
    client, _, _ = remote
    client.create_topic("t", partitions=1)
    client.append("t", b"", b"one", partition=0)
    # drop the transport under the client without telling it: the next call
    # fails mid-flight and must transparently reconnect and retry (the demux
    # reader may notice first and null out the session — keep our own ref)
    sock = client._sock
    sock.shutdown(socket.SHUT_RDWR)
    sock.close()
    client.append("t", b"", b"two", partition=0)
    assert [r.value for r in client.iter_records("t", 0)] == [b"one", b"two"]
    assert client.reconnects >= 1


@pytest.mark.slow
def test_consumer_poll_replay_deterministic_across_processes(tmp_path):
    """The same committed topic read through two RemoteLogStore clients —
    one in this process, one in a spawned child — yields byte-identical
    Consumer.poll sequences (replay determinism over the wire)."""
    ctx = mp.get_context("spawn")
    parent_conn, child_conn = ctx.Pipe()
    proc = ctx.Process(target=serve_store,
                       args=(str(tmp_path / "daemon"), child_conn),
                       daemon=True)
    proc.start()
    address = parent_conn.recv()
    client = RemoteLogStore(address, tmp_path / "c1")
    try:
        client.create_topic("t", partitions=2)
        records = [(f"k{i}".encode(), f"v{i}".encode()) for i in range(64)]
        client.append_batch("t", records[:32], partition=0)
        client.append_batch("t", records[32:], partition=1)
        client.flush_topic("t", fsync=False)

        def drain(log, gid: str) -> list:
            grp = ConsumerGroup(log, "t", gid)
            c = grp.add_member("m0")
            out = []
            while True:
                batch = c.poll(max_records=7)
                if not batch:
                    break
                out.extend((r.offset, r.key, r.value) for r in batch)
            return out

        here = drain(client, "replay-a")
        other = RemoteLogStore(address, tmp_path / "c2")
        try:
            assert drain(other, "replay-b") == here
        finally:
            other.close()
        assert len(here) == 64
    finally:
        client.close()
        parent_conn.send("stop")
        proc.join(timeout=10)
        if proc.is_alive():
            proc.kill()


def test_server_serves_concurrent_clients(remote, tmp_path):
    client, _, server = remote
    client.create_topic("t", partitions=4)
    errs: list[Exception] = []

    def work(i: int) -> None:
        c = RemoteLogStore(server.address, tmp_path / f"w{i}")
        try:
            for j in range(20):
                c.append("t", f"{i}".encode(), f"{i}:{j}".encode(),
                         partition=i)
        except Exception as e:   # noqa: BLE001
            errs.append(e)
        finally:
            c.close()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs
    assert client.end_offsets("t") == [20, 20, 20, 20]
