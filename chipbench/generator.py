"""The open-loop generator: a process of its own that never imports JAX.

It writes to the broker the way a remote producer does, through
``RemoteLogStore`` and ``delivery.Producer``, on a schedule drawn from the
seed that does not slow when the system slows, and stamps each record
with its due time. For serving, a collector thread polls ``completions``
and stamps each arrival.

Protocol on the pipe (parent -> generator): ``{"go": t}`` starts the
schedule at monotonic time ``t``; ``{"stop": ids, "timeout": s}`` stops
sending, waits until the collector has seen ``ids`` (or ``s`` seconds),
and answers with what was sent and what arrived.
"""
from __future__ import annotations

import json
import threading
import time
from pathlib import Path

from . import workload

COLLECT_IDLE_S = 0.005      # collector's sleep when completions are idle


def _collector(store, stop: threading.Event, arrived: dict, lock) -> None:
    from repro.core import ConsumerGroup
    consumer = ConsumerGroup(store, "completions", "collector").add_member("c0")
    while not stop.is_set():
        recs = consumer.poll(512)
        now = time.monotonic()
        if not recs:
            time.sleep(COLLECT_IDLE_S)
            continue
        with lock:
            for r in recs:
                rid = json.loads(r.value)["id"]
                arrived.setdefault(rid, []).append(now)


def _send_loop(conn, schedule, send, go: float, sent: dict) -> dict | None:
    """Send each item at its due time; returns the stop message."""
    for rid, due, payload in schedule:
        at = go + due
        wait = at - time.monotonic()
        if wait > 0 and conn.poll(wait):
            return conn.recv()
        send(payload)
        sent[rid] = (at, time.monotonic())
        if conn.poll():
            return conn.recv()
    return conn.recv()


def main(spec: dict, conn) -> None:
    """Process target. ``spec``: address, root, kind, traffic, seed,
    seconds."""
    from repro.core import make_flowfile
    from repro.core.delivery import Producer
    from repro.core.transport import RemoteLogStore

    root = Path(spec["root"])
    traffic, seed = spec["traffic"], spec["seed"]
    store = RemoteLogStore(tuple(spec["address"]), root / "producer")
    arrived: dict[str, list[float]] = {}
    sent: dict[str, tuple[float, float]] = {}
    lock = threading.Lock()
    stop = threading.Event()
    threads = []
    if spec["kind"] == "train":
        parts = traffic["partitions"]
        prod = Producer(store, traffic["topic"], linger_sec=0.0)

        def send(item):
            i, text = item
            rec = make_flowfile(text, text=text).to_record()
            prod.send(rec[0], rec[1], partition=i % parts)

        schedule = ((str(i), due, (i, text))
                    for i, due, text in workload.doc_schedule(traffic, seed))
    else:
        prod = Producer(store, "requests", linger_sec=0.0)
        reqs = workload.requests(traffic, seed, spec["seconds"])
        parts = traffic["partitions"]
        order = {r["id"]: k for k, r in enumerate(reqs)}

        def send(r):
            prod.send(r["id"].encode(), json.dumps(r).encode(),
                      partition=order[r["id"]] % parts)

        schedule = ((r["id"], r["due"], r) for r in reqs)
        cstore = RemoteLogStore(tuple(spec["address"]), root / "collector",
                                end_cache_ttl_sec=0.0)
        t = threading.Thread(target=_collector, name="collector",
                             args=(cstore, stop, arrived, lock), daemon=True)
        threads.append(t)
    conn.send("ready")
    msg = conn.recv()
    go = msg["go"]
    for t in threads:
        t.start()
    try:
        msg = _send_loop(conn, schedule, send, go, sent)
        want = set(msg.get("stop") or ())
        deadline = time.monotonic() + msg.get("timeout", 0.0)
        while threads and time.monotonic() < deadline:
            with lock:
                if want <= arrived.keys():
                    break
            time.sleep(COLLECT_IDLE_S)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        with lock:
            got = {k: list(v) for k, v in arrived.items()}
        conn.send({"go": go, "sent": sent, "arrived": got})
        store.close()
        if threads:
            cstore.close()
