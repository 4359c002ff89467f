"""The stream->serve cells: topic ``requests`` -> ``ConsumerGroup`` member ->
``Server.serve_once`` -> topic ``completions``, with the open-loop
generator sending requests and a collector stamping completions.

Set-up makes the weights from the seed, builds the ``Server`` and serves
two warm-up batches through the same topics, which compiles every program
the window runs. Then the generator's schedule starts; after ``lead_s`` the
window opens at the next batch boundary and takes whole batches: those
begun before ``--seconds`` had passed, until the last has landed. Cells
judged by latency then drain: they serve on until every request due in
the window has landed, or ``drain_s`` has passed.

Correctness, once the window has closed: every request that should have
landed did, once, under its id; and a sample of the served requests,
drawn from the seed, is run through the plain reference, whose logits
must rank each served token within the limit of its best.
"""
from __future__ import annotations

import gc
import json
import time

import numpy as np

from . import harness, stats, workload
from .harness import Check, note

IDLE_S = 0.001              # the server loop's sleep when nothing was polled
WARM_BATCHES = 2


def run(run: harness.Run, children, counter, devs, fault) -> None:
    import jax
    from repro.core import ConsumerGroup
    from repro.core.transport import RemoteLogStore
    from repro.models import Model
    from repro.runtime import ServeConfig, Server
    from . import flops, trace as tr, weights

    t = run.cell.traffic
    B, P, new = t["batch"], t["prompt_len"], t["max_new"]
    store = RemoteLogStore(children.address, children.run_dir / "device")
    store.create_topic("requests", partitions=t["partitions"])
    store.create_topic("completions", partitions=t["partitions"])
    children.start_generator({"address": children.address, "kind": "serve",
                              "root": str(children.run_dir / "generator"),
                              "traffic": t, "seed": run.seed,
                              "seconds": run.seconds})
    cfg = run.cfg
    model = Model(cfg)
    consumer = ConsumerGroup(store, "requests", "servers").add_member("srv0")
    server = Server(model, weights.make(model.abstract_params(), run.seed),
                    consumer, store,
                    ServeConfig(batch_size=B, prompt_len=P, max_new_tokens=new))
    if fault is not None:
        fault(server)
    spans = harness.Spans(run)
    polled: dict[str, float] = {}
    batch_ids: list[list[str]] = []
    poll = consumer.poll

    def recorded_poll(*a, **k):
        recs = poll(*a, **k)
        now = time.monotonic()
        ids = [json.loads(r.value)["id"] for r in recs]
        for rid in ids:
            polled.setdefault(rid, now)
        batch_ids.append(ids)
        return recs

    consumer.poll = recorded_poll
    spans.wrap(consumer, "poll", "poll")
    spans.wrap(server, "_prefill", "prefill")
    spans.wrap(server, "_decode", "decode")
    spans.wrap(store, "append", "publish")

    def serve_step(batches):
        with spans.span("serve_once") as sp:
            n = server.serve_once()
        if n:
            batches.append((sp.t0, sp.t1, n, batch_ids[-1][:n]))
        else:
            time.sleep(IDLE_S)
        return n

    # set-up: warm-up batches through the topics, then the schedule starts
    for b in range(WARM_BATCHES):
        for i in range(B):
            rid = f"warm{b}-{i}"
            req = {"id": rid, "due": 0.0,
                   "prompt": workload.prompt(run.seed, b * B + i, P - 1, stream=6)}
            store.append("requests", rid.encode(), json.dumps(req).encode(),
                         partition=i % t["partitions"])
        while server.serve_once() == 0:
            pass
    jax.block_until_ready(server.params)
    if run.trace:
        jax.profiler.start_trace(str(children.run_dir / "trace"))
    go = time.monotonic() + 0.05
    children.go(go)
    reqs = workload.requests(t, run.seed, run.seconds)
    due = {r["id"]: go + r["due"] for r in reqs}
    lead_batches = []
    while time.monotonic() < go + t.get("lead_s", 0.0):
        serve_step(lead_batches)
    batches = []
    counter.armed = True
    run.w0 = time.monotonic()
    with spans.span(tr.WINDOW):
        while time.monotonic() < run.w0 + run.seconds:
            serve_step(batches)
    run.w1 = time.monotonic()
    counter.armed = False
    if run.trace:
        jax.profiler.stop_trace()
    run.setup_s = go - run.t_start
    run.compiles_in_window = counter.count
    in_window = [i for i, d in due.items() if run.w0 <= d < run.w0 + run.seconds]
    served_in_window = [i for b in batches for i in b[3]]
    if t["judged_by"] == "latency":
        want = set(in_window)
        drain_until = time.monotonic() + t["drain_s"]
        served = set(i for b in lead_batches + batches for i in b[3])
        drain = []
        while not want <= served and time.monotonic() < drain_until:
            if serve_step(drain):
                served.update(drain[-1][3])
        attempted = in_window
    else:
        attempted = served_in_window
    run.device = harness.device_info(devs, run.chips)
    gen = children.stop_generator(attempted, timeout=30.0)
    arrived = {k: v for k, v in gen["arrived"].items()}
    run.requests = {i: {"due": due[i], "polled": polled.get(i),
                        "arrived": min(arrived[i]) if i in arrived else None}
                    for i in attempted}
    run.attempted = len(attempted)
    run.failed = sum(r["arrived"] is None for r in run.requests.values())
    run.work = {"batches": len(batches), "served": len(served_in_window),
                "batch_s": [b[1] - b[0] for b in batches],
                "tokens": sum(new for i in served_in_window if i in arrived),
                "in_window": len(in_window)}
    sizes = {r["id"]: min(len(r["prompt"]) + 1, P) for r in reqs}
    run.window_flops = sum(flops.serve_batch_flops(cfg, [sizes[i] for i in b[3]], new)
                           for b in batches)
    run.busy_flops_s = sum(b[1] - b[0] for b in batches)
    late = [s - d for d, s in gen["sent"].values()]
    note(f"generator: {len(late)} requests sent, lateness p50 "
         f"{stats.percentile(late, 50)} s p95 {stats.percentile(late, 95)} s "
         f"max {max(late) if late else float('nan')} s")
    note(f"window: {len(batches)} batches, {len(served_in_window)} requests served, "
         f"{len(in_window)} due, in {run.w1 - run.w0} s; compiles in window "
         f"{run.compiles_in_window}; set-up {run.setup_s} s")
    if run.trace:
        harness.load_trace(run, str(children.run_dir / "trace"),
                           ("serve_once", "poll", "prefill", "decode", "publish"))

    landed = _completions(store)
    server.params = None
    del server
    gc.collect()
    store.close()
    _check(run, reqs, attempted, arrived, landed)


def _completions(store) -> dict[str, list[list[int]]]:
    out: dict[str, list[list[int]]] = {}
    for p in range(store.num_partitions("completions")):
        for r in store.iter_records("completions", p):
            doc = json.loads(r.value)
            out.setdefault(doc["id"], []).append(doc["completion_ids"])
    return out


def _prompt_tokens(text: str) -> list[int]:
    return [workload.BOS] + list(text.encode())


def _check(run, reqs, attempted, arrived, landed) -> None:
    from . import reference
    from .families import common as c
    t = run.cell.traffic
    by_id = {r["id"]: r for r in reqs}
    missing = sum(i not in landed for i in attempted)
    dup = sum(len(landed[i]) != 1 for i in landed)
    dup += sum(len(v) != 1 for k, v in arrived.items() if not k.startswith("warm"))
    done = [i for i in attempted if i in landed and len(landed[i]) == 1]
    g = workload.rng(run.seed, 9)
    k = min(t["check_requests"], len(done))
    longest = max(done, key=lambda i: (len(by_id[i]["prompt"]), i)) if done else None
    rest = [i for i in done if i != longest]
    pick = ([longest] if longest else []) + list(
        g.choice(rest, size=max(0, k - 1), replace=False)) if done else []
    gap = float("nan")
    if pick:
        prompts = [_prompt_tokens(by_id[i]["prompt"]) for i in pick]
        served = [landed[i][0] for i in pick]
        rows, at = reference.serve_rows(prompts, served, t["prompt_len"] + t["max_new"] - 1)
        from repro.models import Model
        from . import weights
        w = weights.make(Model(run.cfg).abstract_params(), run.seed)
        lg = np.asarray(reference.logits_fn(run.cfg, c.REFERENCE)(w, rows, at))
        del w
        gap = float(reference.token_gaps(lg, served).max())
    lim = run.cell.limits["numbers"]
    run.checks = [Check("completions_missing", float(missing), 0.0),
                  Check("completions_repeated", float(dup), 0.0),
                  Check("served_logit_gap", gap, lim["served_logit_gap"]["limit"])]
    note(f"reference: {len(pick)} requests, "
         f"{sum(len(landed[i][0]) for i in pick)} served tokens compared")
    for ch in run.checks:
        note(f"check {ch.name} = {ch.value} (limit {ch.limit}) "
             f"{'ok' if ch.ok else 'FAIL'}")
