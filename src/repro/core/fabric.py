"""Multi-process ingestion fabric: sharded acquisition workers over a
socket-transported log (paper §III — the "scalable" half of the claim).

Until this module the whole reproduction ran in one Python process, GIL-
bound near 4k rec/s. The fabric shards the case study across OS processes
the way the paper's systems shard across nodes:

  * the **coordinator** (this process) owns the durable ``LogStore`` and
    hosts it behind a :class:`~repro.core.transport.LogServer` — the Kafka
    *broker*. It also runs the *controller* half of Kafka's
    broker/controller split: a heartbeat failure detector plus lease-based
    assignment of **shard groups** to workers, with leader-epoch fencing
    (the PR 3 epoch machinery, now enforced at the storage boundary by the
    server's :class:`~repro.core.transport.FenceTable`);
  * each **worker** is an OS process (``multiprocessing`` spawn) holding a
    lease on one or more shard groups. A shard group is a vertical slice of
    the pipeline: a subset of ``AcquisitionRuntime`` connectors plus a
    *disjoint* subset of each landing topic's partitions (NiFi would run
    the same flow on every node of a cluster and divide the feed;
    AsterixDB's feeds job runs an intake/compute cascade per node group).
    Workers reach the log only through :class:`RemoteLogStore` — NiFi
    site-to-site, in Kafka terms the producer wire protocol.

Failure handling (paper: "robustness in handling node failures"): workers
heartbeat over the control channel; when one misses
``lease_timeout_sec`` the coordinator declares it dead, bumps the fence
epoch of every partition its groups own (so a paused-not-dead zombie's
in-flight appends are rejected at the server — *then* it is safe to move
the work), and reassigns the groups to surviving workers. The takeover
worker rebuilds each group's pipeline and resumes from the group's cursor
checkpoints (topic ``__acq__.<name>.<group>``) and durable ingress WAL —
the same crash-recovery contract the single-process runtime already
proved, now driven by a failure detector instead of a restart.

Guarantees across a worker ``kill -9`` (with ``durable`` ingress):

  * zero acked-record loss — acked = admitted past the ingress WAL, or
    covered by a cursor checkpoint (the endpoint redelivers the rest);
  * bounded duplicates — at-least-once redelivery + WAL replay, deduped
    per-shard like the single-process pipeline;
  * monotonic fabric-wide low watermark — per-connector watermarks are
    seeded from checkpoints on takeover and aggregated coordinator-side as
    per-connector maxima.

The control protocol is JSON frames over the same length-prefixed framing
as the data protocol (``OP_CTRL``): ``hello`` / ``assign`` / ``hb`` /
``group_done`` / ``group_failed`` / ``shutdown``.
"""
from __future__ import annotations

import importlib
import json
import multiprocessing as mp
import os
import socket
import threading
import time
from pathlib import Path
from typing import Callable, Sequence

from .logstore import LogStore
from .telemetry import (FlightRecorder, ScrapeServer, merge_histogram_states,
                        render_histogram_state_text, serve_scrape,
                        summarize_histogram_state, tracer)
from .transport import (FenceTable, LogServer, RemoteLogStore, recv_ctrl,
                        send_ctrl, TransportError)

__all__ = ["IngestionFabric", "LeaseTable", "FabricError", "resolve_factory"]


class FabricError(RuntimeError):
    pass


def resolve_factory(path: str) -> Callable:
    """Resolve ``"package.module:function"`` — how a worker process turns a
    JSON shard spec back into executable pipeline code."""
    mod_name, _, fn_name = path.partition(":")
    if not fn_name:
        raise ValueError(f"factory {path!r} is not 'module:function'")
    fn = getattr(importlib.import_module(mod_name), fn_name, None)
    if fn is None:
        raise ValueError(f"factory {path!r} not found")
    return fn


# ---------------------------------------------------------------------------
# lease bookkeeping (pure state machine — unit-testable without processes)
# ---------------------------------------------------------------------------

class LeaseTable:
    """Coordinator-side assignment state: which worker holds which shard
    group, under which epoch, and who is still heartbeating.

    Pure bookkeeping over an injected clock (``now`` parameters) so the
    election logic is testable without processes or sleeps. Thread-safe.

    The epoch is per-group and bumps on every reassignment; it is the fence
    token the coordinator pushes into the data server's
    :class:`~repro.core.transport.FenceTable` *before* the new assignment
    goes out, which is what makes a lease takeover safe against a zombie
    holder (Kafka's controller epoch / leader epoch pairing)."""

    def __init__(self, lease_timeout_sec: float) -> None:
        if lease_timeout_sec <= 0:
            raise ValueError("lease_timeout_sec must be positive")
        self.lease_timeout_sec = lease_timeout_sec
        self._lock = threading.Lock()
        self._beats: dict[str, float] = {}       # worker -> last heartbeat
        self._dead: set[str] = set()
        # group -> {"worker", "epoch", "state": assigned|done}
        self._groups: dict[str, dict] = {}

    # -- workers --
    def register_worker(self, worker: str, now: float) -> None:
        with self._lock:
            if worker in self._dead:
                raise FabricError(f"worker {worker!r} was declared dead")
            self._beats[worker] = now

    def heartbeat(self, worker: str, now: float) -> bool:
        """Record a beat. Returns False (beat ignored) for a worker already
        declared dead — a paused-not-dead zombie does not resurrect."""
        with self._lock:
            if worker in self._dead or worker not in self._beats:
                return False
            self._beats[worker] = now
            return True

    def expired_workers(self, now: float) -> list[str]:
        with self._lock:
            return [w for w, t in self._beats.items()
                    if w not in self._dead
                    and now - t > self.lease_timeout_sec]

    def alive_workers(self) -> list[str]:
        with self._lock:
            return sorted(w for w in self._beats if w not in self._dead)

    # -- groups --
    def assign_initial(self, groups: Sequence[str]) -> dict[str, str]:
        """Round-robin the groups over registered workers (first epoch 1).
        Returns {group: worker}."""
        with self._lock:
            workers = sorted(w for w in self._beats if w not in self._dead)
            if not workers:
                raise FabricError("no workers registered")
            out = {}
            for i, gid in enumerate(groups):
                w = workers[i % len(workers)]
                self._groups[gid] = {"worker": w, "epoch": 1,
                                     "state": "assigned"}
                out[gid] = w
            return out

    def declare_dead(self, worker: str) -> list[tuple[str, str, int]]:
        """Mark ``worker`` dead and reassign its unfinished groups to the
        least-loaded survivors. Returns ``[(group, new_worker, new_epoch)]``
        — the caller must fence each group's partitions at ``new_epoch``
        before delivering the new assignments."""
        with self._lock:
            if worker in self._dead:
                return []
            self._dead.add(worker)
            survivors = sorted(w for w in self._beats if w not in self._dead)
            if not survivors:
                raise FabricError(
                    f"worker {worker!r} died and no survivors remain")
            load = {w: 0 for w in survivors}
            for g in self._groups.values():
                if g["state"] != "done" and g["worker"] in load:
                    load[g["worker"]] += 1
            moved = []
            for gid, g in sorted(self._groups.items()):
                if g["worker"] == worker and g["state"] != "done":
                    new = min(survivors, key=lambda w: (load[w], w))
                    load[new] += 1
                    g["worker"] = new
                    g["epoch"] += 1
                    g["state"] = "assigned"
                    moved.append((gid, new, g["epoch"]))
            return moved

    def mark_done(self, gid: str, worker: str, epoch: int) -> bool:
        """Accept a completion report iff it carries the current lease
        (a fenced zombie finishing its local drain does not complete the
        group — its successor owns it now)."""
        with self._lock:
            g = self._groups.get(gid)
            if g is None or g["worker"] != worker or g["epoch"] != epoch:
                return False
            g["state"] = "done"
            return True

    def holder(self, gid: str) -> tuple[str, int]:
        with self._lock:
            g = self._groups[gid]
            return g["worker"], g["epoch"]

    def all_done(self) -> bool:
        with self._lock:
            return bool(self._groups) and all(
                g["state"] == "done" for g in self._groups.values())

    def snapshot(self) -> dict:
        with self._lock:
            return {"groups": {g: dict(v) for g, v in self._groups.items()},
                    "dead": sorted(self._dead),
                    "workers": sorted(self._beats)}


# ---------------------------------------------------------------------------
# coordinator
# ---------------------------------------------------------------------------

class IngestionFabric:
    """Coordinator for N worker processes sharding an acquisition pipeline.

    ``shards`` is a list of JSON-serializable shard-group specs::

        {"group": "g0",
         "factory": "repro.data.pipeline:build_fabric_news_worker",
         "kwargs": {...},                      # factory parameters
         "partitions": {"articles": [0, 2]}}   # topic -> owned partitions

    ``factory(log, spec)`` runs in the worker process and must return
    ``(flow, acquisition_runtime)`` for the group; ``spec`` is the dict
    above plus ``"epoch"``. The ``partitions`` map is the fence unit: on
    takeover the coordinator advances the data server's fence for exactly
    these partitions before re-assigning, so a zombie's appends to them are
    rejected. (Ingress-WAL topics are deliberately left unfenced: a
    zombie's WAL appends are durable records the takeover replays —
    bounded duplicates, never loss.)
    """

    def __init__(self, root: str | Path, store: LogStore, *,
                 shards: Sequence[dict], workers: int,
                 name: str = "fabric",
                 heartbeat_sec: float = 0.2,
                 lease_timeout_sec: float = 2.0,
                 group_timeout_sec: float = 300.0,
                 spawn_timeout_sec: float = 60.0,
                 clock: Callable[[], float] | None = None) -> None:
        if workers < 1:
            raise ValueError("workers must be >= 1")
        for spec in shards:
            for key in ("group", "factory", "partitions"):
                if key not in spec:
                    raise ValueError(f"shard spec missing {key!r}: {spec}")
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.store = store
        self.name = name
        self.shards = {s["group"]: s for s in shards}
        self.n_workers = workers
        self.heartbeat_sec = heartbeat_sec
        self.group_timeout_sec = group_timeout_sec
        self.spawn_timeout_sec = spawn_timeout_sec
        #: monotonic source for spawn deadlines, lease heartbeats, and the
        #: failure detector (injectable; LeaseTable stays pure over it)
        self._clock: Callable[[], float] = \
            clock if clock is not None else time.monotonic
        self.fences = FenceTable()
        self.leases = LeaseTable(lease_timeout_sec)
        self.data_server = LogServer(store, fences=self.fences)
        self._ctrl_sock = socket.create_server(("127.0.0.1", 0))
        self._ctrl_sock.settimeout(0.2)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._procs: dict[str, mp.process.BaseProcess] = {}
        self._conns: dict[str, socket.socket] = {}
        self._send_locks: dict[str, threading.Lock] = {}
        self._threads: list[threading.Thread] = []
        self._hello = threading.Semaphore(0)
        #: per-connector ("<group>/<name>") max watermark seen — maxima keep
        #: the aggregate monotonic across checkpoint-lagged takeovers
        self._wm: dict[str, float] = {}
        self._wm_known: set[str] = set()      # connectors that reported
        self._wm_finished: set[str] = set()
        self._groups_seen: set[str] = set()   # groups that reported once
        self._wm_history: list[float] = []
        self.reassignments: list[tuple[str, str, str, int]] = []
        self._group_errors: dict[str, str] = {}
        #: per (group, epoch) RemoteLogStore transport counters reported at
        #: group completion — status() aggregates them fabric-wide so the
        #: benches can track round trips per record
        self._transport: dict[str, dict] = {}
        #: ``wid -> {gid -> histogram state}``: each worker's latest
        #: heartbeat view of its ACTIVE groups (replaced wholesale per wid,
        #: so lost beats are harmless and a dead worker's last report keeps
        #: counting its in-flight work). Finished groups are evicted from
        #: the live view and move to ``_telemetry_final`` via their
        #: ``group_done`` report — groups routinely complete inside one
        #: heartbeat period, so the beats alone could miss an entire run.
        self._telemetry: dict[str, dict] = {}
        self._telemetry_final: dict[str, dict] = {}
        #: ring of recent status snapshots — dumped to flight-<wid>.json
        #: when the failure detector declares a worker dead
        self.flight = FlightRecorder(capacity=64)
        self._scrape: ScrapeServer | None = None
        self._all_done = threading.Event()
        self._started = False

    # -- lifecycle --
    def start(self) -> "IngestionFabric":
        """Spawn the workers, wait for every hello, push the initial
        assignments, and arm the failure detector. Returns once every
        worker is connected and every group is assigned — the moment to
        start a benchmark clock."""
        if self._started:
            raise FabricError("fabric already started")
        self._started = True
        self.data_server.start()
        t = threading.Thread(target=self._accept_loop,
                             name=f"{self.name}-accept", daemon=True)
        t.start()
        self._threads.append(t)
        ctx = mp.get_context("spawn")
        host, port = self._ctrl_sock.getsockname()[:2]
        for i in range(self.n_workers):
            wid = f"w{i}"
            p = ctx.Process(
                target=_worker_main,
                args=(wid, (host, port), self.data_server.address,
                      str(self.root / "workers" / wid), self.heartbeat_sec),
                name=f"{self.name}-{wid}", daemon=True)
            p.start()
            self._procs[wid] = p
        deadline = self._clock() + self.spawn_timeout_sec
        for _ in range(self.n_workers):
            if not self._hello.acquire(timeout=max(
                    0.0, deadline - self._clock())):
                self.shutdown(force=True)
                raise FabricError(
                    f"workers failed to connect within "
                    f"{self.spawn_timeout_sec}s")
        for gid, wid in self.leases.assign_initial(
                sorted(self.shards)).items():
            self._send_assign(gid, wid)
        mon = threading.Thread(target=self._monitor_loop,
                               name=f"{self.name}-monitor", daemon=True)
        mon.start()
        self._threads.append(mon)
        return self

    def wait(self, timeout: float | None = None) -> dict:
        """Block until every shard group reports done (under its current
        lease), then gracefully shut the workers down. Raises on group
        failure or timeout."""
        if not self._all_done.wait(
                timeout if timeout is not None else self.group_timeout_sec):
            snap = self.status()
            self.shutdown(force=True)
            raise FabricError(f"fabric did not complete: {snap['leases']}")
        with self._lock:
            errors = dict(self._group_errors)
        if errors:
            self.shutdown(force=True)
            raise FabricError(f"groups failed: {errors}")
        self.shutdown()
        return self.status()

    def kill_worker(self, wid: str) -> int:
        """``SIGKILL`` a worker process (the acceptance scenario's failure
        injection). Returns the killed pid."""
        p = self._procs[wid]
        if p.pid is None:
            raise FabricError(f"worker {wid} not started")
        os.kill(p.pid, 9)
        p.join(timeout=10.0)
        return p.pid

    def shutdown(self, force: bool = False) -> None:
        self._stop.set()
        with self._lock:
            conns = dict(self._conns)
        for wid, conn in conns.items():
            try:
                with self._send_locks[wid]:
                    send_ctrl(conn, {"t": "shutdown"})
            except (OSError, TransportError, ValueError):
                pass
        for p in self._procs.values():
            p.join(timeout=5.0)
            if p.is_alive():
                if force:
                    p.terminate()
                    p.join(timeout=5.0)
                if p.is_alive():
                    p.kill()
                    p.join(timeout=5.0)
        for conn in conns.values():
            try:
                conn.close()
            except OSError:
                pass
        try:
            self._ctrl_sock.close()
        except OSError:
            pass
        if self._scrape is not None:
            self._scrape.close()
        self.data_server.stop()

    # -- observability --
    def status(self) -> dict:
        with self._lock:
            wm_hist = list(self._wm_history)
            errors = dict(self._group_errors)
            transports = [dict(t) for t in self._transport.values()]
        transport: dict[str, int] = {}
        for t in transports:
            for k, v in t.items():
                if isinstance(v, (int, float)):
                    transport[k] = transport.get(k, 0) + v
        return {
            "leases": self.leases.snapshot(),
            "reassignments": list(self.reassignments),
            "low_watermark": wm_hist[-1] if wm_hist else None,
            "watermark_history": wm_hist,
            "group_errors": errors,
            "transport": transport,
            "telemetry": summarize_histogram_state(self.telemetry_state()),
        }

    def telemetry_state(self) -> dict:
        """Raw fabric-wide histogram state, merged bucket-wise: every
        finished group's exact final report plus each worker's latest
        heartbeat view of its still-active groups. A dead worker's last
        beat keeps counting the work it did before dying — replayed
        records are then *observed* twice (once per attempt), which is the
        honest reading for latency telemetry."""
        with self._lock:
            reports = [dict(t) for t in self._telemetry_final.values()]
            reports += [dict(t) for by_gid in self._telemetry.values()
                        for t in by_gid.values()]
        merged: dict = {}
        for state in reports:
            merge_histogram_states(merged, state)
        return merged

    def render_metrics_text(self) -> str:
        """Prometheus-style text exposition of the merged fabric
        telemetry plus a few coordinator gauges, followed by this process's
        spans and counters (``telemetry.tracer()``: a training loader
        attached here shows its ``loader/*`` spans and ``loader_*``
        counters beside the fabric's)."""
        status = self.status()
        lines = [render_histogram_state_text(self.telemetry_state())]
        lw = status["low_watermark"]
        if lw is not None:
            lines.append(f"repro_fabric_low_watermark {lw}")
        lines.append(
            f"repro_fabric_reassignments {len(status['reassignments'])}")
        lines.append(
            f"repro_fabric_group_errors {len(status['group_errors'])}")
        for k, v in sorted(status["transport"].items()):
            lines.append(f'repro_fabric_transport{{counter="{k}"}} {v}')
        lines.append(tracer().registry.render_text())
        return "\n".join(ln for ln in lines if ln) + "\n"

    def serve_metrics(self, port: int = 0,
                      host: str = "127.0.0.1") -> ScrapeServer:
        """Start (or return the already-running) HTTP scrape endpoint
        serving :meth:`render_metrics_text` at ``GET /metrics``."""
        if self._scrape is None:
            self._scrape = serve_scrape(
                self.render_metrics_text, port=port, host=host)
        return self._scrape

    def low_watermark_history(self) -> list[float]:
        with self._lock:
            return list(self._wm_history)

    # -- control plane --
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._ctrl_sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            t = threading.Thread(target=self._serve_worker, args=(conn,),
                                 daemon=True)
            t.start()

    def _serve_worker(self, conn: socket.socket) -> None:
        conn.settimeout(30.0)
        try:
            msg = recv_ctrl(conn)
        except (TransportError, OSError, ValueError):
            conn.close()
            return
        if msg.get("t") != "hello":
            conn.close()
            return
        wid = msg["worker"]
        now = self._clock()
        with self._lock:
            self._conns[wid] = conn
            self._send_locks[wid] = threading.Lock()
        self.leases.register_worker(wid, now)
        self._hello.release()
        while not self._stop.is_set():
            try:
                msg = recv_ctrl(conn)
            except socket.timeout:
                continue
            except (TransportError, OSError, ValueError):
                return          # EOF: the monitor declares death by lease
            kind = msg.get("t")
            if kind == "hb":
                self.leases.heartbeat(wid, self._clock())
                self._ingest_watermarks(msg)
                tel = msg.get("telemetry")
                if tel is not None:
                    with self._lock:
                        self._telemetry[wid] = tel
            elif kind == "group_done":
                if msg.get("transport"):
                    with self._lock:
                        self._transport[f"{msg['group']}@e{msg['epoch']}"] = \
                            msg["transport"]
                if self.leases.mark_done(msg["group"], wid, msg["epoch"]):
                    with self._lock:
                        if msg.get("telemetry"):
                            self._telemetry_final[
                                f"{msg['group']}@e{msg['epoch']}"] = \
                                msg["telemetry"]
                        # evict the group from every live heartbeat view:
                        # its exact final state supersedes the lagging beat
                        for t in self._telemetry.values():
                            t.pop(msg["group"], None)
                    for conn_name in msg.get("finished", []):
                        with self._lock:
                            self._wm_finished.add(
                                f"{msg['group']}/{conn_name}")
                    if self.leases.all_done():
                        self._all_done.set()
            elif kind == "group_failed":
                # a *fenced* failure on a stale lease is expected zombie
                # noise; anything else is a real error that fails the run
                holder, epoch = self.leases.holder(msg["group"])
                if not (msg.get("fenced") and
                        (holder != wid or epoch != msg["epoch"])):
                    with self._lock:
                        self._group_errors[msg["group"]] = msg.get(
                            "error", "unknown")
                    self._all_done.set()

    def _ingest_watermarks(self, msg: dict) -> None:
        with self._lock:
            for gid, conns in (msg.get("groups") or {}).items():
                self._groups_seen.add(gid)
                for cname, info in conns.items():
                    key = f"{gid}/{cname}"
                    self._wm_known.add(key)
                    wm = info.get("watermark")
                    if wm is not None and wm > self._wm.get(key, float("-inf")):
                        self._wm[key] = wm
                    if info.get("state") in ("COMPLETED", "STOPPED"):
                        self._wm_finished.add(key)
            if self._groups_seen != set(self.shards):
                return          # startup: min over a partial fleet is junk
            # fabric-wide low watermark: min over unfinished connectors'
            # maxima — monotone because maxima only rise and the active
            # set only shrinks (takeovers reuse the same group/conn keys)
            active = self._wm_known - self._wm_finished
            if active and all(k in self._wm for k in active):
                low = min(self._wm[k] for k in active)
                if not self._wm_history or low > self._wm_history[-1]:
                    self._wm_history.append(low)

    def _send_assign(self, gid: str, wid: str) -> None:
        _, epoch = self.leases.holder(gid)
        spec = dict(self.shards[gid])
        spec["epoch"] = epoch
        with self._lock:
            conn = self._conns.get(wid)
            lock = self._send_locks.get(wid)
        if conn is None or lock is None:
            raise FabricError(f"no control connection to worker {wid!r}")
        with lock:
            send_ctrl(conn, {"t": "assign", "spec": spec})

    def _monitor_loop(self) -> None:
        """The failure detector: poll heartbeat freshness, fence + reassign
        on expiry (fence FIRST — the zombie must be locked out of the
        storage layer before its groups move)."""
        interval = max(0.05, self.heartbeat_sec / 2)
        while not self._stop.is_set():
            time.sleep(interval)
            self.flight.record(self.status())
            for wid in self.leases.expired_workers(self._clock()):
                try:
                    moved = self.leases.declare_dead(wid)
                except FabricError as e:
                    with self._lock:
                        self._group_errors["<fabric>"] = str(e)
                    self._all_done.set()
                    return
                try:
                    self.flight.dump(self.root / f"flight-{wid}.json")
                except OSError:
                    pass
                for gid, new_wid, epoch in moved:
                    for topic, parts in self.shards[gid]["partitions"].items():
                        for p in parts:
                            self.fences.advance(topic, p, epoch)
                    try:
                        self._send_assign(gid, new_wid)
                    except (OSError, TransportError, FabricError) as e:
                        with self._lock:
                            self._group_errors[gid] = (
                                f"reassign to {new_wid} failed: {e}")
                        self._all_done.set()
                        return
                    self.reassignments.append((gid, wid, new_wid, epoch))


# ---------------------------------------------------------------------------
# worker process
# ---------------------------------------------------------------------------

def _is_fenced(exc: BaseException) -> bool:
    """True when ``exc`` (or anything in its cause/context chain) is a fence
    rejection — the expected way a zombie's shard group dies."""
    from .transport import FencedError
    seen: set[int] = set()
    cur: BaseException | None = exc
    while cur is not None and id(cur) not in seen:
        seen.add(id(cur))
        if isinstance(cur, FencedError) or "stale epoch" in str(cur):
            return True
        cur = cur.__cause__ or cur.__context__
    return False


def _worker_main(worker_id: str, control_addr: tuple[str, int],
                 data_addr: tuple[str, int], scratch: str,
                 heartbeat_sec: float) -> None:
    """Worker entry point (``multiprocessing`` spawn target).

    Connects the control channel, heartbeats, and runs one thread per
    assigned shard group: build the group's pipeline against a
    :class:`RemoteLogStore` fenced at the lease epoch, drive it to
    completion, report back. A group that fails with a fence rejection
    reports ``fenced`` — the coordinator ignores it when the lease has
    already moved on."""
    ctrl = socket.create_connection(control_addr, timeout=10.0)
    ctrl.settimeout(1.0)
    send_lock = threading.Lock()

    def send(msg: dict) -> None:
        try:
            with send_lock:
                send_ctrl(ctrl, msg)
        except (OSError, TransportError, ValueError):
            pass                   # coordinator gone: we exit on recv EOF

    send({"t": "hello", "worker": worker_id})
    stop = threading.Event()
    groups: dict[str, dict] = {}   # gid -> {"runtime", "flow", "log", ...}
    groups_lock = threading.Lock()

    def _group_telemetry(flow, log) -> dict:
        tel: dict = {}
        if flow.telemetry is not None:
            merge_histogram_states(tel, flow.telemetry.histograms_state())
        merge_histogram_states(tel, log.rpc_histograms_state())
        return tel

    def run_group(spec: dict) -> None:
        gid, epoch = spec["group"], spec["epoch"]
        log = RemoteLogStore(
            data_addr, Path(scratch) / gid / f"epoch-{epoch}",
            op_timeout=60.0)
        log.set_fence_epoch(epoch)
        try:
            flow, rt = resolve_factory(spec["factory"])(log, spec)
            with groups_lock:
                groups[gid] = {"runtime": rt, "flow": flow, "log": log,
                               "epoch": epoch}
            rt.run_with_flow(timeout=spec.get("timeout_sec", 300.0))
            status = rt.status()["connectors"]
            # final histogram state rides the completion report: groups
            # routinely finish inside one heartbeat period, so the beat
            # alone could miss the run entirely
            try:
                tel = _group_telemetry(flow, log)
            except Exception:   # noqa: BLE001 — best-effort telemetry
                tel = {}
            send({"t": "group_done", "group": gid, "epoch": epoch,
                  "finished": [n for n, s in status.items()
                               if s.get("state") in ("COMPLETED",
                                                     "STOPPED")],
                  "transport": log.transport_stats(),
                  "telemetry": tel})
        except Exception as e:   # noqa: BLE001 — report, don't kill worker
            send({"t": "group_failed", "group": gid, "epoch": epoch,
                  "fenced": _is_fenced(e),
                  "error": f"{type(e).__name__}: {e}"})
        finally:
            with groups_lock:
                groups.pop(gid, None)
            try:
                log.close()
            except Exception:   # noqa: BLE001
                pass

    def heartbeat_loop() -> None:
        while not stop.is_set():
            payload: dict = {}
            tel: dict = {}
            with groups_lock:
                active = {g: dict(v) for g, v in groups.items()}
            for gid, v in active.items():
                rt = v["runtime"]
                try:
                    conns = rt.status()["connectors"]
                except Exception:   # noqa: BLE001 — racing teardown
                    continue
                payload[gid] = {
                    n: {"watermark": s.get("watermark"),
                        "state": s.get("state")}
                    for n, s in conns.items()}
                try:
                    tel[gid] = _group_telemetry(v["flow"], v["log"])
                except Exception:   # noqa: BLE001 — racing teardown
                    pass
            # telemetry is keyed per group and always present (even empty):
            # the live view covers ACTIVE groups only — once a group's
            # exact final state ships via group_done, the coordinator
            # evicts its live entry so the two never double-count
            send({"t": "hb", "worker": worker_id, "groups": payload,
                  "telemetry": tel})
            stop.wait(heartbeat_sec)

    hb = threading.Thread(target=heartbeat_loop, daemon=True)
    hb.start()
    while True:
        try:
            msg = recv_ctrl(ctrl)
        except socket.timeout:
            continue
        except (TransportError, OSError, ValueError):
            break                  # coordinator gone
        kind = msg.get("t")
        if kind == "assign":
            threading.Thread(target=run_group, args=(msg["spec"],),
                             daemon=True).start()
        elif kind == "shutdown":
            break
    stop.set()
    hb.join(timeout=2.0)
    try:
        ctrl.close()
    except OSError:
        pass
