"""Figure 11: optimization breakdown, Myria->Giraph analog
(colstore -> graphstore).

Rungs: file baseline -> IORedirect only (text) -> +binary primitives
(parts) -> +delimiter removal (binary_rows) -> full PipeGen (arrowcol,
column pivot).  A manually-optimized pipe (hand-written socket transfer of
the typed columns, no PipeGen machinery) bounds what generation could hope
to reach.

Beyond the ladder, the stream-fabric rungs: a streams sweep (one pipe
striped across N member connections; ``run.py --transport/--streams``
override the swept sets) measured both raw and under a per-link
bandwidth cap (the multi-NIC scenario striping exists for), and an
N=2→M=3 hash-partitioned shuffle probe."""

from __future__ import annotations

import pickle
import socket
import threading
import time

from repro.core import LinkSim, PipeConfig, transfer
from repro.core.directory import WorkerDirectory, set_directory
from repro.engines import make_engine, make_paper_block

from .common import (
    DEFAULT_ROWS,
    REPEATS,
    emit,
    file_transfer,
    fresh,
    pipe_transfer,
    timed,
)

RUNGS = [
    ("ioredirect", PipeConfig(mode="text")),
    ("binary", PipeConfig(mode="parts")),
    ("delim_removed", PipeConfig(mode="binary_rows")),
    # full PipeGen: typed block export, pooled zero-copy scatter-gather
    # encode, vectored send, double-buffered pipelined sender
    ("pipegen_full", PipeConfig(mode="arrowcol")),
    # same data plane over the in-process channel (one materialization at
    # the queue boundary) and over the shared-memory ring (in-place spans,
    # zero intermediate copies, works across OS processes)
    ("pipegen_channel", PipeConfig(mode="arrowcol", transport="channel")),
    ("pipegen_shm", PipeConfig(mode="arrowcol", transport="shm")),
]


def _manual_pipe(n_rows: int) -> float:
    """Hand-optimized: typed columns pickled straight over a socket."""
    src = make_engine("colstore")
    dst = make_engine("graphstore")
    src.put_block("t", make_paper_block(n_rows, seed=1))

    def run():
        lsock = socket.socket()
        lsock.bind(("127.0.0.1", 0))
        lsock.listen(1)
        port = lsock.getsockname()[1]

        def serve():
            conn, _ = lsock.accept()
            blk = src.get_block("t")
            payload = pickle.dumps((blk.schema.to_dict(),
                                    [list(map(float, c)) if not hasattr(c, "dtype")
                                     else c for c in blk.columns]))
            conn.sendall(len(payload).to_bytes(8, "little") + payload)
            conn.close()

        t = threading.Thread(target=serve)
        t.start()
        s = socket.create_connection(("127.0.0.1", port))
        ln = int.from_bytes(_recv_exact(s, 8), "little")
        schema_doc, cols = pickle.loads(_recv_exact(s, ln))
        s.close()
        t.join()
        from repro.core.types import ColumnBlock, Schema

        dst.put_block("t2", ColumnBlock(Schema.from_dict(schema_doc), cols))

    return timed(run)


def _recv_exact(sock, n):
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise IOError("eof")
        buf += chunk
    return buf


#: streams-sweep defaults (overridable via ``run.py --transport/--streams``)
SWEEP_TRANSPORTS = ("socket",)
SWEEP_STREAMS = (1, 4)
#: per-link bandwidth cap for the link-limited sweep: striping across N
#: members models N NICs, so the capped rung shows the N-fold pipe (tight
#: enough to bind even at --quick row counts)
_SWEEP_LINK_BPS = 100e6
_SWEEP_BLOCK_ROWS = 2048  # many frames even at --quick row counts


def _streams_sweep(n_rows: int, transports, streams_list) -> dict:
    out = {}
    for t in transports:
        for s in streams_list:
            cfg = PipeConfig(mode="arrowcol", transport=t, streams=s,
                             block_rows=_SWEEP_BLOCK_ROWS,
                             shm_capacity=1 << 22)
            sec = pipe_transfer("colstore", "graphstore", n_rows, cfg)
            out[(t, s)] = sec
            emit(f"fig11.streams_{t}_x{s}", sec)
        base = out.get((t, 1))
        best = min(s for s in streams_list)
        top = max(s for s in streams_list)
        if base and (t, top) in out and top != best:
            emit(f"fig11.streams{top}_vs_streams1_{t}",
                 base - out[(t, top)],
                 f"speedup={base / out[(t, top)]:.2f}x")
    # link-limited: same sweep under a per-connection bandwidth cap — the
    # multi-NIC case where striping buys aggregate bandwidth outright
    for s in sorted({min(streams_list), max(streams_list)}):
        cfg = PipeConfig(mode="arrowcol", streams=s,
                         block_rows=_SWEEP_BLOCK_ROWS,
                         link=LinkSim(bandwidth_bps=_SWEEP_LINK_BPS,
                                      min_sleep_s=0.0005))
        sec = pipe_transfer("colstore", "graphstore", n_rows, cfg)
        out[("link", s)] = sec
        emit(f"fig11.streams_link_x{s}", sec)
    lo, hi = min(streams_list), max(streams_list)
    if lo != hi:
        emit("fig11.streams_link_speedup",
             out[("link", lo)] - out[("link", hi)],
             f"speedup={out[('link', lo)] / out[('link', hi)]:.2f}x")
    return out


def _broadcast_probe(n_rows: int) -> dict:
    """Plan fan-out A→{B,C,D} over shm: three independent SPSC edges
    (three encodes of the same relation) vs the planner's broadcast group
    (ONE encode into a ring with three reader cursors)."""
    from repro.core import plan

    def run(use_broadcast: bool) -> None:
        fresh()
        src = make_engine("colstore")
        dsts = [make_engine("colstore") for _ in range(3)]
        src.put_block("t", make_paper_block(n_rows, seed=1))
        p = plan(negotiate=False)
        for i, d in enumerate(dsts):
            # 2 MiB rings: broadcast segments are single-use (never
            # pooled), so an oversized capacity taxes every run with
            # ~3 ms/MiB of first-touch faults the pooled SPSC side
            # never pays
            p.move(src, "t", d, "t2", transport="shm",
                   broadcast=use_broadcast,
                   config=PipeConfig(mode="arrowcol",
                                     block_rows=_SWEEP_BLOCK_ROWS,
                                     shm_capacity=1 << 21))
        p.compile().execute()
        assert all(len(d.get_block("t2")) == n_rows for d in dsts)

    def sample(use_broadcast: bool) -> float:
        t0 = time.perf_counter()
        run(use_broadcast)
        return time.perf_counter() - t0

    run(False)  # warm the adapters, ring pool, and engine code paths
    run(True)
    # interleaved best-of-8 pairs: these are *throughput* samples, where
    # scheduling noise is strictly additive, so min() is the honest
    # noise-robust estimator (the timeit convention — unlike the
    # latency-tail probe above, where min() would hide exactly the tail
    # being measured); pairing makes box-state drift hit both equally
    samples: dict = {False: [], True: []}
    for _ in range(8):
        for use_broadcast in (False, True):
            samples[use_broadcast].append(sample(use_broadcast))
    out = {
        "spsc_fanout_1x3": min(samples[False]),
        "broadcast_1x3": min(samples[True]),
    }
    emit("fig11.spsc_fanout_1x3", out["spsc_fanout_1x3"])
    emit("fig11.broadcast_1x3", out["broadcast_1x3"],
         f"speedup_vs_3xspsc="
         f"{out['spsc_fanout_1x3'] / out['broadcast_1x3']:.2f}x")
    return out


def _recovery_probe(n_rows: int) -> dict:
    """Mid-stream failure recovery: kill the importer after ~85% of the
    data frames crossed a bandwidth-capped socket edge, retry once, and
    compare a *resumed* retry (exporter restarts at the acked watermark)
    against a full re-run (``resume=False``).  The capped link makes
    elapsed time track bytes moved, so the resume win is the re-send
    bound made visible: ~1.15x of one clean pass vs ~1.85x."""
    from repro.core import faults
    from repro.core.plan import plan

    n_blocks = 16
    block_rows = max(64, n_rows // n_blocks)  # always >= n_blocks frames
    # recv #15 = schema + RESUME hello + 13 data frames on the resumed
    # leg (schema + 14 data frames on the rerun leg): ~85% either way
    kill_at = 15
    link = LinkSim(bandwidth_bps=_SWEEP_LINK_BPS, min_sleep_s=0.0005)

    def run(resume: bool) -> float:
        fresh()
        src = make_engine("colstore")
        dst = make_engine("colstore")
        src.put_block("t", make_paper_block(n_rows, seed=1))
        fp = faults.FaultPlan(42).kill("transport.recv", at=kill_at,
                                       count=1)
        t0 = time.perf_counter()
        with faults.use(fp):
            res = (plan(negotiate=False)
                   .move(src, "t", dst, "t2",
                         config=PipeConfig(mode="arrowcol",
                                           block_rows=block_rows,
                                           link=link),
                         timeout=300)
                   .options(retries=1, backoff=0.001, failover=False,
                            resume=resume)
                   .compile()
                   .execute(raise_on_error=False))
        sec = time.perf_counter() - t0
        assert not res.exceptions and len(dst.get_block("t2")) == n_rows
        assert len(res.single().attempts) == 2
        return sec

    out = {"recovery_resume": float("inf"), "recovery_rerun": float("inf")}
    for _ in range(REPEATS):  # interleaved best-of-N pairs
        out["recovery_rerun"] = min(out["recovery_rerun"], run(False))
        out["recovery_resume"] = min(out["recovery_resume"], run(True))
    emit("fig11.recovery_midstream", out["recovery_resume"],
         f"resume_vs_rerun="
         f"{out['recovery_rerun'] / out['recovery_resume']:.2f}x")
    return out


def _broker_probe(n_rows: int) -> dict:
    """Broker stress rung: 200 concurrent small plans through one
    resident :class:`~repro.core.broker.PipeBroker` (shared directory,
    one doorbell-hub thread, admission capped at 16 rings) vs the
    per-transfer-directory sequential baseline (a fresh
    ``WorkerDirectory`` per plan — the pre-broker lifecycle).  The
    per-plan latency is the figure; the note carries the speedup and
    the peak process fd count, which stays bounded because parked
    idle rings share the hub instead of each holding a poller."""
    from repro.core.broker import PipeBroker, process_fd_count
    from repro.core.plan import plan

    rows = 256
    cfg = PipeConfig(mode="arrowcol", block_rows=64, transport="shm")

    def one_plan(i: int) -> None:
        src = make_engine("colstore")
        dst = make_engine("colstore")
        src.put_block("t", make_paper_block(rows, seed=i))
        res = (plan(negotiate=False)
               .move(src, "t", dst, "t2", config=cfg,
                     dataset=f"bk{i}", timeout=120)
               .compile()
               .execute(raise_on_error=False))
        assert not res.exceptions, res.exceptions
        assert len(dst.get_block("t2")) == rows

    # baseline: one directory per transfer, strictly sequential.  One
    # untimed plan first so the adapter cache is warm on both legs —
    # otherwise the baseline eats the one-off codegen cost and the
    # broker leg looks faster than it is.
    fresh()
    one_plan(0)
    n_base = 20
    t0 = time.perf_counter()
    for i in range(n_base):
        fresh()
        one_plan(i)
    base_per = (time.perf_counter() - t0) / n_base

    # broker leg: one control plane, 200 plans racing through admission
    n_plans = 200
    broker = PipeBroker(max_rings=16, admit_timeout=120.0)
    broker.install()
    errors: list = []
    fd_base = process_fd_count()
    peak = [fd_base]
    stop_sampling = threading.Event()

    def sample():
        while not stop_sampling.wait(0.002):
            peak[0] = max(peak[0], process_fd_count())

    sampler = threading.Thread(target=sample, daemon=True)
    sampler.start()
    try:
        threads = [threading.Thread(target=lambda i=i: one_plan(i), daemon=True)
                   for i in range(n_plans)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=180)
            if th.is_alive():
                errors.append(f"{th.name} still running")
        wall = time.perf_counter() - t0
        st = broker.stats()
    finally:
        stop_sampling.set()
        sampler.join(timeout=2)
        broker.stop()
        fresh()
    assert not errors, errors
    broker_per = wall / n_plans
    emit("fig11.broker_seq_baseline", base_per,
         f"n={n_base} sequential, fresh directory per plan")
    emit("fig11.broker_stress", broker_per,
         f"n={n_plans} concurrent, vs_sequential={base_per / broker_per:.2f}x"
         f" per-plan, admitted={st['admitted']}, queued={st['queued']},"
         f" peak_fds={peak[0]} (base={fd_base})")
    return {"broker_seq_baseline": base_per, "broker_stress": broker_per,
            "peak_fds": peak[0]}


def _serve_failover_broker(port: int, journal: str, recover: bool) -> None:
    """Spawn-child body for the failover rung: a served broker that
    journals to disk and lives until SIGKILLed."""
    from repro.core.broker import PipeBroker

    b = PipeBroker(serve=True, host="127.0.0.1", port=port, hub=False,
                   journal_path=journal, max_rings=16, lease_ttl=10.0,
                   sweep_every=1.0, admit_timeout=120.0)
    b.start(recover=recover)
    while True:
        time.sleep(3600.0)


def _failover_probe(n_rows: int) -> dict:
    """Broker failover rung: the same 200-plan stress as the broker
    rung, but through a SERVED broker (its own OS process, journal on
    disk) that is SIGKILLed mid-run and restarted from the journal —
    measured against the identical stress left uninterrupted.  The
    figure is the interrupted wall clock; the gate is the ratio: one
    kill+recover may cost at most 1.5x the uninterrupted run, i.e. the
    client ladder (bounded retry -> degraded rendezvous -> re-attach)
    must keep the fleet draining while the control plane is down."""
    import multiprocessing
    import os
    import shutil
    import signal
    import tempfile

    from repro.core.broker import BrokerClient
    from repro.core.plan import plan

    mp = multiprocessing.get_context("spawn")
    rows = 32
    n_plans = 200
    # connect_timeout bounds how long an attempt wedged at rendezvous
    # (its exporter died with the broker) can hold the retry hostage —
    # the knob IS part of the recovery story, so the rung pins it tight
    cfg = PipeConfig(mode="arrowcol", block_rows=32, transport="shm",
                     shm_capacity=1 << 16, connect_timeout=1.0)

    def wait_port(port: int, timeout: float = 15.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port),
                                         timeout=1.0).close()
                return
            except OSError:
                time.sleep(0.05)
        raise TimeoutError(f"broker child never listened on {port}")

    dead = [0.0]  # measured broker-down window of the killed run

    def run(kill: bool) -> float:
        fresh()
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        tmp = tempfile.mkdtemp(prefix="pipegen-failover-")
        journal = os.path.join(tmp, "broker.journal")
        child = mp.Process(target=_serve_failover_broker,
                           args=(port, journal, False), daemon=True)
        child.start()
        wait_port(port)
        client = BrokerClient("127.0.0.1", port, admit_timeout=120.0)
        client.directory.probe_every = 0.2
        client.install()
        child2 = None
        src = make_engine("colstore")
        dst = make_engine("colstore")
        for i in range(n_plans):
            src.put_block(f"t{i}", make_paper_block(rows, seed=i))
        errors: list = []

        def one(i: int) -> None:
            try:
                res = (plan(negotiate=False)
                       .move(src, f"t{i}", dst, f"d{i}", config=cfg,
                             dataset=f"fo{i}", timeout=10)
                       .options(retries=3, backoff=0.1)
                       .compile()
                       .execute())
                assert res.ok, res.errors
            except Exception as e:  # noqa: BLE001 - aggregated below
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(n_plans)]
        try:
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            if kill:
                time.sleep(0.4)  # grants out, queue deep, plans live
                t_kill = time.perf_counter()
                os.kill(child.pid, signal.SIGKILL)
                child.join(10.0)
                time.sleep(0.3)
                child2 = mp.Process(target=_serve_failover_broker,
                                    args=(port, journal, True), daemon=True)
                child2.start()
                wait_port(port)
                dead[0] = time.perf_counter() - t_kill
            for th in threads:
                th.join(timeout=180)
            wall = time.perf_counter() - t0
            assert not errors, errors[:5]
            assert all(len(dst.get_block(f"d{i}")) == rows
                       for i in range(n_plans))
        finally:
            client.stop()
            for p in (child, child2):
                if p is not None and p.is_alive():
                    p.terminate()
                    p.join(5.0)
            shutil.rmtree(tmp, ignore_errors=True)
            fresh()
        return wall

    # one untimed pass so adapter codegen and spawn machinery are paid
    # before either leg — otherwise the first-run tax dwarfs the outage.
    # Interleaved best-of-2 pairs, like the other contended rungs: a
    # 200-thread stress swings hard on small CI boxes.  The injected
    # outage (kill -> new incarnation listening) is a test parameter,
    # not recovery overhead, so the gate is on the wall clock BEYOND
    # the dead window vs the clean run.
    run(kill=False)
    base, excess, hit, outage = float("inf"), float("inf"), 0.0, 0.0
    for _ in range(2):
        base = min(base, run(kill=False))
        h = run(kill=True)
        if h - dead[0] < excess:
            excess, hit, outage = h - dead[0], h, dead[0]
    ratio = max(excess, 0.0) / base
    emit("fig11.broker_failover", hit,
         f"n={n_plans} plans, uninterrupted={base:.3f}s, "
         f"outage={outage:.3f}s, "
         f"recover_ratio_excl_outage={ratio:.2f}x (gate <=1.5x)")
    assert ratio <= 1.5, f"broker failover cost {ratio:.2f}x > 1.5x gate"
    return {"broker_failover": hit, "broker_failover_base": base,
            "outage": outage, "ratio": ratio}


def _incremental_probe(n_rows: int) -> dict:
    """Continuous pipes: N epochs of small deltas (5% of the relation
    each) delivered through ONE long-lived subscription vs re-exporting
    the whole growing relation every epoch.  The subscription pays one
    rendezvous + one snapshot and then moves only the delta bytes; the
    re-export baseline pays a full transfer (rendezvous, encode, copy of
    every row) per refresh — the gap is the entire reason the
    subscription layer exists, so it is benchmarked, not asserted."""
    from repro.core.subscribe import apply_to_engine, publish, subscribe

    n_epochs = 20
    delta_rows = max(1, n_rows // 20)  # 5% delta rate
    base = make_paper_block(n_rows, seed=1)
    deltas = [make_paper_block(delta_rows, seed=100 + e)
              for e in range(n_epochs)]
    total = n_rows + n_epochs * delta_rows

    def run_reexport() -> float:
        fresh()
        src = make_engine("colstore")
        dst = make_engine("colstore")
        src.put_block("t", base)
        cfg = PipeConfig(mode="arrowcol", transport="shm")
        t0 = time.perf_counter()
        for d in deltas:
            src.append("t", d)
            dst.drop("t2")
            transfer(src, "t", dst, "t2", config=cfg, timeout=300)
        sec = time.perf_counter() - t0
        assert len(dst.get_block("t2")) == total
        return sec

    def run_subscription() -> float:
        d = WorkerDirectory()
        dst = make_engine("colstore")
        t0 = time.perf_counter()
        pub = publish("bench.inc", initial=base, directory=d)
        sub = subscribe("bench.inc", directory=d, transport="shm",
                        apply=apply_to_engine(dst, "t2"))
        for blk in deltas:
            pub.append(blk)
        deadline = time.monotonic() + 300
        while sub.watermark < n_epochs + 1 and time.monotonic() < deadline:
            sub.poll(timeout=0.2)
        sec = time.perf_counter() - t0
        assert len(dst.get_block("t2")) == total
        sub.close()
        pub.close()
        return sec

    run_reexport()  # warm adapters / ring pool / engine code paths
    run_subscription()
    out = {"reexport": float("inf"), "incremental": float("inf")}
    for _ in range(REPEATS):  # interleaved best-of-N pairs
        out["reexport"] = min(out["reexport"], run_reexport())
        out["incremental"] = min(out["incremental"], run_subscription())
    emit("fig11.reexport_x20", out["reexport"])
    emit("fig11.incremental_vs_reexport", out["incremental"],
         f"speedup_vs_reexport="
         f"{out['reexport'] / out['incremental']:.2f}x")
    return out


def _telemetry_probe(n_rows: int, baseline: float = 0.0) -> dict:
    """Observability tax rung: the arrowcol shm transfer (same shape as
    the ``pipegen_shm`` rung) with telemetry left
    OFF vs fully ON (span tracer enabled, ``trace=True`` pipes).  The
    disabled path is the contract — ``span()`` must collapse to one
    module-attribute load and a null context manager — so the figure is
    the disabled run's wall-clock delta against the plain ``pipegen_shm``
    rung (``baseline``, <2% is the acceptance bar), with the traced
    delta in the note.  Interleaved best-of-N, like the other
    throughput rungs."""
    from repro.core import disable_tracing, enable_tracing
    from repro.core.telemetry import tracer

    cfg = PipeConfig(mode="arrowcol", transport="shm")
    tcfg = PipeConfig(mode="arrowcol", transport="shm", trace=True)

    pipe_transfer("colstore", "graphstore", n_rows, cfg)  # warm
    out = {"telemetry_off": float("inf"), "telemetry_on": float("inf")}
    n_spans = 0
    for _ in range(max(3, REPEATS)):
        disable_tracing()
        out["telemetry_off"] = min(
            out["telemetry_off"],
            pipe_transfer("colstore", "graphstore", n_rows, cfg))
        enable_tracing()
        try:
            out["telemetry_on"] = min(
                out["telemetry_on"],
                pipe_transfer("colstore", "graphstore", n_rows, tcfg))
            n_spans = max(n_spans, len(tracer().spans()))
        finally:
            disable_tracing()
    off_delta = (out["telemetry_off"] / baseline - 1.0) if baseline else 0.0
    on_delta = out["telemetry_on"] / out["telemetry_off"] - 1.0
    emit("fig11.telemetry_overhead", out["telemetry_off"],
         f"disabled_delta_vs_plain={off_delta * 100:+.1f}% "
         f"traced={out['telemetry_on']:.4f}s "
         f"traced_delta={on_delta * 100:+.1f}% spans={n_spans}")
    return out


def _shuffle_probe(n_rows: int, streams: int = 1) -> float:
    """N=2→M=3 hash-partitioned repartitioning transfer (colstore both
    sides: the graphstore analog cannot hold arbitrary relations).  With
    ``streams`` > 1 every shuffle member pipe is itself striped — the
    composition path (slotted rendezvous)."""

    def run():
        fresh()
        src = make_engine("colstore")
        dst = make_engine("colstore")
        src.put_block("t", make_paper_block(n_rows, seed=1))
        transfer(src, "t", dst, "t2",
                 config=PipeConfig(mode="arrowcol",
                                   block_rows=_SWEEP_BLOCK_ROWS),
                 workers=2, import_workers=3, partition="hash",
                 streams=streams if streams > 1 else None, timeout=300)
        assert len(dst.get_block("t2")) == n_rows

    return timed(run, repeats=REPEATS)


def main(n_rows: int = DEFAULT_ROWS, transports=None, streams_sweep=None) -> dict:
    out = {}
    tf = file_transfer("colstore", "graphstore", n_rows)
    out["file"] = tf
    emit("fig11.file_baseline", tf)
    for name, cfg in RUNGS:
        tp = pipe_transfer("colstore", "graphstore", n_rows, cfg)
        out[name] = tp
        emit(f"fig11.{name}", tp, f"speedup={tf / tp:.2f}x")
    # acceptance probe: the cross-process-capable shm ring should at least
    # match the in-process channel on colocated transfers.  Single samples
    # swing +-30% on small CI boxes, so refine both with two more
    # best-of-N samples before comparing.
    rungs = dict(RUNGS)
    for name in ("pipegen_channel", "pipegen_shm"):
        for _ in range(2):
            out[name] = min(out[name], pipe_transfer(
                "colstore", "graphstore", n_rows, rungs[name]))
        # re-emit so the CSV rows the ratio is computed from are in the CSV
        emit(f"fig11.{name}_best3", out[name], f"speedup={tf / out[name]:.2f}x")
    emit("fig11.shm_vs_channel", out["pipegen_channel"] - out["pipegen_shm"],
         f"ratio={out['pipegen_channel'] / out['pipegen_shm']:.2f}x")
    # the fan-out broadcast ring (one encode feeding three importers)
    out["broadcast"] = _broadcast_probe(n_rows)
    # self-healing transfers: resumed retry vs full re-run after a
    # mid-stream importer death on a bandwidth-capped edge
    out["recovery"] = _recovery_probe(n_rows)
    # broker stress: 200 concurrent plans through one resident broker
    # vs the per-transfer-directory sequential baseline
    out["broker"] = _broker_probe(n_rows)
    # broker failover: the same stress through a served broker with a
    # mid-run SIGKILL + journal recovery, gated at <=1.5x uninterrupted
    out["failover"] = _failover_probe(n_rows)
    # continuous pipes: one subscription moving 20 small deltas vs 20
    # full re-exports of the growing relation
    out["incremental"] = _incremental_probe(n_rows)
    # observability tax: tracing disabled (the near-free contract) vs on
    out["telemetry"] = _telemetry_probe(n_rows, baseline=out["pipegen_shm"])
    # stream-fabric rungs: striping sweep + N→M shuffle
    out["streams"] = _streams_sweep(
        n_rows,
        transports or SWEEP_TRANSPORTS,
        streams_sweep or SWEEP_STREAMS,
    )
    ts = _shuffle_probe(n_rows)
    out["shuffle_2x3"] = ts
    emit("fig11.shuffle_2x3", ts, f"vs_file={tf / ts:.2f}x")
    # the streams×partition composition: the same 2→3 shuffle with every
    # member pipe striped across 2 connections (hash partition, slotted
    # rendezvous) — benchmarked from day one so regressions surface here
    tss = _shuffle_probe(n_rows, streams=2)
    out["striped_shuffle_2x3_s2"] = tss
    emit("fig11.striped_shuffle_2x3_s2", tss, f"vs_unstriped={ts / tss:.2f}x")
    set_directory(WorkerDirectory())
    tm = _manual_pipe(n_rows)
    out["manual"] = tm
    emit("fig11.manual_pipe", tm, f"speedup={tf / tm:.2f}x")
    return out


if __name__ == "__main__":
    main()
