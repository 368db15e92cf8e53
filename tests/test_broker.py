"""The pipe broker and the three scale bugs it rides on: poll-based
doorbell waits (fds >= 1024 crashed ``select.select``), the bounded
directory RPC handler pool (one untracked thread per connection before),
dead-lease heartbeats surfacing as loud importer failures that the
executor's retry path heals, and the broker itself — doorbell hub,
admission control, QoS priority, per-tenant quotas, and fd flatness
under hundreds of concurrent small plans."""

import json
import os
import socket
import tempfile
import threading
import time

import pytest

from repro.core.broker import (
    BrokerBusy,
    DoorbellHub,
    PipeBroker,
    TenantQuota,
    process_fd_count,
    set_broker,
)
from repro.core.datapipe import DataPipeInput, PipeConfig
from repro.core.directory import DirectoryServer, WorkerDirectory, set_directory
from repro.core.plan import PlanError, plan
from repro.core.shm_ring import _Doorbell, doorbell_supported
from repro.engines import make_engine, make_paper_block
from repro.engines.base import assert_blocks_equal

needs_doorbell = pytest.mark.skipif(
    not doorbell_supported(), reason="platform has no eventfd/fifo doorbell")


def _small_edge_cfg(transport="shm", **kw):
    return PipeConfig(mode="arrowcol", block_rows=32, transport=transport,
                      **kw)


def _one_transfer(src_rows=64, transport="shm", seed=3, **options):
    src, dst = make_engine("colstore"), make_engine("colstore")
    blk = make_paper_block(src_rows, seed=seed)
    src.put_block("t", blk)
    res = (plan(negotiate=False)
           .move(src, "t", dst, "t2",
                 config=_small_edge_cfg(transport), timeout=30)
           .options(**options)
           .compile()
           .execute())
    return blk, dst.get_block("t2"), res


# -- satellite 1: FD_SETSIZE ---------------------------------------------------------


@needs_doorbell
def test_doorbell_wait_survives_fd_over_1024():
    """select.select raised ValueError for any fd >= FD_SETSIZE; the
    poll-based wait must not care where dup2 lands the fd."""
    import resource

    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if hard != resource.RLIM_INFINITY and hard < 1600:
        pytest.skip(f"hard RLIMIT_NOFILE {hard} < 1600")
    if soft != resource.RLIM_INFINITY and soft < 1600:
        resource.setrlimit(resource.RLIMIT_NOFILE, (1600, hard))
    path = os.path.join(tempfile.gettempdir(),
                        f"pgtest-{os.getpid()}.pgdb-hi")
    os.mkfifo(path)
    db = None
    try:
        db = _Doorbell(path, create_event=False)
        target = 1500
        os.dup2(db.fd, target)
        os.close(db.fd)
        db.fd = target
        assert db.fd >= 1024
        # empty: a select.select-based wait would raise ValueError here
        assert db.wait(0.05) is False
        wfd = os.open(path, os.O_WRONLY | os.O_NONBLOCK)
        try:
            os.write(wfd, b"!")
        finally:
            os.close(wfd)
        assert db.wait(1.0) is True
    finally:
        if db is not None:
            db.close()
        os.unlink(path)


@needs_doorbell
def test_hub_wait_delivers_wakeup():
    """A hub-mediated wait parks on an Event and is woken by the hub's
    selector thread draining the fifo."""
    path = os.path.join(tempfile.gettempdir(),
                        f"pgtest-{os.getpid()}.pgdb-hub")
    os.mkfifo(path)
    hub = DoorbellHub().start()
    db = None
    try:
        db = _Doorbell(path, create_event=False)
        woke = []
        t = threading.Thread(target=lambda: woke.append(hub.wait(db, 5.0)))
        t.start()
        time.sleep(0.1)  # let the waiter register + park
        wfd = os.open(path, os.O_WRONLY | os.O_NONBLOCK)
        try:
            os.write(wfd, b"!")
        finally:
            os.close(wfd)
        t.join(timeout=5.0)
        assert woke == [True]
        assert hub.wakeups >= 1 and hub.registered == 1
        hub.discard(db)
        assert hub.registered == 0
    finally:
        if db is not None:
            db.close()
        hub.stop()
        os.unlink(path)


# -- satellite 2: bounded RPC handlers ----------------------------------------------


def _rpc(host, port, req, timeout=10.0):
    with socket.create_connection((host, port), timeout=timeout) as s:
        s.sendall(json.dumps(req).encode() + b"\n")
        f = s.makefile("rb")
        return json.loads(f.readline())


def test_directory_server_thread_count_is_bounded():
    srv = DirectoryServer("127.0.0.1", 0, handlers=4).start()
    try:
        baseline = threading.active_count()
        peak = [baseline]
        stop = threading.Event()

        def sample():
            while not stop.is_set():
                peak[0] = max(peak[0], threading.active_count())
                time.sleep(0.002)

        sampler = threading.Thread(target=sample)
        sampler.start()
        n_clients, per_client = 16, 8

        def client():
            for _ in range(per_client):
                r = _rpc(srv.host, srv.port,
                         {"op": "renew", "dataset": "none",
                          "query_id": "q", "pid": 1})
                assert r["ok"]

        clients = [threading.Thread(target=client) for _ in range(n_clients)]
        for c in clients:
            c.start()
        for c in clients:
            c.join(timeout=30.0)
        stop.set()
        sampler.join(timeout=5.0)
        # the burst adds client + sampler threads only: the server must
        # not have grown beyond its fixed pool (the old code added one
        # daemon thread per connection — 128 here)
        assert peak[0] <= baseline + n_clients + 2
    finally:
        srv.stop()
    # stop() joins everything it started
    names = [t.name for t in threading.enumerate()]
    assert not any(n.startswith("pgdir-handler-") for n in names)


def test_directory_server_blocking_query_does_not_starve_fast_ops():
    """A pool-full pile of blocked queries must not delay the register
    they are all waiting for (the fast lane runs in the accept loop)."""
    srv = DirectoryServer("127.0.0.1", 0, handlers=2).start()
    try:
        results = []

        def q():
            results.append(_rpc(
                srv.host, srv.port,
                {"op": "query", "dataset": "d", "query_id": "q1",
                 "timeout": 10.0}, timeout=30.0))

        qs = [threading.Thread(target=q) for _ in range(2)]  # fill the pool
        for t in qs:
            t.start()
        time.sleep(0.2)
        r = _rpc(srv.host, srv.port, {
            "op": "register", "dataset": "d", "query_id": "q1",
            "host": "127.0.0.1", "port": 5, "pid": 1, "workers": 1,
            "transport": "socket"})
        assert r["ok"]
        for t in qs:
            t.join(timeout=30.0)
        assert len(results) == 2
        assert any(x["ok"] for x in results)  # one query got the endpoint
    finally:
        srv.stop()


# -- satellite 3: dead-lease heartbeats ---------------------------------------------


class _LeaseKiller(WorkerDirectory):
    """Simulates the GC'd-registration race: the first attempt's renewals
    find nothing (entry dropped, renew -> 0); retry attempts (query ids
    carrying the executor's ``a<k>`` suffix) behave normally."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.killed = threading.Event()

    def _is_retry(self, query_id):
        return "a" in str(query_id)

    def renew(self, dataset, query_id="0", pid=None, lease_s=None):
        if not self._is_retry(query_id):
            with self._lock:  # the GC: registration dropped, shm released
                st = self._queries.get((dataset, str(query_id)))
                if st is not None:
                    st.entries.clear()
                self._lock.notify_all()
            self.killed.set()
            return 0
        return super().renew(dataset, query_id, pid=pid, lease_s=lease_s)

    def query(self, dataset, query_id="0", export_workers=None,
              timeout=30.0):
        if not self._is_retry(query_id):
            # exporter arrives "late": after the lease is already gone
            self.killed.wait(timeout=5.0)
        return super().query(dataset, query_id, export_workers,
                             timeout=timeout)


@needs_doorbell
def test_renew_zero_surfaces_as_loud_importer_failure():
    d = _LeaseKiller()
    set_directory(d)
    inp = DataPipeInput("db://dead?workers=1&query=q0",
                        config=PipeConfig(transport="shm", lease_s=0.15))
    try:
        with pytest.raises(BrokenPipeError) as e:
            inp.read(1)  # parked on the ring until the renew loop aborts it
        assert "lease" in str(e.value)
        assert d.killed.is_set()
    finally:
        inp.close()


@needs_doorbell
def test_lease_loss_heals_through_executor_retry():
    d = _LeaseKiller()
    set_directory(d)
    src, dst = make_engine("colstore"), make_engine("colstore")
    blk = make_paper_block(96, seed=11)
    src.put_block("t", blk)
    res = (plan(negotiate=False)
           .move(src, "t", dst, "t2",
                 config=_small_edge_cfg("shm", lease_s=0.15),
                 timeout=1.5, retries=1, backoff=0.01)
           .compile()
           .execute())
    r = res.single()
    assert len(r.attempts) == 2  # attempt 0 lost its lease, attempt 1 ran
    assert not r.attempts[0]["ok"] and r.attempts[1]["ok"]
    assert_blocks_equal(blk, dst.get_block("t2"), check_names=False)
    # attempt 0's abandoned exporter must unwind within its (clamped)
    # connect timeout — a lingering thread still holds its open-splice
    for t in threading.enumerate():
        if t.name.startswith("pipegen-export"):
            t.join(timeout=10.0)
            assert not t.is_alive(), t.name


def test_renew_of_popped_endpoint_is_not_lease_loss():
    """Once the exporter pops the registration the importer's heartbeat
    must report success (the transfer is past rendezvous), not the
    fatal renewed-0."""
    from repro.core.directory import Endpoint

    d = WorkerDirectory(lease_ttl=30.0)
    ep = Endpoint(host="h", port=1, pid=os.getpid())
    d.register("ds", ep, "q1", lease_s=30.0)
    assert d.renew("ds", "q1", pid=os.getpid()) == 1
    got = d.query("ds", "q1", timeout=1.0)  # pops the entry
    assert got.pid == os.getpid()
    assert d.renew("ds", "q1", pid=os.getpid()) == 1  # popped, not GC'd
    assert d.renew("ds", "q1", pid=999999) == 0  # unknown pid: truly gone


# -- the broker: admission + QoS + quotas -------------------------------------------


def test_admission_blocks_until_release_and_rejects_never_fits():
    with PipeBroker(max_rings=2, hub=False) as b:
        a = b.admit(rings=2)
        with pytest.raises(BrokerBusy):
            b.admit(rings=1, timeout=0.2)
        with pytest.raises(BrokerBusy):  # can never fit: instant reject
            b.admit(rings=3, timeout=30.0)
        got = []
        t = threading.Thread(
            target=lambda: got.append(b.admit(rings=2, timeout=10.0)))
        t.start()
        time.sleep(0.1)
        assert b.stats()["waiting"] == 1
        a.release()
        t.join(timeout=10.0)
        assert len(got) == 1
        got[0].release()
        assert b.stats()["active_rings"] == 0
        assert b.rejected == 2 and b.queued >= 1


def test_latency_class_jumps_bulk_queue():
    with PipeBroker(max_rings=2, hub=False) as b:
        a = b.admit(rings=2, qos="bulk")
        order = []
        lock = threading.Lock()

        def take(qos):
            adm = b.admit(rings=2, qos=qos, timeout=10.0)
            with lock:
                order.append(qos)
            time.sleep(0.15)
            adm.release()

        bulk = threading.Thread(target=take, args=("bulk",))
        bulk.start()
        time.sleep(0.1)  # bulk queues first...
        lat = threading.Thread(target=take, args=("latency",))
        lat.start()
        time.sleep(0.1)
        assert b.stats()["waiting"] == 2
        a.release()  # ...but latency is admitted first
        bulk.join(timeout=10.0)
        lat.join(timeout=10.0)
        assert order == ["latency", "bulk"]


def test_oversized_ticket_does_not_starve_small_ones():
    with PipeBroker(max_rings=4, hub=False) as b:
        a = b.admit(rings=3)
        blocked = threading.Thread(
            target=lambda: b.admit(rings=4, timeout=3.0).release())
        blocked.start()
        time.sleep(0.1)
        small = b.admit(rings=1, timeout=0.5)  # fits NOW; big one waits
        small.release()
        a.release()
        blocked.join(timeout=10.0)


def test_tenant_quotas_isolate_budgets():
    with PipeBroker(max_rings=None, hub=False,
                    tenants={"a": TenantQuota(max_rings=1)}) as b:
        a1 = b.admit(tenant="a", rings=1)
        with pytest.raises(BrokerBusy):
            b.admit(tenant="a", rings=1, timeout=0.2)  # a is at quota
        b1 = b.admit(tenant="b", rings=8, timeout=0.2)  # b is not
        a1.release()
        a2 = b.admit(tenant="a", rings=1, timeout=1.0)
        a2.release()
        b1.release()


def test_qos_concurrency_cap():
    with PipeBroker(max_rings=None, hub=False,
                    qos_concurrency={"bulk": 1}) as b:
        x = b.admit(qos="bulk", rings=1)
        with pytest.raises(BrokerBusy):
            b.admit(qos="bulk", rings=1, timeout=0.2)
        y = b.admit(qos="latency", rings=1, timeout=0.2)  # uncapped class
        x.release()
        y.release()


def test_plan_validates_qos_and_broker_rejection_fails_edge():
    with pytest.raises(PlanError):
        src, dst = make_engine("colstore"), make_engine("colstore")
        plan().move(src, "t", dst, "t2", qos="turbo").compile()
    b = PipeBroker(max_rings=None, hub=False,
                   default_quota=TenantQuota(max_rings=0)).install()
    try:
        src, dst = make_engine("colstore"), make_engine("colstore")
        src.put_block("t", make_paper_block(64, seed=3))
        res = (plan(negotiate=False)
               .move(src, "t", dst, "t2",
                     config=_small_edge_cfg("shm"), timeout=5)
               .compile()
               .execute(raise_on_error=False))
        assert res.exceptions and isinstance(res.exceptions[0], BrokerBusy)
    finally:
        b.stop()
        set_broker(None)


# -- the broker: hub-mediated transfers + fd flatness -------------------------------


@needs_doorbell
def test_transfer_through_installed_broker_uses_hub():
    from repro.core.shm_ring import ShmRing, ShmRingTransport
    from repro.core.datapipe import FRAME_TEXT

    b = PipeBroker(max_rings=8).install()
    try:
        blk, got, _ = _one_transfer(src_rows=640, qos="latency")
        assert_blocks_equal(blk, got, check_names=False)
        st = b.stats()
        assert st["admitted"] == 1
        assert st["hub_registered"] == 0  # parked rings released their fds
        # a guaranteed-idle wait (slow writer) must park through the hub
        ring = ShmRing.create(capacity=4096, role="reader")
        tx, rx = ShmRingTransport(ring), ShmRingTransport(ring)

        def send():
            time.sleep(0.1)  # reader reaches the parked doorbell wait
            tx.send_frames(FRAME_TEXT, [b"ping"])

        th = threading.Thread(target=send, daemon=True)
        th.start()
        assert rx.recv_frame() == (FRAME_TEXT, b"ping")
        th.join(10.0)
        ring.close()
        assert b.stats()["hub_wakeups"] >= 1
    finally:
        b.stop()


@needs_doorbell
def test_broker_sustains_200_concurrent_plans_with_flat_fds():
    """The acceptance bar: >= 200 concurrent small plans through ONE
    broker, fd count bounded by admission (not by plan count)."""
    n_plans = 200
    b = PipeBroker(max_rings=16, admit_timeout=120.0).install()
    try:
        _one_transfer(src_rows=32)  # warm the adapter cache serially
        base = process_fd_count()
        peak = [base]
        stop = threading.Event()

        def sample():
            while not stop.is_set():
                peak[0] = max(peak[0], process_fd_count())
                time.sleep(0.005)

        sampler = threading.Thread(target=sample)
        sampler.start()
        failures = []

        def one(i):
            try:
                blk, got, _ = _one_transfer(src_rows=32, seed=i)
                assert_blocks_equal(blk, got, check_names=False)
            except Exception as e:  # noqa: BLE001 - aggregated below
                failures.append((i, repr(e)))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_plans)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        stop.set()
        sampler.join(timeout=5.0)
        assert not failures, failures[:5]
        st = b.stats()
        assert st["admitted"] == n_plans + 1
        # flat: bounded by the 16-ring admission ceiling (each live SPSC
        # ring holds <= 6 doorbell fds across both in-process sides),
        # NOT by the 200 plans
        assert peak[0] - base < 16 * 6 + 40, (base, peak[0])
    finally:
        b.stop()
    after = process_fd_count()
    assert after <= base + 4, (base, after)  # pools drained, hub closed
