"""Continuous pipes: a publish/subscribe data plane over the pipe fabric.

One-shot pipes move a relation once; a *publication* keeps moving it.  An
exporter ``publish()``es a relation under a name registered in the worker
directory; every ``commit()`` of delta blocks becomes a monotonically
increasing **epoch**.  Each epoch is encoded exactly once into wire payload
bytes and appended to a bounded in-memory **replay log** (epoch- and
byte-capped; oldest epochs evicted first).  Committed epochs are pushed to
subscribers over the existing transports — the broadcast shm ring for a
colocated fan-out (one encode, one ring write, R readers), striped pipes
or sockets for remote subscribers — reusing ring doorbells so an idle
subscription parks on an fd instead of polling.

Importers ``subscribe()`` with a **watermark**, the last epoch they have
applied.  The publisher's per-subscriber sender walks forward from that
watermark: epochs still retained in the log are *replayed* from their
stored payloads (no re-encode); if the watermark has fallen off the log,
the subscriber receives a full **snapshot** of the publication's current
image stamped with its epoch, then live deltas — the same RESUME-style
idea the fault harness uses for one-shot edges.  Publisher crash +
restart therefore heals end-to-end: the restarted publisher re-publishes
under the same name (the registry entry is pid-owned and lease-swept) and
subscribers resubscribe at their watermark.

Wire protocol per subscriber connection::

    S  schema hello (schema + {"mode","codec","name"} meta)
    D  epoch header {"epoch","head","kind","blocks","rows","ts"}
    B  x header["blocks"] — wire-format payload, one committed block each
    ...repeated per epoch...
    E  publication closed

Lifecycle notes: a :class:`Subscription` owns its directory lease renewer
(:class:`repro.core.directory.LeaseRenewer`) until ``close()`` — renewal
is *not* bounded by any single transfer.  Broker admission is taken per
subscriber ring under the publication's ``tenant``/``qos`` so a bulk
fan-out queues behind latency traffic instead of starving it.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import telemetry
from .broker import BrokerBusy, get_broker
from .compression import get_codec
from .datapipe import _connect
from .directory import (DirectoryLike, Endpoint, LeaseRenewer,
                        get_directory)
from .shm_ring import (DEFAULT_RING_CAPACITY, ShmRing, ShmRingTransport,
                       acquire_broadcast_ring, acquire_ring)
from .stream import StripedReceiver
from .transport import (Channel, ChannelTransport, FRAME_BLOCK, FRAME_EOF,
                        FRAME_EPOCH, FRAME_SCHEMA, LinkSim, SocketTransport,
                        Transport, listen_socket)
from .types import ColumnBlock, Schema
from .wire import decode_schema, encode_schema, get_wire_format

__all__ = [
    "EpochDelta",
    "Publication",
    "PublicationEnded",
    "ReplayLog",
    "SubscribeError",
    "Subscription",
    "apply_to_engine",
    "decode_epoch_header",
    "encode_epoch_header",
    "publications_snapshot",
    "publish",
    "subscribe",
]

_SUB_QUERY = "sub"


def _sub_dataset(name: str) -> str:
    return f"__sub__.{name}"


class SubscribeError(RuntimeError):
    """Misuse or unrecoverable state of a publication/subscription."""


class PublicationEnded(BrokenPipeError):
    """The publisher closed (or died) and every queued epoch is drained.

    Carries ``watermark`` so a caller can resubscribe exactly where it
    stopped: ``subscribe(name, watermark=exc.watermark)``.
    """

    def __init__(self, msg: str, watermark: int = 0):
        super().__init__(msg)
        self.watermark = watermark


# -- epoch framing (rides FRAME_EPOCH over any transport) ------------------------

def encode_epoch_header(epoch: int, head: int, kind: str = "delta",
                        blocks: int = 1, rows: int = 0,
                        ts: float = 0.0) -> bytes:
    return json.dumps({
        "epoch": int(epoch), "head": int(head), "kind": kind,
        "blocks": int(blocks), "rows": int(rows), "ts": float(ts),
    }).encode()


def decode_epoch_header(payload: Any) -> Dict[str, Any]:
    return json.loads(bytes(payload).decode())


# -- replay log --------------------------------------------------------------------

@dataclass
class _EpochRecord:
    epoch: int
    kind: str                 # "delta" | "snapshot"
    payloads: List[bytes]     # encoded + compressed, one per block
    rows: int
    nbytes: int
    ts: float


class ReplayLog:
    """Bounded epoch → payload store; oldest epochs evicted first.

    Retention is the product of two caps: at most ``retain_epochs``
    entries and at most ``retain_bytes`` of stored payload (the newest
    epoch is always kept even if it alone exceeds the byte cap, so the
    live path never starves).
    """

    def __init__(self, retain_epochs: int = 64,
                 retain_bytes: int = 64 << 20):
        self.retain_epochs = int(retain_epochs)
        self.retain_bytes = int(retain_bytes)
        self._lock = threading.Lock()
        self._recs: "OrderedDict[int, _EpochRecord]" = OrderedDict()
        self.nbytes = 0
        self.evicted = 0

    def append(self, rec: _EpochRecord) -> None:
        with self._lock:
            self._recs[rec.epoch] = rec
            self.nbytes += rec.nbytes
            while len(self._recs) > 1 and (
                    len(self._recs) > self.retain_epochs
                    or self.nbytes > self.retain_bytes):
                _, old = self._recs.popitem(last=False)
                self.nbytes -= old.nbytes
                self.evicted += 1

    def get(self, epoch: int) -> Optional[_EpochRecord]:
        with self._lock:
            return self._recs.get(epoch)

    @property
    def floor(self) -> int:
        """Oldest retained epoch (0 when the log is empty)."""
        with self._lock:
            return next(iter(self._recs), 0)

    def __len__(self) -> int:
        with self._lock:
            return len(self._recs)


# -- publisher ---------------------------------------------------------------------

@dataclass
class PubStats:
    epochs: int = 0            # epochs committed
    encodes: int = 0           # block encodes in commit (one per block, ever)
    fallback_encodes: int = 0  # snapshot re-encodes for un-retained watermarks
    snapshot_fallbacks: int = 0
    replayed_epochs: int = 0   # epochs served to late joiners from the log
    bytes_logged: int = 0
    admission_rejects: int = 0


def _chunk_rows(block: ColumnBlock,
                target_bytes: Optional[int]) -> List[ColumnBlock]:
    """Row-slice ``block`` so each piece carries at most ~``target_bytes``
    of raw payload (None = no cap).  Lets a snapshot of a large image ship
    over a small shm ring as k frames instead of one oversized frame."""
    n = len(block)
    if target_bytes is None or n <= 1 or block.nbytes <= target_bytes:
        return [block]
    step = max(1, int(n * target_bytes / max(1, block.nbytes)))
    return [ColumnBlock(block.schema, [c[i:i + step] for c in block.columns])
            for i in range(0, n, step)]


class _SubscriberConn:
    """One attached subscriber: a transport plus the sender thread that
    walks it forward from its watermark.  Broadcast rings fan out to R
    readers through a single conn (one write per epoch)."""

    _ids = itertools.count()

    def __init__(self, pub: "Publication", transport: Transport,
                 watermark: int, readers: int = 1, admission: Any = None):
        self.pub = pub
        self.transport = transport
        self.sent = int(watermark)
        self.readers = readers
        self.admission = admission
        self.attached_at_head = pub.head
        self._stop = False
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"pipegen-pub-send-{pub.name}-{next(self._ids)}")

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        with self.pub._cv:
            self._stop = True
            self.pub._cv.notify_all()

    def join(self, timeout: float = 10.0) -> None:
        self._thread.join(timeout)

    # sender loop ---------------------------------------------------------------
    def _run(self) -> None:
        pub = self.pub
        try:
            self.transport.send_frame(
                FRAME_SCHEMA, encode_schema(pub.schema, pub.hello_meta()))
            while True:
                with pub._cv:
                    while (pub.head <= self.sent and not self._stop
                           and not pub._closing):
                        pub._cv.wait(1.0)
                    if pub.head <= self.sent and (self._stop or pub._closing):
                        break  # drained: graceful EOF below
                    head = pub.head
                rec = pub._log.get(self.sent + 1)
                if rec is not None:
                    self._send_record(rec, head)
                    if rec.epoch <= self.attached_at_head:
                        pub.stats.replayed_epochs += 1
                    self.sent = rec.epoch
                else:
                    # watermark fell off the log: full snapshot of the
                    # current image stamped with its epoch, then deltas
                    self._send_snapshot()
                pub._update_gauges()
            self.transport.send_frame(FRAME_EOF, b"")
        except (OSError, ValueError, IOError):
            pass  # subscriber went away; the publication keeps running
        finally:
            try:
                self.transport.close()
            except Exception:
                pass
            if self.admission is not None:
                try:
                    self.admission.release()
                except Exception:
                    pass
            pub._retire(self)

    def _send_record(self, rec: _EpochRecord, head: int) -> None:
        hdr = encode_epoch_header(rec.epoch, head, rec.kind,
                                  len(rec.payloads), rec.rows, rec.ts)
        self.transport.send_frame(FRAME_EPOCH, hdr)
        for payload in rec.payloads:
            self.transport.send_frame(FRAME_BLOCK, payload)

    def _max_chunk_bytes(self) -> Optional[int]:
        """Raw-bytes budget per snapshot chunk: an shm ring bounds the frame
        size at its capacity, so a big image must ship as k row-slices (the
        D header's ``blocks`` field already frames multi-payload epochs).
        Socket/channel/striped transports have no frame cap."""
        ring = getattr(self.transport, "ring", None)
        if ring is None:
            return None
        return max(4096, ring.capacity // 2)

    def _send_snapshot(self) -> None:
        pub = self.pub
        epoch, image = pub._snapshot_image()
        if image is None:       # closing before any commit
            return
        chunks = _chunk_rows(image, self._max_chunk_bytes())
        payloads, rows, _ = pub._encode_blocks(chunks, fallback=True)
        pub.stats.snapshot_fallbacks += 1
        hdr = encode_epoch_header(epoch, epoch, "snapshot",
                                  len(payloads), rows, time.time())
        self.transport.send_frame(FRAME_EPOCH, hdr)
        for payload in payloads:
            self.transport.send_frame(FRAME_BLOCK, payload)
        self.sent = epoch


class Publication:
    """A named, continuously-updated relation other processes subscribe to.

    ``commit(blocks)`` assigns the next epoch, encodes each block exactly
    once, appends the payloads to the replay log, folds the delta into the
    publication's running *image* (the late-joiner snapshot source — kept
    here, not in the engine, so snapshots are epoch-consistent without
    holding any engine lock), and wakes every sender.
    """

    def __init__(self, name: str, schema: Schema, *,
                 directory: Optional[DirectoryLike] = None,
                 mode: str = "arrowcol", codec: str = "none",
                 retain_epochs: int = 64, retain_bytes: int = 64 << 20,
                 start_epoch: int = 0, lease_s: Optional[float] = None,
                 tenant: str = "default", qos: str = "bulk",
                 link: Optional[LinkSim] = None,
                 attach_wait: Optional[float] = None):
        self.name = name
        self.schema = schema
        self.mode = mode
        self.codec_name = codec
        self.tenant = tenant
        self.qos = qos
        self._directory = directory if directory is not None else get_directory()
        self._wire = get_wire_format(mode)
        self._codec = get_codec(codec)
        self._link = link
        self._dataset = _sub_dataset(name)
        self._log = ReplayLog(retain_epochs, retain_bytes)
        self.head = int(start_epoch)
        self._image: Optional[ColumnBlock] = None
        self._cv = threading.Condition()
        self._conns: List[_SubscriberConn] = []
        self._closing = False
        self._closed = False
        self.stats = PubStats()

        # long-lived concurrency ticket: the publication itself holds a
        # zero-byte admission under its tenant/qos; each subscriber ring
        # admits its own (rings, bytes) vector at attach time
        self._admission = None
        broker = get_broker()
        if broker is not None:
            self._admission = broker.admit(
                tenant=tenant, qos=qos, rings=0, segments=0, nbytes=0)

        doc = {
            "name": name, "dataset": self._dataset, "query": _SUB_QUERY,
            "mode": mode, "codec": codec, "pid": os.getpid(),
            "schema": schema.to_dict(), "start_epoch": int(start_epoch),
        }
        self._pub_doc = doc
        self._lease_s = lease_s
        self._head_published = int(start_epoch)
        self._head_pub_at = time.monotonic()
        self._directory.publish_name(name, doc, lease_s=lease_s)
        self._renewer: Optional[LeaseRenewer] = None
        if lease_s and hasattr(self._directory, "renew_name"):
            self._renewer = LeaseRenewer(
                lambda ls: self._directory.renew_name(self.name, lease_s=ls),
                lease_s, on_lost=self._republish,
                name=f"pipegen-pub-renew-{name}").start()

        _register_publication(self)
        # in-process directories block cheaply on a condvar; a
        # DirectoryClient burns an RPC per poll, so poll it coarser
        in_proc = hasattr(self._directory, "_queries")
        self._attach_wait = attach_wait if attach_wait else (
            5.0 if in_proc else 1.0)
        self._attach_thread = threading.Thread(
            target=self._attach_loop, daemon=True,
            name=f"pipegen-pub-attach-{name}")
        self._attach_thread.start()

    # -- commit path ------------------------------------------------------------
    def commit(self, blocks: Any, kind: str = "delta") -> int:
        """Commit one epoch of ``blocks`` (a ColumnBlock or sequence).
        Returns the epoch assigned; an empty delta commits nothing and
        returns the current head."""
        if isinstance(blocks, ColumnBlock):
            blocks = [blocks]
        blocks = [b for b in blocks if len(b)]
        if not blocks:
            if kind == "snapshot":
                raise SubscribeError("snapshot commit needs at least one row")
            return self.head
        with telemetry.span("subscribe.epoch", pub=self.name, kind=kind):
            payloads, rows, nbytes = self._encode_blocks(blocks)
            with self._cv:
                if self._closing:
                    raise SubscribeError(
                        f"publication {self.name!r} is closed")
                epoch = self.head + 1
                self._log.append(_EpochRecord(
                    epoch, kind, payloads, rows, nbytes, time.time()))
                if kind == "snapshot":
                    self._image = (blocks[0] if len(blocks) == 1
                                   else ColumnBlock.concat(blocks))
                elif self._image is not None and len(self._image):
                    self._image = ColumnBlock.concat([self._image] + blocks)
                else:
                    self._image = (blocks[0] if len(blocks) == 1
                                   else ColumnBlock.concat(blocks))
                self.head = epoch
                self.stats.epochs += 1
                self.stats.bytes_logged += nbytes
                self._cv.notify_all()
        self._update_gauges()
        self._maybe_publish_head()
        return epoch

    def _maybe_publish_head(self) -> None:
        """Re-stamp the published name doc with the committed head,
        throttled to one RPC per half second.  A journaling broker logs
        every ``publish_name``, so after a control-plane crash the
        recovered registry re-pins this publication at (close to) its
        committed head instead of the head it had at publish time."""
        now = time.monotonic()
        with self._cv:
            if (self._closing or self.head == self._head_published
                    or now - self._head_pub_at < 0.5):
                return
            self._head_published = self.head
            self._head_pub_at = now
            doc = dict(self._pub_doc, head=self.head)
        try:
            self._directory.publish_name(self.name, doc,
                                         lease_s=self._lease_s)
        except (OSError, ValueError):  # pragma: no cover - broker flap
            pass

    def _republish(self) -> None:
        """Name-lease ``on_lost``: the published name expired under us
        (broker restart without a journal, or a GC race) while the
        publication itself is alive and committing.  Self-heal: publish
        again at the current head and restart the heartbeat."""
        with self._cv:
            if self._closing:
                return
            doc = dict(self._pub_doc, head=self.head)
            self._head_published = self.head
        try:
            self._directory.publish_name(self.name, doc,
                                         lease_s=self._lease_s)
        except (OSError, ValueError):  # pragma: no cover - broker gone
            return
        telemetry.counter("subscribe.name_republished").inc()
        self._renewer = LeaseRenewer(
            lambda ls: self._directory.renew_name(self.name, lease_s=ls),
            self._lease_s, on_lost=self._republish,
            name=f"pipegen-pub-renew-{self.name}").start()

    def append(self, block: ColumnBlock) -> int:
        return self.commit(block, kind="delta")

    def commit_snapshot(self, block: ColumnBlock) -> int:
        """Commit the relation's full current contents as one epoch — the
        normal first commit, and the restart path after a crash (the new
        image replaces, rather than extends, what subscribers hold)."""
        return self.commit(block, kind="snapshot")

    def _encode_blocks(self, blocks: Sequence[ColumnBlock],
                       fallback: bool = False
                       ) -> Tuple[List[bytes], int, int]:
        payloads: List[bytes] = []
        rows = 0
        nbytes = 0
        for b in blocks:
            data = self._codec.compress(self._wire.encode_block(b).join())
            payloads.append(bytes(data))
            rows += len(b)
            nbytes += len(payloads[-1])
        if fallback:
            self.stats.fallback_encodes += len(blocks)
        else:
            self.stats.encodes += len(blocks)
        return payloads, rows, nbytes

    def _snapshot_image(self, timeout: float = 30.0
                        ) -> Tuple[int, Optional[ColumnBlock]]:
        """The current (head, image) pair, bound together under the
        publication lock so the snapshot is exactly epoch ``head``."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._image is None and not self._closing:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cv.wait(remaining)
            return self.head, self._image

    def hello_meta(self) -> Dict[str, Any]:
        return {"mode": self.mode, "codec": self.codec_name,
                "name": self.name}

    # -- subscriber attach ------------------------------------------------------
    def _attach_loop(self) -> None:
        while True:
            with self._cv:
                if self._closing:
                    return
            try:
                ep = self._directory.query(
                    self._dataset, _SUB_QUERY, timeout=self._attach_wait)
            except TimeoutError:
                continue
            except Exception:
                with self._cv:
                    if self._closing:
                        return
                time.sleep(0.2)
                continue
            if ep.resume_seq < 0:
                # wake sentinel — ours, or one a closed predecessor of
                # this name never popped; never a real subscriber
                with self._cv:
                    if self._closing:
                        return
                continue
            with self._cv:
                closing = self._closing
            if closing:
                self._refuse(ep)
                return
            self._attach(ep)

    def _attach(self, ep: Endpoint) -> None:
        admission = None
        broker = get_broker()
        shm_rings = ((1 if ep.is_shm else 0)
                     + sum(1 for m in ep.members if m.is_shm))
        if broker is not None and shm_rings:
            nbytes = (ep.shm_capacity or 0) + sum(
                m.shm_capacity or 0 for m in ep.members)
            try:
                admission = broker.admit(
                    tenant=self.tenant, qos=self.qos, rings=shm_rings,
                    segments=shm_rings, nbytes=nbytes, timeout=30.0)
            except BrokerBusy:
                self.stats.admission_rejects += 1
                telemetry.counter("pipe.subscription.admission_rejects",
                                  pub=self.name).inc()
                self._refuse(ep)
                return
        try:
            if ep.is_group:
                # per-subscriber striped pipes (remote): wrap the member
                # transports in the striped sender used by one-shot edges
                from .stream import StripedSender
                parts = [_connect(m, self._link) for m in ep.members]
                transport: Transport = StripedSender(parts)
            else:
                transport = _connect(ep, self._link)
        except (OSError, IOError):
            if admission is not None:
                admission.release()
            return
        conn = _SubscriberConn(
            self, transport, watermark=ep.resume_seq,
            readers=max(1, ep.broadcast), admission=admission)
        with self._cv:
            self._conns.append(conn)
        conn.start()
        self._update_gauges()

    def _refuse(self, ep: Endpoint) -> None:
        """EOF a subscriber we cannot serve so it fails fast instead of
        waiting out its rendezvous timeout."""
        try:
            tr = _connect(ep, self._link)
            tr.send_frame(FRAME_EOF, b"")
            tr.close()
        except Exception:
            pass

    def _retire(self, conn: _SubscriberConn) -> None:
        with self._cv:
            if conn in self._conns:
                self._conns.remove(conn)
        self._update_gauges()

    # -- introspection ----------------------------------------------------------
    @property
    def subscribers(self) -> int:
        with self._cv:
            return sum(c.readers for c in self._conns)

    @property
    def min_watermark(self) -> int:
        with self._cv:
            if not self._conns:
                return self.head
            return min(c.sent for c in self._conns)

    def _update_gauges(self) -> None:
        reg = telemetry.registry()
        labels = {"pub": self.name}
        reg.gauge("pipe.subscription.head_epoch", **labels).set(self.head)
        reg.gauge("pipe.subscription.retained_bytes",
                  **labels).set(self._log.nbytes)
        reg.gauge("pipe.subscription.subscribers",
                  **labels).set(self.subscribers)
        reg.gauge("pipe.subscription.min_watermark",
                  **labels).set(self.min_watermark)

    def snapshot_row(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "subscribers": self.subscribers,
            "head_epoch": self.head,
            "min_watermark": self.min_watermark,
            "retained_bytes": self._log.nbytes,
            "retained_epochs": len(self._log),
            "floor": self._log.floor,
            "epochs": self.stats.epochs,
            "snapshot_fallbacks": self.stats.snapshot_fallbacks,
        }

    # -- teardown ---------------------------------------------------------------
    def close(self, timeout: float = 10.0) -> None:
        """Drain committed epochs to every subscriber, EOF them, release
        admission, drop the name, stop the lease renewer."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._closing = True
            self._cv.notify_all()
        self._wake_attach()
        self._attach_thread.join(self._attach_wait + 2.0)
        with self._cv:
            conns = list(self._conns)
        for conn in conns:
            conn.stop()
        for conn in conns:
            conn.join(timeout)
        if self._renewer is not None:
            self._renewer.stop(join=True)
        try:
            self._directory.unpublish_name(self.name)
        except Exception:
            pass
        if self._admission is not None:
            try:
                self._admission.release()
            except Exception:
                pass
        _unregister_publication(self)
        reg = telemetry.registry()
        for g in ("head_epoch", "retained_bytes", "subscribers",
                  "min_watermark"):
            reg.drop(f"pipe.subscription.{g}", kind="g", pub=self.name)

    def _wake_attach(self) -> None:
        # the attach loop may be parked inside query(); an in-process
        # directory wakes instantly off a sentinel channel endpoint, a
        # DirectoryClient polls out within _attach_wait on its own
        try:
            if hasattr(self._directory, "_queries"):
                # resume_seq=-1 marks it as a sentinel: if the attach
                # loop exits before popping it, a successor publication
                # under the same name must not mistake it for a real
                # subscriber and serve a snapshot into the void
                self._directory.register(
                    self._dataset, Endpoint(channel=Channel(),
                                            resume_seq=-1), _SUB_QUERY)
        except Exception:
            pass

    def __enter__(self) -> "Publication":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


# -- subscriber --------------------------------------------------------------------

@dataclass
class EpochDelta:
    """One received epoch: ``kind == "snapshot"`` replaces the local copy
    of the relation, ``"delta"`` extends it."""

    epoch: int
    kind: str
    blocks: List[ColumnBlock] = field(default_factory=list)
    rows: int = 0
    ts: float = 0.0
    head: int = 0

    @property
    def block(self) -> ColumnBlock:
        if len(self.blocks) == 1:
            return self.blocks[0]
        return ColumnBlock.concat(self.blocks)


@dataclass
class SubStats:
    epochs: int = 0
    snapshots: int = 0
    duplicates: int = 0
    rows: int = 0


class Subscription:
    """A live importer handle on a named publication.

    ``poll()`` returns the epochs received since the last call (advancing
    ``watermark``); once the publisher EOFs or dies *and* every queued
    epoch is drained, ``poll()`` raises :class:`PublicationEnded` carrying
    the watermark to resubscribe at.  The handle owns its directory lease
    renewer for its whole lifetime — close() stops and joins it.
    """

    _ids = itertools.count()

    def __init__(self, name: str, *, watermark: int = 0,
                 directory: Optional[DirectoryLike] = None,
                 transport: str = "shm", broadcast: int = 0,
                 group: str = "bc0", streams: int = 1,
                 shm_capacity: int = DEFAULT_RING_CAPACITY,
                 lease_s: Optional[float] = None,
                 timeout: float = 30.0, link: Optional[LinkSim] = None,
                 host: str = "127.0.0.1",
                 apply: Optional[Callable[[EpochDelta], None]] = None,
                 queue_max: int = 0, sub_id: Optional[str] = None):
        self.name = name
        self._directory = directory if directory is not None else get_directory()
        doc = self._directory.lookup_name(name, timeout=timeout)
        self._dataset = doc.get("dataset") or _sub_dataset(name)
        self.watermark = int(watermark)
        self.head = int(watermark)
        self.mode = doc.get("mode", "arrowcol")
        self.schema: Optional[Schema] = (
            Schema.from_dict(doc["schema"]) if doc.get("schema") else None)
        self.sub_id = sub_id or f"{os.getpid()}-{next(self._ids)}"
        self._apply = apply
        self._link = link
        self._cv = threading.Condition()
        self._queue: "deque[EpochDelta]" = deque()
        # bounded queue = real backpressure: a subscriber that stops
        # polling stops draining its ring, the publisher's sender blocks,
        # and retention eviction heals it with a snapshot on resume
        self._queue_max = int(queue_max)
        self._received = int(watermark)   # dedup floor (broadcast overlap)
        self._ended = False
        self._error: Optional[BaseException] = None
        self._closed = False
        self.stats = SubStats()
        self._ring: Optional[ShmRing] = None
        self._renewer: Optional[LeaseRenewer] = None

        self._transport = self._rendezvous(
            transport, broadcast, group, streams, shm_capacity,
            lease_s, timeout, host)

        self._recv_thread = threading.Thread(
            target=self._recv_loop, daemon=True,
            name=f"pipegen-sub-recv-{name}-{self.sub_id}")
        self._recv_thread.start()

    # -- rendezvous -------------------------------------------------------------
    def _rendezvous(self, transport: str, broadcast: int, group: str,
                    streams: int, shm_capacity: int,
                    lease_s: Optional[float], timeout: float,
                    host: str) -> Transport:
        d = self._directory
        if broadcast > 1:
            if transport != "shm":
                raise SubscribeError(
                    "broadcast subscriptions require the shm transport")
            # R colocated subscribers share one ring: first joiner creates
            # and registers it (its watermark seeds the group's resume
            # point — co-subscribers should join at the same watermark)
            slot, ep = d.join_broadcast(
                self._dataset, _SUB_QUERY, readers=broadcast,
                timeout=timeout)
            if ep is None:
                ring = acquire_broadcast_ring(shm_capacity, broadcast)
                d.publish_broadcast(
                    self._dataset,
                    Endpoint(shm_name=ring.name, shm_capacity=ring.capacity,
                             broadcast=broadcast, shared=True,
                             resume_seq=self.watermark),
                    _SUB_QUERY, import_workers=1)
            else:
                ring = ShmRing.attach(ep.shm_name, role="reader", slot=slot)
            self._ring = ring
            return ShmRingTransport(ring, self._link)
        if streams > 1:
            # striped remote subscription: N member sockets, one logical pipe
            members: List[Endpoint] = []
            socks = []
            for _ in range(streams):
                ls = listen_socket(host)
                socks.append(ls)
                members.append(Endpoint(host=host, port=ls.getsockname()[1]))
            ep = Endpoint(members=tuple(members), resume_seq=self.watermark)
            d.register(self._dataset, ep, _SUB_QUERY, lease_s=lease_s)
            self._start_renewer(lease_s)
            parts = []
            for ls in socks:
                ls.settimeout(timeout)
                conn, _ = ls.accept()
                ls.close()
                parts.append(SocketTransport(conn, self._link))
            return StripedReceiver(parts)
        if transport == "channel":
            ch = Channel()
            d.register(self._dataset, Endpoint(channel=ch),
                       _SUB_QUERY, lease_s=lease_s)
            self._start_renewer(lease_s)
            return ChannelTransport(ch, self._link)
        if transport == "shm":
            ring = acquire_ring(shm_capacity)
            d.register(self._dataset,
                       Endpoint(shm_name=ring.name,
                                shm_capacity=ring.capacity,
                                resume_seq=self.watermark),
                       _SUB_QUERY, lease_s=lease_s)
            self._start_renewer(lease_s)
            self._ring = ring
            return ShmRingTransport(ring, self._link)
        if transport == "socket":
            ls = listen_socket(host)
            d.register(self._dataset,
                       Endpoint(host=host, port=ls.getsockname()[1],
                                resume_seq=self.watermark),
                       _SUB_QUERY, lease_s=lease_s)
            self._start_renewer(lease_s)
            ls.settimeout(timeout)
            conn, _ = ls.accept()
            ls.close()
            return SocketTransport(conn, self._link)
        raise SubscribeError(f"unknown subscription transport {transport!r}")

    def _start_renewer(self, lease_s: Optional[float]) -> None:
        # satellite fix: the renewer belongs to the *subscription handle*,
        # not to any single transfer — it heartbeats until close()
        if not lease_s or not hasattr(self._directory, "renew"):
            return
        self._renewer = LeaseRenewer(
            lambda ls: self._directory.renew(
                self._dataset, _SUB_QUERY, lease_s=ls),
            lease_s, on_lost=self._on_lease_lost,
            name=f"pipegen-sub-renew-{self.name}").start()

    def _on_lease_lost(self) -> None:
        if self._ring is not None:
            try:
                self._ring.abort(
                    f"subscription lease on {self.name!r} expired")
            except Exception:
                pass

    # -- receive path -----------------------------------------------------------
    def _recv_loop(self) -> None:
        tr = self._transport
        try:
            kind, payload = tr.recv_frame()
            if kind == FRAME_SCHEMA:
                schema, meta = decode_schema(bytes(payload))
                self.schema = schema
                wire = get_wire_format(meta.get("mode", self.mode))
                codec = get_codec(meta.get("codec", "none"))
                while True:
                    kind, payload = tr.recv_frame()
                    if kind == FRAME_EOF:
                        break
                    if kind != FRAME_EPOCH:
                        continue  # tolerate stray frames (verify etc.)
                    hdr = decode_epoch_header(payload)
                    blocks: List[ColumnBlock] = []
                    for _ in range(int(hdr.get("blocks", 1))):
                        k2, data = tr.recv_frame()
                        if k2 == FRAME_EOF:
                            raise BrokenPipeError(
                                "publication ended mid-epoch")
                        if k2 != FRAME_BLOCK:
                            raise IOError(
                                f"expected block frame, got {k2!r}")
                        # decode immediately: shm payloads are in-place
                        # views consumed by the next recv
                        blocks.append(wire.decode_block(
                            codec.decompress(data), schema))
                    self._on_epoch(hdr, blocks)
            elif kind != FRAME_EOF:
                raise IOError(f"expected schema hello, got {kind!r}")
        except BaseException as e:
            with self._cv:
                if not self._closed:
                    self._error = e
                self._ended = True
                self._cv.notify_all()
        else:
            with self._cv:
                self._ended = True
                self._cv.notify_all()

    def _on_epoch(self, hdr: Dict[str, Any],
                  blocks: List[ColumnBlock]) -> None:
        epoch = int(hdr.get("epoch", 0))
        kind = hdr.get("kind", "delta")
        head = int(hdr.get("head", epoch))
        ts = float(hdr.get("ts", 0.0))
        with self._cv:
            while (self._queue_max and len(self._queue) >= self._queue_max
                   and not self._closed):
                self._cv.wait(0.2)
            self.head = max(self.head, head)
            if epoch <= self._received:
                # broadcast rings share one stream: co-subscribers with a
                # lower watermark see replays this handle already applied
                self.stats.duplicates += 1
                return
            self._received = epoch
            delta = EpochDelta(epoch, kind, blocks,
                               int(hdr.get("rows", 0)), ts, head)
            self._queue.append(delta)
            self.stats.epochs += 1
            if kind == "snapshot":
                self.stats.snapshots += 1
            self.stats.rows += delta.rows
            self._cv.notify_all()
        self._lag_gauges(ts)

    def _lag_gauges(self, ts: float = 0.0) -> None:
        reg = telemetry.registry()
        labels = {"pub": self.name, "sub": self.sub_id}
        reg.gauge("pipe.subscription.lag_epochs", **labels).set(
            max(0, self.head - self.watermark))
        if ts:
            reg.gauge("pipe.subscription.lag_seconds", **labels).set(
                max(0.0, time.time() - ts))

    # -- consumer API -----------------------------------------------------------
    def poll(self, timeout: float = 0.0,
             max_epochs: Optional[int] = None) -> List[EpochDelta]:
        """Epochs received since the last poll, oldest first.  Blocks up
        to ``timeout`` seconds for at least one (0 = non-blocking).
        Raises :class:`PublicationEnded` once the publisher is gone *and*
        the queue is drained."""
        deadline = time.monotonic() + timeout if timeout else None
        out: List[EpochDelta] = []
        with self._cv:
            while True:
                while self._queue and (max_epochs is None
                                       or len(out) < max_epochs):
                    out.append(self._queue.popleft())
                if out:
                    self._cv.notify_all()  # wake a backpressured receiver
                if out or self._closed:
                    break
                if self._ended:
                    raise PublicationEnded(
                        f"publication {self.name!r} ended "
                        f"(watermark {self.watermark})",
                        watermark=self.watermark) from self._error
                if deadline is None:
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cv.wait(remaining)
        for delta in out:
            if self._apply is not None:
                self._apply(delta)
            self.watermark = delta.epoch
        if out:
            self._lag_gauges()
        return out

    @property
    def lag_epochs(self) -> int:
        return max(0, self.head - self.watermark)

    @property
    def ended(self) -> bool:
        with self._cv:
            return self._ended and not self._queue

    def close(self) -> None:
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()
        if self._ring is not None:
            try:
                self._ring.abort("subscription closed")
            except Exception:
                pass
        try:
            self._transport.close()
        except Exception:
            pass
        self._recv_thread.join(5.0)
        if self._renewer is not None:
            self._renewer.stop(join=True)
        reg = telemetry.registry()
        for g in ("lag_epochs", "lag_seconds"):
            reg.drop(f"pipe.subscription.{g}", kind="g",
                     pub=self.name, sub=self.sub_id)

    def __enter__(self) -> "Subscription":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


# -- module registry (pipetop / broker stats) --------------------------------------

_PUBS_LOCK = threading.Lock()
_PUBS: Dict[int, Publication] = {}


def _register_publication(pub: Publication) -> None:
    with _PUBS_LOCK:
        _PUBS[id(pub)] = pub


def _unregister_publication(pub: Publication) -> None:
    with _PUBS_LOCK:
        _PUBS.pop(id(pub), None)


def publications_snapshot() -> List[Dict[str, Any]]:
    """One row per live publication in this process — what pipetop's
    subscriptions table and ``PipeBroker.stats()`` serve."""
    with _PUBS_LOCK:
        pubs = list(_PUBS.values())
    return [p.snapshot_row() for p in pubs]


# -- factories ---------------------------------------------------------------------

def publish(name: str, schema: Optional[Schema] = None, *,
            initial: Optional[ColumnBlock] = None,
            **kw: Any) -> Publication:
    """Publish a relation under ``name``.  ``initial`` commits the current
    contents as epoch ``start_epoch + 1`` (a snapshot) so subscribers have
    a base image; pass ``schema`` alone to start empty."""
    if schema is None:
        if initial is None:
            raise SubscribeError("publish() needs a schema or an initial block")
        schema = initial.schema
    pub = Publication(name, schema, **kw)
    if initial is not None and len(initial):
        pub.commit_snapshot(initial)
    return pub


def subscribe(name: str, **kw: Any) -> Subscription:
    """Subscribe to publication ``name`` at ``watermark`` (default 0 = from
    the beginning; the publisher decides replay vs snapshot per its log)."""
    return Subscription(name, **kw)


def apply_to_engine(engine: Any, table: str) -> Callable[[EpochDelta], None]:
    """An ``apply=`` callback that maintains ``engine[table]`` from the
    epoch stream: snapshots replace the table, deltas append to it."""
    def _apply(delta: EpochDelta) -> None:
        if delta.kind == "snapshot" or table not in engine.tables:
            engine.put_block(table, delta.block)
        else:
            engine.append(table, delta.block)
    return _apply
