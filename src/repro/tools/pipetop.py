"""pipetop: a ``top``-style live view of a running PipeBroker.

Polls the broker's directory server over its ``stats`` RPC (see
:meth:`repro.core.broker.PipeBroker.stats`) and renders admission
pressure, per-tenant/QoS grants and rejects, live resource use, pool
occupancy, doorbell-hub activity and, where a pipe feeder shares the
broker's process, its consumer's input wait as a plain-terminal
dashboard::

    python -m repro.tools.pipetop --host 127.0.0.1 --port 7070

``--once`` prints a single frame (scriptable; used by tests), otherwise
the screen refreshes every ``--interval`` seconds until Ctrl-C.  Stdlib
only — the tool must work on a bare operator box.
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Any, Dict, List

__all__ = ["render", "main"]


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GiB"


def _fmt_s(v: Any) -> str:
    if not isinstance(v, (int, float)):
        return "-"
    if v < 1e-3:
        return f"{v * 1e6:.0f}us"
    if v < 1.0:
        return f"{v * 1e3:.1f}ms"
    return f"{v:.2f}s"


def _tenant_rows(stats: Dict[str, Any]) -> List[str]:
    """Per-tenant/QoS table: live use joined with grant/reject counters."""
    by_tenant = stats.get("active_by_tenant") or {}
    grants = stats.get("grants_by") or {}
    rejects = stats.get("rejects_by") or {}
    tenants = sorted(set(by_tenant)
                     | {k.split("/", 1)[0] for k in grants}
                     | {k.split("/", 1)[0] for k in rejects})
    rows = [f"  {'tenant':<14} {'rings':>6} {'segs':>6} {'bytes':>10} "
            f"{'grants':>14} {'rejects':>14}"]
    for t in tenants:
        use = by_tenant.get(t, [0, 0, 0])
        gr = ", ".join(f"{k.split('/', 1)[1]}={v}"
                       for k, v in sorted(grants.items())
                       if k.split("/", 1)[0] == t) or "0"
        rj = ", ".join(f"{k.split('/', 1)[1]}={v}"
                       for k, v in sorted(rejects.items())
                       if k.split("/", 1)[0] == t) or "0"
        rows.append(f"  {t:<14} {use[0]:>6} {use[1]:>6} "
                    f"{_fmt_bytes(use[2]):>10} {gr:>14} {rj:>14}")
    if len(rows) == 1:
        rows.append("  (no tenants yet)")
    return rows


def _subscription_rows(stats: Dict[str, Any]) -> List[str]:
    """Continuous-pipe publications table: one row per live publication
    (see :func:`repro.core.subscribe.publications_snapshot`)."""
    subs = stats.get("subscriptions") or []
    rows = [f"  {'name':<18} {'subs':>5} {'head':>8} {'min wm':>8} "
            f"{'lag':>6} {'log':>10} {'fallbacks':>10}"]
    for s in subs:
        head = s.get("head_epoch", 0)
        wm = s.get("min_watermark", 0)
        rows.append(
            f"  {str(s.get('name', '?')):<18} {s.get('subscribers', 0):>5} "
            f"{head:>8} {wm:>8} {max(0, head - wm):>6} "
            f"{_fmt_bytes(s.get('retained_bytes', 0)):>10} "
            f"{s.get('snapshot_fallbacks', 0):>10}")
    if len(rows) == 1:
        rows.append("  (no publications)")
    return rows


def _broker_health(stats: Dict[str, Any]) -> List[str]:
    """Crash-tolerance row: incarnation epoch, journal footprint, and
    the fencing/degraded-mode counters an operator checks first after a
    control-plane flap.  Hidden on pre-journal snapshots."""
    if "epoch" not in stats:
        return []
    j = stats.get("journal") or {}
    jtxt = (f"journal={_fmt_bytes(j.get('bytes', 0))}"
            f"/{j.get('records', 0)}rec"
            f" ckpts={j.get('checkpoints', 0)}" if j else "journal=off")
    rec = stats.get("recovered") or {}
    rtxt = (f" recovered(leases={rec.get('entries', 0)} "
            f"names={rec.get('names', 0)} "
            f"expired_tickets={rec.get('expired_tickets', 0)})"
            if rec else "")
    return [
        f"broker      epoch={stats.get('epoch', 0)} {jtxt} "
        f"stale_rejects={stats.get('stale_releases', 0)} "
        f"remote_tickets={stats.get('remote_tickets', 0)}" + rtxt,
    ]


def render(stats: Dict[str, Any], now: float = 0.0) -> str:
    """One dashboard frame from a broker ``stats`` snapshot.  Pure —
    takes the dict, returns the text — so tests can feed it canned or
    live snapshots without a terminal."""
    gw = stats.get("grant_wait") or {}
    lines = [
        f"pipetop — broker snapshot"
        + (f" @ {time.strftime('%H:%M:%S', time.localtime(now))}"
           if now else ""),
        "",
        f"admission   admitted={stats.get('admitted', 0)} "
        f"queued={stats.get('queued', 0)} "
        f"rejected={stats.get('rejected', 0)} "
        f"queue_depth={stats.get('waiting', 0)}",
        f"grant wait  n={gw.get('total', 0)} "
        f"p50={_fmt_s(gw.get('p50_s'))} p95={_fmt_s(gw.get('p95_s'))} "
        f"p99={_fmt_s(gw.get('p99_s'))}",
        f"live use    rings={stats.get('active_rings', 0)} "
        f"segments={stats.get('active_segments', 0)} "
        f"bytes={_fmt_bytes(stats.get('active_bytes', 0))} "
        f"fds={stats.get('fds', -1)}",
        *_broker_health(stats),
        "",
        "tenants",
        *_tenant_rows(stats),
        "",
        "subscriptions",
        *_subscription_rows(stats),
    ]
    qos = stats.get("active_by_qos") or {}
    if qos:
        lines.append("")
        lines.append("qos         " + "  ".join(
            f"{k}={v}" for k, v in sorted(qos.items())))
    if "hub_registered" in stats:
        lines.append(
            f"doorbells   registered={stats.get('hub_registered', 0)} "
            f"wakeups={stats.get('hub_wakeups', 0)} "
            f"waits={stats.get('hub_waits', 0)}")
    hists = (stats.get("metrics") or {}).get("histograms") or {}
    wait = hists.get("feeder.get_wait_s")
    if wait:
        # a feeder in the broker's process: its consumer's input wait
        lines.append(
            f"input wait  n={wait.get('total', 0)} "
            f"sum={_fmt_s(wait.get('sum'))} p50={_fmt_s(wait.get('p50'))} "
            f"p95={_fmt_s(wait.get('p95'))}")
    pool = stats.get("pool") or {}
    bpool = stats.get("buffer_pool") or {}
    if pool or bpool:
        lines.append(
            f"pools       shm_parked={pool.get('spsc_parked', 0)}"
            f"+{pool.get('broadcast_parked', 0)}bcast "
            f"bufs hit/miss={bpool.get('hits', 0)}/{bpool.get('misses', 0)} "
            f"retained={_fmt_bytes(bpool.get('bytes_retained', 0))}")
    return "\n".join(lines)


def main(argv: List[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="pipetop", description="live PipeBroker dashboard")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True,
                    help="broker directory-server port")
    ap.add_argument("--interval", type=float, default=1.0)
    ap.add_argument("--once", action="store_true",
                    help="print one frame and exit")
    args = ap.parse_args(argv)

    from repro.core.directory import DirectoryClient

    client = DirectoryClient(args.host, args.port)
    try:
        while True:
            try:
                stats = client.stats()
            except (OSError, IOError, ValueError) as e:
                print(f"pipetop: stats RPC failed: {e}", file=sys.stderr)
                return 1
            frame = render(stats, now=time.time())
            if args.once:
                print(frame)
                return 0
            # clear + home, like top(1); plain prints under a dumb term
            if sys.stdout.isatty():
                sys.stdout.write("\x1b[2J\x1b[H")
            print(frame, flush=True)
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
