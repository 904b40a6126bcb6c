"""Live run watcher: poll an obs endpoint and render a terminal status.

The shell-side half of the old tpu_watch.sh workflow (tailing logs to
see whether a run is making progress) is replaced by polling the
runner's live observability endpoint (oversim_tpu/obs/): /statusz for
the run snapshot (tick, window, checkpoint age, request counts) and
/metrics for the counter deltas between polls — so the watcher shows
RATES (windows/s, requests/s) computed host-side from two scrapes, not
just totals.  Curses-free: one ANSI home+clear per refresh, plain
stdlib urllib, works over any port-forwarded tunnel.

Usage:
  python scripts/obs_watch.py http://127.0.0.1:9100 [--interval 2]
  python scripts/obs_watch.py 9100 --once        # one snapshot, no ANSI
"""

import argparse
import json
import re
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from oversim_tpu.obs.metrics import parse_exposition  # noqa: E402

# metrics whose per-second rate is worth a line (counter families)
_RATED = ("oversim_windows_total", "oversim_requests_settled_total",
          "oversim_fleet_ticks_done", "oversim_requests_nacked_total",
          "oversim_gateway_rx_shed_total")

# autoscale / admission families shown as current values when present
# (gauges + slow counters — a rate line would round to 0.00/s)
_LEVELS = ("oversim_autoscale_workers_target",
           "oversim_autoscale_backlog_rows",
           "oversim_autoscale_backlog_per_worker",
           "oversim_autoscale_scale_ups_total",
           "oversim_autoscale_scale_downs_total",
           "oversim_autoscale_deferred_total",
           "oversim_gateway_rx_frames_total",
           "oversim_gateway_rx_dropped_total",
           "oversim_gateway_rx_socket_errors_total",
           "oversim_gateway_rx_shed_total")


def _fetch(url: str, timeout: float):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode("utf-8", "replace")


def scrape(base: str, timeout: float = 5.0) -> dict:
    """One poll: healthz status + statusz doc + parsed metric samples.
    Network errors land in ``"error"`` instead of raising — a watcher
    must survive the runner restarting."""
    out = {"t": time.monotonic(), "error": None, "health": None,
           "statusz": None, "metrics": None}
    try:
        code, body = _fetch(base + "/healthz", timeout)
        out["health"] = json.loads(body).get("status")
    except urllib.error.HTTPError as e:     # 503 draining is an answer
        try:
            out["health"] = json.loads(
                e.read().decode("utf-8", "replace")).get("status")
        except Exception:  # noqa: BLE001
            out["health"] = f"http {e.code}"
    except Exception as e:  # noqa: BLE001
        out["error"] = f"{type(e).__name__}: {e}"
        return out
    try:
        _, body = _fetch(base + "/statusz", timeout)
        out["statusz"] = json.loads(body)
        _, body = _fetch(base + "/metrics", timeout)
        out["metrics"] = parse_exposition(body)
    except Exception as e:  # noqa: BLE001
        out["error"] = f"{type(e).__name__}: {e}"
    return out


# per-tenant request families (daemon tier, service/tenant.py): one
# labelled series per tenant on the shared registry
_TENANT_CTR = re.compile(
    r'^oversim_tenant_requests_(minted|settled|nacked)_total'
    r'\{tenant="(\d+)"\}$')
_TENANT_BUCKET = re.compile(
    r'^oversim_tenant_request_window_latency_bucket'
    r'\{le="([^"]+)",tenant="(\d+)"\}$|'
    r'^oversim_tenant_request_window_latency_bucket'
    r'\{tenant="(\d+)",le="([^"]+)"\}$')


def _bucket_p99(buckets: list) -> float | None:
    """p99 estimate from cumulative ``(le, count)`` pairs."""
    if not buckets:
        return None
    buckets = sorted(buckets, key=lambda b: b[0])
    total = buckets[-1][1]
    if total <= 0:
        return None
    rank = 0.99 * total
    for le, cum in buckets:
        if cum >= rank:
            return le
    return buckets[-1][0]


def tenant_panel(metrics: dict) -> list:
    """Render per-tenant request counters + window-latency p99 lines
    from one parsed /metrics scrape ([] when no tenant series)."""
    tenants: dict = {}
    for key, value in metrics.items():
        m = _TENANT_CTR.match(key)
        if m:
            tenants.setdefault(int(m.group(2)), {})[m.group(1)] = value
            continue
        m = _TENANT_BUCKET.match(key)
        if m:
            le = m.group(1) if m.group(1) is not None else m.group(4)
            tid = int(m.group(2) if m.group(2) is not None else m.group(3))
            if le != "+Inf":
                tenants.setdefault(tid, {}).setdefault(
                    "buckets", []).append((float(le), value))
    if not tenants:
        return []
    lines = ["per-tenant:",
             f"  {'tenant':>6} {'minted':>9} {'settled':>9} "
             f"{'nacked':>9} {'p99_w':>7}"]
    for tid in sorted(tenants):
        t = tenants[tid]
        p99 = _bucket_p99(t.get("buckets", []))
        lines.append(
            f"  {tid:>6} {t.get('minted', 0):>9.0f} "
            f"{t.get('settled', 0):>9.0f} {t.get('nacked', 0):>9.0f} "
            f"{p99 if p99 is not None else '-':>7}")
    return lines


def render(cur: dict, prev: dict | None) -> str:
    lines = []
    if cur["error"]:
        lines.append(f"endpoint error: {cur['error']}")
        return "\n".join(lines)
    lines.append(f"health: {cur['health']}")
    st = cur.get("statusz") or {}
    for key in ("role", "window", "tick", "t_sim", "alive",
                "windows_done", "checkpoints_written",
                "checkpoint_age_s", "replicas", "ingest_rate"):
        if key in st and st[key] is not None:
            lines.append(f"{key:22s} {st[key]}")
    if isinstance(st.get("requests"), dict):
        r = st["requests"]
        lines.append(f"{'requests':22s} minted={r.get('minted')} "
                     f"settled={r.get('settled')} "
                     f"nacked={r.get('nacked')} "
                     f"outstanding={r.get('outstanding')}")
    if isinstance(st.get("fleet"), dict):
        f = st["fleet"]
        lines.append(f"{'fleet':22s} "
                     f"{f.get('workers_reporting')}/{f.get('workers_total')}"
                     f" reporting, ticks {f.get('ticks_done')}/"
                     f"{f.get('ticks_target')}, retries "
                     f"{f.get('retries')}")
    m = cur.get("metrics") or {}
    lines.extend(tenant_panel(m))
    shown = [fam for fam in _LEVELS if fam in m]
    if shown:
        lines.append("autoscale/admission:")
        for fam in shown:
            lines.append(f"  {fam:40s} {m[fam]:12.0f}")
    if prev and prev.get("metrics") and not prev.get("error"):
        dt = cur["t"] - prev["t"]
        if dt > 0:
            for fam in _RATED:
                if fam in m and fam in prev["metrics"]:
                    rate = (m[fam] - prev["metrics"][fam]) / dt
                    lines.append(f"{fam:38s} {m[fam]:12.0f}  "
                                 f"({rate:+.2f}/s)")
    flight = st.get("flight")
    if isinstance(flight, dict):
        lines.append(f"{'flight':22s} {flight.get('events_total')} events"
                     f" -> {flight.get('path')}")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("endpoint",
                    help="obs endpoint: URL, host:port, or bare port "
                    "(localhost)")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--timeout", type=float, default=5.0)
    ap.add_argument("--once", action="store_true",
                    help="one snapshot to stdout (no ANSI refresh)")
    ap.add_argument("--max-polls", type=int, default=None,
                    help="stop after N polls (default: forever)")
    args = ap.parse_args()

    base = args.endpoint
    if base.isdigit():
        base = f"http://127.0.0.1:{base}"
    elif "://" not in base:
        base = f"http://{base}"
    base = base.rstrip("/")

    prev = None
    polls = 0
    while True:
        cur = scrape(base, timeout=args.timeout)
        body = render(cur, prev)
        if args.once:
            print(body)
            return 0 if not cur["error"] else 1
        # ANSI home + clear-below: a live refresh without curses
        sys.stdout.write("\x1b[H\x1b[J")
        sys.stdout.write(f"obs_watch {base}  "
                         f"{time.strftime('%H:%M:%S')}\n\n")
        sys.stdout.write(body + "\n")
        sys.stdout.flush()
        prev = cur
        polls += 1
        if args.max_polls is not None and polls >= args.max_polls:
            return 0
        time.sleep(args.interval)


if __name__ == "__main__":
    sys.exit(main())
