"""CI smoke for the live observability plane (obs_smoke gate).

Four proofs, end to end against REAL processes:

  1. serving+scraping — scripts/service_run.py with an ephemeral
     metrics endpoint and in-process synthetic ingest; two mid-run
     /metrics scrapes must show monotone counters and a ready /healthz,
  2. graceful drain — SIGTERM flips /healthz to 503 "draining" during
     the --term-grace window, the process still exits 0, the flight
     JSONL parses line-by-line, and the SIGTERM tail dump exists,
  3. loadgen — scripts/loadgen.py answers every synthetic request and
     prints the p50/p99 request-to-response latency table (its report
     and latency-histogram SVG land in --dir), and
  4. do-no-harm — the graph-contract analyzer run WITH the obs plane
     armed in-process (OVERSIM_OBS_ARMED=1) produces the same verdict
     as the obs-off baseline: same entries, same per-entry HLO/trace
     stats (compile wall seconds excluded — timing is not a graph
     property).  The baseline reuses $OVERSIM_ANALYSIS_VERDICT when
     run_suite.sh's analyze gate already produced one.

Exit 0 only if all four hold.
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from oversim_tpu.obs.metrics import parse_exposition  # noqa: E402

PY = [sys.executable]
ENV = dict(os.environ, JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"))

# counters that MUST move while the service drains windows
MONOTONE = ("oversim_windows_total", "oversim_requests_minted_total",
            "oversim_requests_settled_total")


def log(msg):
    print(f"[obs_smoke] {msg}", flush=True)


def _get(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def _wait_obs_port(proc, deadline_s=240.0):
    """Read the child's stdout until the '"phase": "obs"' record."""
    t0 = time.monotonic()
    lines = []
    while time.monotonic() - t0 < deadline_s:
        line = proc.stdout.readline()
        if not line:
            break
        lines.append(line)
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if rec.get("phase") == "obs" and rec.get("metrics_port"):
            return int(rec["metrics_port"]), lines
    raise SystemExit(f"no obs phase record from service_run; got: {lines}")


def smoke_service(workdir: Path) -> None:
    flight = workdir / "service_flight.jsonl"
    # enough windows that SIGTERM always lands mid-run (the per-window
    # stdout stream is drained by a thread so the child never blocks)
    cmd = PY + [str(ROOT / "scripts" / "service_run.py"),
                "--n", "8", "--overlay", "chord", "--windows", "100000",
                "--window-sim-s", "0.1", "--chunk", "8",
                "--engine-window", "0.02",
                "--ingest-rate", "2", "--ingest-clients", "2",
                "--metrics-port", "0", "--flight", str(flight),
                "--term-grace", "4",
                "--out", str(workdir / "service_artifact.json")]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=ENV)
    drained = []
    try:
        port, _ = _wait_obs_port(proc)
        t = threading.Thread(target=lambda: drained.extend(proc.stdout),
                             daemon=True)
        t.start()
        base = f"http://127.0.0.1:{port}"
        log(f"service obs endpoint up on {base}")

        code, body = _get(base + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "ready", body

        # two scrapes with windows draining in between: counters must
        # be present and strictly monotone (>= with real progress on
        # at least the window counter)
        first = parse_exposition(_get(base + "/metrics")[1])
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline:
            time.sleep(1.0)
            second = parse_exposition(_get(base + "/metrics")[1])
            if second.get("oversim_windows_total", 0) > first.get(
                    "oversim_windows_total", 0):
                break
        else:
            raise SystemExit("oversim_windows_total never advanced")
        for fam in MONOTONE:
            assert fam in first and fam in second, f"missing {fam}"
            assert second[fam] >= first[fam], \
                f"{fam} went backwards: {first[fam]} -> {second[fam]}"
        log("counters monotone across scrapes "
            f"(windows {first['oversim_windows_total']:.0f} -> "
            f"{second['oversim_windows_total']:.0f})")

        st = json.loads(_get(base + "/statusz")[1])
        for key in ("role", "overlay", "windows_done", "flight"):
            assert key in st, f"statusz missing {key}: {st}"

        # graceful drain: SIGTERM, then healthz must serve 503
        # "draining" during the --term-grace window
        proc.send_signal(signal.SIGTERM)
        draining = False
        for _ in range(40):
            try:
                _get(base + "/healthz", timeout=2.0)
            except urllib.error.HTTPError as e:
                if e.code == 503:
                    doc = json.loads(e.read().decode())
                    assert doc["status"] == "draining", doc
                    draining = True
                    break
            except OSError:
                break                       # endpoint already closed
            time.sleep(0.2)
        assert draining, "healthz never flipped to draining after SIGTERM"
        log("healthz flipped ready -> draining on SIGTERM")

        proc.wait(timeout=300)
        assert proc.returncode == 0, (
            f"service_run exited {proc.returncode}:\n"
            + "".join(drained)[-2000:])
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    # flight stream parses line-by-line; the SIGTERM tail dump exists
    events = [json.loads(line) for line in
              flight.read_text().splitlines()]
    kinds = {e["kind"] for e in events}
    assert {"obs_start", "window_dispatched", "window_fetched",
            "draining"} <= kinds, kinds
    tail_path = str(flight) + ".tail.json"
    assert os.path.exists(tail_path), "SIGTERM tail dump missing"
    tail_doc = json.loads(open(tail_path).read())
    assert tail_doc["kind"] == "flight_tail" and tail_doc["tail"]
    log(f"flight recorder: {len(events)} events parsed, tail dumped")


def smoke_loadgen(workdir: Path) -> None:
    out = workdir / "loadgen_report.json"
    svg = workdir / "loadgen_latency.svg"
    cmd = PY + [str(ROOT / "scripts" / "loadgen.py"),
                "--clients", "3", "--rate", "4", "--windows", "4",
                "--n", "4", "--out", str(out), "--svg", str(svg)]
    r = subprocess.run(cmd, capture_output=True, text=True, env=ENV,
                       timeout=600)
    assert r.returncode == 0, f"loadgen failed:\n{r.stdout}\n{r.stderr}"
    assert "request-to-response latency" in r.stdout, r.stdout
    rep = json.loads(out.read_text())
    assert rep["answered"] == rep["submitted"] > 0, rep
    assert rep["wrong_payloads"] == 0, rep
    assert rep["percentiles"]["wall_s"]["p99"] is not None
    assert svg.read_text().startswith("<svg")
    log(f"loadgen: {rep['answered']}/{rep['submitted']} answered, "
        f"p50 {rep['percentiles']['wall_s']['p50'] * 1e3:.2f}ms "
        f"p99 {rep['percentiles']['wall_s']['p99'] * 1e3:.2f}ms")


def _strip_timings(doc):
    """Recursively drop wall-clock keys — timing is not a graph fact."""
    if isinstance(doc, dict):
        return {k: _strip_timings(v) for k, v in doc.items()
                if k != "compile_seconds"}
    if isinstance(doc, list):
        return [_strip_timings(v) for v in doc]
    return doc


def _analyze(json_path: Path, *, armed: bool) -> dict:
    env = dict(ENV)
    env.pop("OVERSIM_ANALYSIS_VERDICT", None)   # fresh verdict, no reuse
    if armed:
        env["OVERSIM_OBS_ARMED"] = "1"
    else:
        env.pop("OVERSIM_OBS_ARMED", None)
    cmd = PY + [str(ROOT / "scripts" / "analyze.py"), "--all", "--fast",
                "--json", str(json_path)]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=2400)
    assert r.returncode == 0, \
        f"analyze ({'armed' if armed else 'baseline'}) failed:\n" \
        f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    if armed:
        assert "obs armed: metrics endpoint on port" in r.stderr \
            or "obs armed: metrics endpoint on port" in r.stdout, \
            "armed analyze never started the RunObserver"
    return json.loads(json_path.read_text())


def smoke_analysis_unchanged(workdir: Path) -> None:
    baseline_path = os.environ.get("OVERSIM_ANALYSIS_VERDICT")
    if baseline_path and os.path.exists(baseline_path):
        log(f"baseline verdict reused from {baseline_path}")
        baseline = json.loads(open(baseline_path).read())
    else:
        log("no suite verdict; running obs-off baseline analyze")
        baseline = _analyze(workdir / "verdict_baseline.json", armed=False)
    armed = _analyze(workdir / "verdict_obs_armed.json", armed=True)

    assert baseline["ok"] and armed["ok"], (baseline["ok"], armed["ok"])
    b_hlo = _strip_timings(baseline["passes"]["hlo"]["entries"])
    a_hlo = _strip_timings(armed["passes"]["hlo"]["entries"])
    assert sorted(b_hlo) == sorted(a_hlo) and len(a_hlo) == 9, \
        f"entry sets differ: {sorted(b_hlo)} vs {sorted(a_hlo)}"
    for name in sorted(b_hlo):
        assert b_hlo[name] == a_hlo[name], (
            f"HLO stats for {name} changed with obs armed:\n"
            f"  baseline: {b_hlo[name]}\n  armed:    {a_hlo[name]}")
    b_tr = _strip_timings(baseline["passes"].get("trace") or {})
    a_tr = _strip_timings(armed["passes"].get("trace") or {})
    assert b_tr == a_tr, "trace stats changed with obs armed"
    log(f"analysis verdict unchanged with obs armed "
        f"({len(a_hlo)} entries, ok={armed['ok']})")


def main() -> int:
    with tempfile.TemporaryDirectory(prefix="obs_smoke_") as td:
        workdir = Path(td)
        smoke_service(workdir)
        smoke_loadgen(workdir)
        smoke_analysis_unchanged(workdir)
    log("OK: endpoints + drain + loadgen + analysis-unchanged all green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
