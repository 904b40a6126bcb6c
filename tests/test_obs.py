"""Live observability plane: metrics exposition, HTTP endpoints,
flight recorder, request tracing, fleet heartbeat rollup, and the AST
rule that keeps ``oversim_tpu.obs`` out of compiled-graph modules.

Everything here is host-side and stdlib-shaped — no jax in the units
under test — so the pins are exact-text/exact-value, not tolerance
bands.  Each test builds its own ``Registry`` (the process-global
``REGISTRY`` is shared with any runner in this process and must not be
polluted by unit tests).
"""

import json
import math
import signal
import urllib.error
import urllib.request

import pytest

from oversim_tpu.obs.flight import FlightRecorder
from oversim_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    escape_help,
    escape_label_value,
    format_value,
    parse_exposition,
)
from oversim_tpu.obs.requests import (RampLoad, RequestTracer,
                                      SyntheticLoad, percentile,
                                      ramp_profile)
from oversim_tpu.obs.runtime import RunObserver
from oversim_tpu.obs.server import DRAINING, READY, ObsServer


# ------------------------------------------------------------ metrics --


def test_counter_monotone_and_negative_refused():
    r = Registry()
    c = r.counter("oversim_test_total", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    assert c.value == 3.5


def test_registry_get_or_create_and_kind_conflict():
    r = Registry()
    a = r.counter("oversim_x_total")
    b = r.counter("oversim_x_total")
    assert a is b  # idempotent call sites
    # same (name, labels) as a different kind
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("oversim_x_total")
    # same family name with different labels but different kind
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("oversim_x_total", labels={"w": "0"})
    # distinct labels of the SAME kind are distinct series
    lab = r.counter("oversim_x_total", labels={"w": "0"})
    assert lab is not a


def test_bad_metric_and_label_names_rejected():
    r = Registry()
    with pytest.raises(ValueError, match="bad metric name"):
        r.counter("0starts_with_digit")
    with pytest.raises(ValueError, match="bad label name"):
        r.gauge("ok_name", labels={"bad-dash": "v"})


def test_format_value_pins():
    assert format_value(3.0) == "3"
    assert format_value(0.25) == "0.25"
    assert format_value(math.inf) == "+Inf"
    assert format_value(-math.inf) == "-Inf"


def test_escaping_pins():
    assert escape_help("a\\b\nc") == "a\\\\b\\nc"
    assert escape_label_value('say "hi"\n') == 'say \\"hi\\"\\n'


def test_histogram_buckets_sum_count_and_validation():
    h = Histogram("oversim_h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(105.0)
    # NON-cumulative per-bucket counts, +Inf last
    assert h.bucket_counts() == [1, 1, 1, 1]
    with pytest.raises(ValueError, match="ascending finite"):
        Histogram("oversim_bad", buckets=(2.0, 1.0))
    with pytest.raises(ValueError, match="ascending finite"):
        Histogram("oversim_bad", buckets=(1.0, math.inf))


def test_histogram_quantile():
    h = Histogram("oversim_q", buckets=(1.0, 2.0, 4.0))
    assert h.quantile(0.5) is None            # empty
    for v in (0.5, 0.5, 1.5, 1.5):
        h.observe(v)
    # rank 2 of 4 lands exactly on the first bucket's upper edge
    assert h.quantile(0.5) == pytest.approx(1.0)
    h.observe(50.0)                           # beyond last finite bound
    assert h.quantile(1.0) == pytest.approx(4.0)   # clamps to last edge
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_exposition_text_pins():
    r = Registry()
    c = r.counter("oversim_req_total", 'requests "in"\nflight')
    c.inc(2)
    g = r.gauge("oversim_g", labels={"role": 'a"b'})
    g.set(1.5)
    h = r.histogram("oversim_lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = r.render()
    lines = text.splitlines()
    # one HELP/TYPE header per family; HELP escaped
    assert '# HELP oversim_req_total requests "in"\\nflight' in lines
    assert "# TYPE oversim_req_total counter" in lines
    assert "oversim_req_total 2" in lines
    # label-value escaping
    assert 'oversim_g{role="a\\"b"} 1.5' in lines
    # cumulative buckets with +Inf, then _sum/_count
    assert 'oversim_lat_bucket{le="0.1"} 1' in lines
    assert 'oversim_lat_bucket{le="1"} 1' in lines
    assert 'oversim_lat_bucket{le="+Inf"} 2' in lines
    assert "oversim_lat_sum 5.05" in lines
    assert "oversim_lat_count 2" in lines
    # OpenMetrics terminator, trailing newline
    assert lines[-1] == "# EOF"
    assert text.endswith("# EOF\n")


def test_parse_exposition_roundtrip_and_monotonicity():
    r = Registry()
    c = r.counter("oversim_w_total")
    c.inc(3)
    first = parse_exposition(r.render())
    assert first["oversim_w_total"] == 3.0
    c.inc(2)
    second = parse_exposition(r.render())
    # the scrape-side monotonicity check obs_smoke relies on
    assert second["oversim_w_total"] >= first["oversim_w_total"]
    assert second["oversim_w_total"] == 5.0
    # labeled sample keys keep their literal suffix
    g = r.gauge("oversim_g", labels={"role": "svc"})
    g.set(7)
    assert parse_exposition(r.render())['oversim_g{role="svc"}'] == 7.0


# ------------------------------------------------------------- server --


def _get(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def test_obs_server_endpoints_and_draining_flip():
    r = Registry()
    r.counter("oversim_t_total").inc(4)
    srv = ObsServer(r, port=0, statusz=lambda: {"window": 7})
    try:
        port = srv.start()
        assert port > 0 and srv.port == port
        base = srv.url()

        code, body = _get(base + "/metrics")
        assert code == 200
        assert parse_exposition(body)["oversim_t_total"] == 4.0

        code, body = _get(base + "/healthz")
        assert code == 200
        assert json.loads(body)["status"] == READY

        code, body = _get(base + "/statusz")
        doc = json.loads(body)
        assert doc["window"] == 7 and doc["health"] == READY

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/nope")
        assert ei.value.code == 404

        # SIGTERM path: ready -> draining flips healthz to 503
        srv.set_health(DRAINING)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read().decode())["status"] == DRAINING
        with pytest.raises(ValueError):
            srv.set_health("upside-down")
    finally:
        srv.stop()


def test_obs_server_statusz_error_contained():
    def boom():
        raise RuntimeError("scrape bug")

    srv = ObsServer(Registry(), port=0, statusz=boom)
    try:
        srv.start()
        code, body = _get(srv.url() + "/statusz")
        assert code == 200
        assert json.loads(body)["statusz_error"] == "scrape bug"
    finally:
        srv.stop()


# ------------------------------------------------------------- flight --


def test_flight_ring_truncation_and_stream(tmp_path):
    p = tmp_path / "flight.jsonl"
    fr = FlightRecorder(str(p), capacity=4)
    for i in range(10):
        fr.event("tick", i=i)
    fr.close()
    assert fr.events_total == 10
    assert fr.dropped == 6
    # ring keeps only the LAST capacity events
    assert [e["i"] for e in fr.tail()] == [6, 7, 8, 9]
    # ...but the stream on disk has all of them, one JSON per line
    docs = [json.loads(line) for line in p.read_text().splitlines()]
    assert [d["i"] for d in docs] == list(range(10))
    assert all(d["kind"] == "tick" and "wall" in d and "mono" in d
               for d in docs)
    s = fr.summary()
    assert s == {"path": str(p), "events_total": 10, "ring": 4,
                 "capacity": 4}


def test_flight_dump_tail(tmp_path):
    p = tmp_path / "f.jsonl"
    fr = FlightRecorder(str(p), capacity=8)
    fr.event("a")
    fr.event("b", detail="x")
    out = fr.dump_tail()
    fr.close()
    assert out == str(p) + ".tail.json"
    doc = json.loads(open(out).read())
    assert doc["kind"] == "flight_tail"
    assert doc["events_total"] == 2
    assert [e["kind"] for e in doc["tail"]] == ["a", "b"]


def test_flight_signal_install_chains_and_dumps(tmp_path):
    p = tmp_path / "sig.jsonl"
    fr = FlightRecorder(str(p), capacity=8)
    fr.event("pre")
    seen = []
    prev = signal.signal(signal.SIGUSR1, lambda s, f: seen.append(s))
    try:
        fr.install(signals=(signal.SIGUSR1,), excepthook=False)
        signal.raise_signal(signal.SIGUSR1)
        # the recorder logged + dumped, then CHAINED to the old handler
        assert seen == [signal.SIGUSR1]
        doc = json.loads(open(str(p) + ".tail.json").read())
        assert [e["kind"] for e in doc["tail"]] == ["pre", "signal"]
        fr.uninstall()
        assert signal.getsignal(signal.SIGUSR1) is not fr
    finally:
        signal.signal(signal.SIGUSR1, prev)
        fr.close()


def test_flight_no_path_keeps_ring_only():
    fr = FlightRecorder(None, capacity=2)
    fr.event("a")
    fr.event("b")
    fr.event("c")
    assert fr.path is None
    assert [e["kind"] for e in fr.tail()] == ["b", "c"]
    with pytest.raises(ValueError):
        FlightRecorder(None, capacity=0)


# ----------------------------------------------------------- requests --


def test_percentile_exact():
    assert percentile([], 0.5) is None
    assert percentile([3.0], 0.99) == 3.0
    vals = [1.0, 2.0, 3.0, 4.0]
    assert percentile(vals, 0.5) == pytest.approx(2.5)
    assert percentile(vals, 0.0) == 1.0
    assert percentile(vals, 1.0) == 4.0


def test_tracer_mint_settle_window_math():
    t = [0.0]
    tr = RequestTracer(Registry(), keep_samples=True,
                       clock=lambda: t[0])
    tr.mint("s1", window=3)
    t[0] = 0.25
    wall, windows = tr.settle("s1", window=5)
    assert wall == pytest.approx(0.25)
    # injected before window 3, drained after window 5 -> 3 windows
    assert windows == 3
    # same-window turnaround is 1, never 0
    tr.mint("s2", window=4)
    assert tr.settle("s2", window=4)[1] == 1
    assert tr.minted.value == 2 and tr.settled.value == 2
    assert tr.outstanding() == 0
    assert tr.samples_windows == [3, 1]


def test_tracer_unmatched_and_duplicate_settle():
    tr = RequestTracer(Registry())
    assert tr.settle("ghost") is None
    tr.mint("s", window=0)
    assert tr.settle("s", window=0) is not None
    assert tr.settle("s", window=0) is None     # double drain
    assert tr.unmatched.value == 2
    assert tr.settled.value == 1


def test_tracer_percentiles_and_table():
    t = [0.0]
    tr = RequestTracer(Registry(), keep_samples=True,
                       clock=lambda: t[0])
    for i, wall in enumerate((0.010, 0.020, 0.030, 0.040)):
        t[0] = float(i)
        tr.mint(i, window=i)
        t[0] = float(i) + wall
        tr.settle(i, window=i + 1)
    p = tr.percentiles()
    assert p["exact"] is True and p["count"] == 4
    assert p["wall_s"]["p50"] == pytest.approx(0.025)
    assert p["windows"]["p50"] == 2.0
    tab = tr.table()
    assert "request-to-response latency (4 settled, exact)" in tab
    assert "wall_ms" in tab and "p99" in tab


def test_tracer_histogram_fallback_without_samples():
    tr = RequestTracer(Registry(), keep_samples=False)
    tr.mint("a", window=0)
    tr.settle("a", window=0)
    p = tr.percentiles()
    assert p["exact"] is False and p["count"] == 1
    assert "histogram-estimated" in tr.table()


class _FakeIngest:
    """InProcessIngest protocol double: submit/before/after + responses."""

    def __init__(self):
        self.submits = []
        self.before = 0
        self.after = 0
        self.responses = {}

    def submit(self, b, c):
        self.submits.append((b, c))
        return len(self.submits) - 1

    def before_window(self, state, target_ns):
        self.before += 1
        return state

    def after_window(self, state):
        self.after += 1
        return state


def test_synthetic_load_round_robin_and_cap():
    inner = _FakeIngest()
    load = SyntheticLoad(inner, clients=3, per_window=4, max_requests=6)
    load.before_window("st", 10)
    load.before_window("st", 20)
    load.after_window("st")
    # 4 in window 0, capped to 2 more in window 1; b round-robins
    assert inner.submits == [(0, 0), (1, 1), (2, 2), (0, 3), (1, 4), (2, 5)]
    assert load.submitted == 6
    assert load.sids == list(range(6))
    assert (inner.before, inner.after) == (2, 1)
    assert load.responses is inner.responses
    with pytest.raises(ValueError):
        SyntheticLoad(inner, clients=0)


# ---------------------------------------------------------- RunObserver --


def test_run_observer_window_and_loop_events(tmp_path):
    obs = RunObserver(role="test", registry=Registry(),
                      flight_path=str(tmp_path / "f.jsonl"))
    obs.set_static(overlay="chord", replicas=2)
    obs.on_window(0, {"_ticks": 64, "_t_sim": 1.0, "_alive": 8}, 0.5)
    obs.on_window(1, {"_ticks": 128, "_t_sim": 2.0, "_alive": 8}, 0.8)
    obs.loop_event("checkpoint_written", windows_done=2, path="ck")
    st = obs.statusz()
    assert st["role"] == "test"
    assert st["overlay"] == "chord" and st["replicas"] == 2
    assert st["window"] == 1 and st["tick"] == 128
    assert st["t_sim"] == 2.0 and st["alive"] == 8
    assert st["windows_done"] == 2
    assert st["checkpoints_written"] == 1
    assert st["checkpoint_age_s"] is not None
    assert st["flight"]["events_total"] == 1
    # wall histogram got the per-window DELTA, not the cumulative stamp
    assert obs.window_wall.count == 1
    assert obs.window_wall.sum == pytest.approx(0.3)
    obs.close()


def test_run_observer_endpoint_and_draining(tmp_path):
    tr = RequestTracer(Registry(), keep_samples=True)
    obs = RunObserver(role="svc", registry=tr.registry, port=0,
                      flight_path=str(tmp_path / "f.jsonl"), tracer=tr)
    try:
        port = obs.start()
        assert port and obs.describe() == {
            "metrics_port": port, "flight": str(tmp_path / "f.jsonl")}
        tr.mint("s", window=0)
        tr.settle("s", window=0)
        base = f"http://127.0.0.1:{port}"
        _, body = _get(base + "/statusz")
        doc = json.loads(body)
        assert doc["requests"] == {"minted": 1, "settled": 1,
                                   "nacked": 0, "outstanding": 0}
        obs.draining()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/healthz")
        assert ei.value.code == 503
    finally:
        obs.close(dump_tail=True)
    tail = json.loads(open(str(tmp_path / "f.jsonl") + ".tail.json").read())
    kinds = [e["kind"] for e in tail["tail"]]
    assert kinds == ["obs_start", "draining"]


def test_run_observer_no_port_is_endpointless():
    obs = RunObserver(role="bench", registry=Registry())
    assert obs.start() is None
    assert obs.describe() == {"metrics_port": None, "flight": None}
    obs.record("aot", enabled=False)
    assert obs.events.value == 1
    obs.close()


# -------------------------------------------------- fleet heartbeats --


def test_aggregate_heartbeats_rollup():
    from oversim_tpu.elastic.fleet import aggregate_heartbeats

    docs = {
        0: {"wall": 100.0, "ticks_done": 32, "ticks": 64, "retries": 1,
            "chunk_wall_s": 0.5},
        1: {"wall": 99.0, "ticks_done": 64, "ticks": 64, "retries": 0},
        2: None,                               # never wrote / torn file
    }
    agg = aggregate_heartbeats(docs, now=101.0)
    assert agg["workers_total"] == 3
    assert agg["workers_reporting"] == 2
    assert agg["ticks_done"] == 96 and agg["ticks_target"] == 128
    assert agg["retries"] == 1
    assert agg["heartbeat_age_max_s"] == pytest.approx(2.0)
    assert agg["per_worker"]["2"] is None
    w0 = agg["per_worker"]["0"]
    assert w0["age_s"] == pytest.approx(1.0)
    assert w0["chunk_wall_s"] == 0.5
    # no degradation to another platform exists any more: the rollup
    # carries no such key (PR 22)
    assert "degraded_to_cpu" not in agg and "degraded_to_cpu" not in w0
    # empty fleet: no ages, nothing reporting
    empty = aggregate_heartbeats({}, now=0.0)
    assert empty["workers_reporting"] == 0
    assert empty["heartbeat_age_max_s"] is None


# ------------------------------------------------------- obs-import rule --


def _lint(src, rel):
    from oversim_tpu.analysis.ast_pass import lint_source

    return lint_source(src, rel, rules=("obs-import",))


@pytest.mark.parametrize("src", [
    "import oversim_tpu.obs\n",
    "import oversim_tpu.obs.metrics\n",
    "from oversim_tpu.obs import RunObserver\n",
    "from oversim_tpu.obs.metrics import parse_exposition\n",
    "from oversim_tpu import obs\n",
])
def test_obs_import_rule_catches_all_forms(src):
    finds = _lint(src, "oversim_tpu/engine.py")
    assert len(finds) == 1
    assert finds[0].rule == "obs-import"


def test_obs_import_rule_allows_host_side_and_unrelated():
    assert _lint("from oversim_tpu import telemetry\n",
                 "oversim_tpu/engine.py") == []
    assert _lint("import observability\n", "oversim_tpu/engine.py") == []


def test_obs_import_rule_exempts_obs_package():
    from pathlib import Path

    from oversim_tpu.analysis.ast_pass import iter_targets

    root = Path(__file__).resolve().parent.parent
    targets = {rel: rules for _, rel, rules in iter_targets(root)}
    obs_rels = [r for r in targets if r.startswith("oversim_tpu/obs/")]
    assert obs_rels, "obs package must be scanned"
    assert all("obs-import" not in targets[r] for r in obs_rels)
    assert "obs-import" in targets["oversim_tpu/engine/sim.py"]


# ------------------------------------- admission control (ISSUE 17) --


def test_tracer_nack_closes_without_latency():
    t = [0.0]
    tr = RequestTracer(Registry(), keep_samples=True,
                       clock=lambda: t[0])
    tr.mint("s1", window=0)
    tr.mint("s2", window=0)
    t[0] = 5.0
    assert tr.nack("s1", window=1) is True
    # the refusal closed the trace but NEVER entered the histograms
    assert tr.nacked.value == 1 and tr.settled.value == 0
    assert tr.samples_wall_s == [] and tr.outstanding() == 1
    # a NACKed sid cannot settle later (same contract as double drain)
    assert tr.settle("s1", window=2) is None
    assert tr.unmatched.value == 1
    # unknown sid -> unmatched, not a crash
    assert tr.nack("ghost") is False
    assert tr.unmatched.value == 2
    # the accounting identity the smoke gate asserts:
    # minted == settled + nacked + outstanding
    assert tr.minted.value == tr.settled.value + tr.nacked.value \
        + tr.outstanding()


def test_ramp_profile_shape():
    # even window count: symmetric triangle ending at exactly 0
    assert ramp_profile(4, 8) == [1, 2, 3, 4, 3, 2, 1, 0]
    prof = ramp_profile(24, 12)
    assert max(prof) == 24 and prof[-1] == 0
    assert all(0 <= a <= 24 for a in prof)
    # rises to the peak then never rises again
    peak = prof.index(24)
    assert prof[:peak + 1] == sorted(prof[:peak + 1])
    assert prof[peak:] == sorted(prof[peak:], reverse=True)
    # odd window count still peaks at clients and lands on 0
    prof = ramp_profile(4, 5)
    assert max(prof) == 4 and prof[-1] == 0
    with pytest.raises(ValueError):
        ramp_profile(0, 8)
    with pytest.raises(ValueError):
        ramp_profile(4, 0)


def test_ramp_load_follows_profile_and_remembers_sends():
    inner = _FakeIngest()
    load = RampLoad(inner, clients=2, windows=4, per_client=2)
    assert load.profile == ramp_profile(2, 4)      # [1, 2, 1, 0]
    for _ in range(6):                             # 4 profile + 2 drain
        load.before_window("st", 10)
    load.after_window("st")
    # per window: per_client submissions per active client (client-major
    # order), b = client id, c = global serial; drain windows submit
    # nothing
    assert inner.submits == [(0, 0), (0, 1),
                             (0, 2), (0, 3), (1, 4), (1, 5),
                             (0, 6), (0, 7)]
    assert load.submitted == 8
    assert [(b, c) for _sid, b, c in load.sent] == inner.submits
    assert (inner.before, inner.after) == (6, 1)
    assert load.responses is inner.responses
    with pytest.raises(ValueError):
        RampLoad(inner, per_client=0)


def test_run_observer_overloaded_transitions(tmp_path):
    obs = RunObserver(role="test", registry=Registry(), port=0)
    port = obs.start()
    base = f"http://127.0.0.1:{port}"
    try:
        # ready -> overloaded: healthz serves 503 with the distinct state
        obs.overloaded(shed=3)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz", timeout=5)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "overloaded"
        # overloaded -> ready clears it
        obs.ready()
        with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
            assert json.loads(r.read())["status"] == "ready"
        # draining is terminal: neither overloaded() nor ready() move it
        obs.draining()
        obs.overloaded()
        obs.ready()
        assert obs.server.health == DRAINING
    finally:
        obs.close()
    # endpointless observer: the flips are harmless no-ops
    quiet = RunObserver(role="test", registry=Registry())
    quiet.overloaded()
    quiet.ready()
    quiet.close()


class _FakeRxSource:
    """Gateway/ingest double: bare integer rx_* counters (no rx_batches
    — attach must skip families the source does not carry)."""

    def __init__(self):
        self.rx_frames = 0
        self.rx_dropped = 0
        self.rx_shed = 0


def test_attach_rx_source_exports_and_deltas():
    r = Registry()
    obs = RunObserver(role="test", registry=r)
    src = _FakeRxSource()
    src.rx_frames, src.rx_shed = 5, 2
    obs.attach_rx_source(src)
    text = r.render()
    # present attrs exported (initial values synced), absent ones skipped
    assert "oversim_gateway_rx_frames_total 5" in text
    assert "oversim_gateway_rx_shed_total 2" in text
    assert "oversim_gateway_rx_dropped_total 0" in text
    assert "oversim_gateway_rx_batches_total" not in text
    # deltas flow through on_window's sync; counters stay monotone
    src.rx_frames, src.rx_shed = 9, 3
    obs.on_window(0, {}, 0.1)
    fam = parse_exposition(r.render())
    assert fam["oversim_gateway_rx_frames_total"] == 9.0
    assert fam["oversim_gateway_rx_shed_total"] == 3.0
    # statusz carries the raw source snapshot
    assert obs.statusz()["rx"] == {"rx_frames": 9, "rx_dropped": 0,
                                   "rx_shed": 3}
    obs.close()
