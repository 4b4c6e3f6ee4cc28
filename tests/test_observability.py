"""Request-scoped tracing, cluster metrics plane, and SLO burn tracking
(ISSUE 15): per-request lifecycle timelines with histogram exemplars,
per-host metric snapshots aggregated on process 0, and declarative
objectives evaluated on the multi-window burn-rate rule — plus the
Prometheus text-format conformance and Chrome-trace process-metadata
satellites.
"""
import json
import math
import os
import re
import textwrap
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import monitoring as mon
from deeplearning4j_tpu import resilience
from deeplearning4j_tpu.monitoring import cluster
from deeplearning4j_tpu.monitoring import requests as reqmod
from deeplearning4j_tpu.monitoring import slo
from deeplearning4j_tpu.monitoring.registry import MetricsRegistry
from deeplearning4j_tpu.monitoring.requests import RequestLog
from deeplearning4j_tpu.parallel import coordination as coord_mod
from deeplearning4j_tpu.parallel.coordination import (LocalKV,
                                                      PeerCoordinator)


@pytest.fixture(autouse=True)
def _observability_clean():
    """Every test starts from (and leaves) clean process-global
    switches: monitoring off, request ring empty, no SLO tracker, no
    coordinator — earlier suite modules may have served traced
    requests into the global ring, and later modules must keep the
    zero-overhead fast path."""
    mon.disable()
    reqmod.log().clear()
    slo.clear_tracker()
    yield
    mon.disable()
    mon.get_tracer().clear()
    reqmod.log().clear()
    slo.clear_tracker()
    coord_mod.clear_coordinator()


# ===================== request-scoped tracing ==========================
def test_start_returns_none_when_disabled():
    mon.disable()
    assert reqmod.start("generation") is None
    # and nothing landed anywhere
    snap = reqmod.log().snapshot()
    assert snap["active"] == [] and snap["recent"] == []


def test_timeline_lifecycle_active_then_ring():
    mon.enable()
    tl = reqmod.start("generation", meta={"prompt_len": 3})
    assert tl is not None and tl.status is None
    tl.event("enqueue", queued=0)
    tl.event("admit", slot=1)
    tl.event("block", k=8, tokens=8)
    snap = reqmod.log().snapshot()
    assert [t["trace_id"] for t in snap["active"]] == [tl.trace_id]
    tl.finish("eos")
    snap = reqmod.log().snapshot()
    assert snap["active"] == []
    rec = snap["recent"][-1]
    assert rec["trace_id"] == tl.trace_id and rec["status"] == "eos"
    assert [e["event"] for e in rec["events"]] == ["enqueue", "admit",
                                                   "block"]
    assert rec["meta"] == {"prompt_len": 3}
    # event timestamps are monotone non-decreasing ms offsets
    ts = [e["t_ms"] for e in rec["events"]]
    assert ts == sorted(ts) and all(t >= 0 for t in ts)
    # lookup works from the ring after retirement, and is JSON-native
    assert reqmod.log().get(tl.trace_id) is tl
    json.dumps(snap)


def test_timeline_bounds_and_idempotent_finish():
    log = RequestLog(capacity=4)
    tl = log.start("inference", max_events=3)
    for i in range(10):
        tl.event(f"e{i}")
    assert len(tl.events) == 3 and tl.dropped == 7
    assert tl.snapshot()["dropped_events"] == 7
    tl.finish("ok")
    tl.finish("error")                     # first status wins
    assert tl.status == "ok"
    # ring capacity is a hard bound
    for i in range(9):
        log.start("inference").finish("ok")
    snap = log.snapshot(last=100)
    assert len(snap["recent"]) == 4 and snap["ring_capacity"] == 4
    # aged-out ids resolve to None, not a crash
    assert log.get(tl.trace_id) is None


def test_trace_ids_unique_across_requests():
    log = RequestLog(capacity=16)
    ids = {log.start("generation").trace_id for _ in range(16)}
    assert len(ids) == 16
    assert all(i.startswith("gen-") for i in ids)


# ===================== histogram exemplars =============================
def test_histogram_exemplars_link_tail_to_trace_ids():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for i in range(100):
        h.observe(float(i), trace_id=f"t-{i}")
    ex = h.exemplars(top=3)
    assert [e["trace_id"] for e in ex] == ["t-99", "t-98", "t-97"]
    assert ex[0]["value"] == 99.0 and ex[0]["ts"] > 0
    # bounded window: old exemplars evicted, newest retained
    assert len(h._exemplars) == h.EXEMPLAR_WINDOW
    snap = h.snapshot()
    assert snap["exemplars"][0]["trace_id"] == "t-99"


def test_histogram_without_trace_ids_allocates_no_exemplars():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for i in range(100):
        h.observe(float(i))
    assert h._exemplars is None           # nothing allocated
    assert h.exemplars() == []
    assert "exemplars" not in h.snapshot()


# ===================== Prometheus conformance (satellite) ==============
#: text exposition format 0.0.4: every non-comment line is
#: NAME{LABELS}? VALUE, label values are quoted with \\ \" \n escaped,
#: values are decimal / +Inf / -Inf / NaN
_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{([a-zA-Z_][a-zA-Z0-9_]*="(\\\\|\\"|\\n|[^"\\\n])*",?)*\})? '
    r'(NaN|[+-]Inf|[-+]?[0-9.e+-]+)$')


def _assert_conformant(text):
    families = set()
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            families.add(line.split()[2])
            assert line.split()[3] in ("counter", "gauge", "summary")
        elif line.startswith("# HELP "):
            assert "\n" not in line
        else:
            m = _SAMPLE.match(line)
            assert m, f"non-conformant sample line: {line!r}"
    return families


def test_prometheus_text_escapes_label_values_and_help():
    reg = MetricsRegistry()
    reg.counter("dl4j.test.esc",
                labels={"path": 'a"b\nc\\d'},
                help='help with "quotes"\nand a newline').inc(3)
    reg.gauge("dl4j.test.inf").set(float("inf"))
    reg.gauge("dl4j.test.ninf").set(float("-inf"))
    reg.gauge("dl4j.test.nan").set(float("nan"))
    text = reg.prometheus_text()
    _assert_conformant(text)
    assert r'path="a\"b\nc\\d"' in text
    assert '# HELP dl4j_test_esc help with "quotes"\\nand a newline' \
        in text
    assert "dl4j_test_inf +Inf" in text
    assert "dl4j_test_ninf -Inf" in text
    assert "dl4j_test_nan NaN" in text
    # a histogram whose sum went non-finite must not break the scrape
    h = reg.histogram("dl4j.test.lat")
    h.observe(float("inf"))
    _assert_conformant(reg.prometheus_text())


def test_prometheus_every_family_has_type_header():
    reg = MetricsRegistry()
    reg.counter("a.b").inc()
    reg.gauge("c.d", labels={"x": "1"}).set(2)
    reg.histogram("e.f").observe(1.0)
    text = reg.prometheus_text()
    fams = _assert_conformant(text)
    assert fams == {"a_b", "c_d", "e_f"}
    # help_texts() exposes the registered help lines for the cluster
    # renderer to reuse
    reg.counter("a.b", help="counts a.b")
    assert reg.help_texts()["a.b"] == "counts a.b"


# ===================== Chrome-trace process metadata (satellite) =======
def test_chrome_trace_leads_with_process_metadata():
    mon.enable()
    tracer = mon.get_tracer()
    tracer.clear()
    with tracer.span("work"):
        pass
    doc = tracer.to_chrome_trace()
    evs = doc["traceEvents"]
    # metadata events lead, naming this process and its span threads
    assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
    assert evs[0]["pid"] == os.getpid()
    assert f"pid {os.getpid()}" in evs[0]["args"]["name"]
    tnames = [e for e in evs if e.get("name") == "thread_name"]
    assert tnames and all(e["ph"] == "M" for e in tnames)
    assert any(e["tid"] == threading.get_ident() for e in tnames)
    # explicit override for merged multi-process documents
    doc2 = tracer.to_chrome_trace(process_name="worker 3")
    assert doc2["traceEvents"][0]["args"]["name"] == "worker 3"


def test_chrome_trace_process_name_carries_distributed_index():
    from deeplearning4j_tpu.resilience import faults
    mon.enable()
    old = faults.PROCESS_ID
    faults.PROCESS_ID = 1
    try:
        doc = mon.get_tracer().to_chrome_trace()
        assert doc["traceEvents"][0]["args"]["name"].startswith("dl4j p1 ")
    finally:
        faults.PROCESS_ID = old


def test_merged_chrome_trace_renders_request_lanes():
    mon.enable()
    mon.get_tracer().clear()
    with mon.span("serve"):
        tl = reqmod.start("generation")
        tl.event("admit", slot=0)
        tl.event("block", k=8)
        tl.event("retire", reason="eos")
        tl.finish("eos")
    doc = reqmod.merged_chrome_trace()
    evs = doc["traceEvents"]
    json.dumps(doc)
    # the request rides its own named lane, far from real thread ids
    lane_meta = [e for e in evs if e["ph"] == "M"
                 and e["name"] == "thread_name"
                 and tl.trace_id in str(e["args"].get("name"))]
    assert len(lane_meta) == 1
    lane = lane_meta[0]["tid"]
    assert lane >= 1_000_000
    slices = [e for e in evs if e.get("tid") == lane and e["ph"] in "Xi"]
    assert [e["name"] for e in slices] == ["admit", "block", "retire"]
    assert slices[0]["ph"] == "X" and slices[-1]["ph"] == "i"
    assert all(e["args"]["trace_id"] == tl.trace_id for e in slices)
    # the span events are in the same document (merged, one timebase)
    assert any(e.get("name") == "serve" for e in evs)


# ===================== SLO burn-rate tracker ===========================
def _latency_tracker(reg, clock, **kw):
    kw.setdefault("short_window", 10.0)
    kw.setdefault("long_window", 40.0)
    kw.setdefault("min_interval", 0.0)
    obj = slo.LatencyObjective("per_token_p99", metric="lat",
                               max_value=5.0)
    # bind measurement to the test registry, not the process global
    obj.measure = lambda registry=None, _o=obj, _r=reg: \
        slo.LatencyObjective.measure(_o, registry=_r)
    return slo.SloTracker([obj], clock=clock, **kw)


def test_latency_breach_requires_both_windows_then_recovers():
    reg = MetricsRegistry()
    h = reg.histogram("lat", reservoir=64)
    fake = [0.0]
    tr = _latency_tracker(reg, lambda: fake[0])
    h.observe(1.0)
    for _ in range(15):
        fake[0] += 2.0
        tr.evaluate(force=True)
    assert tr.breaches() == []            # healthy baseline
    # regression: p99 shoots over the threshold
    for _ in range(64):
        h.observe(100.0)
    fake[0] += 2.0
    snap = tr.evaluate(force=True)
    # one bad sample after a healthy baseline: the SHORT window burns
    # but the long one hasn't — no page from a single bad scrape
    assert tr.breaches() == []
    for _ in range(8):
        fake[0] += 2.0
        snap = tr.evaluate(force=True)
    assert tr.breaches() == ["per_token_p99"]
    d = snap["objectives"]["per_token_p99"]
    assert d["breached"] and d["burn_short"] >= 1.0 \
        and d["burn_long"] >= 1.0
    assert d["last_value"] == pytest.approx(100.0, rel=0.1)
    assert d["breached_for_s"] >= 0
    # recovery: the latency comes back down and the windows drain
    for _ in range(64):
        h.observe(1.0)
    for _ in range(30):
        fake[0] += 2.0
        tr.evaluate(force=True)
    assert tr.breaches() == []            # auto-recovered


def test_breach_flips_health_to_degraded_with_objective_named():
    mon.enable()
    reg = MetricsRegistry()
    h = reg.histogram("lat", reservoir=32)
    for _ in range(32):
        h.observe(50.0)
    fake = [0.0]
    tr = _latency_tracker(reg, lambda: fake[0]).install()
    for _ in range(8):
        fake[0] += 2.0
        tr.evaluate(force=True)
    snap = resilience.health_snapshot()
    assert snap["status"] == "degraded"
    assert snap["slo"]["violated"] == ["per_token_p99"]
    # breach state published on the registry
    g = mon.get_registry().get(
        mon.SLO_BREACHED, labels={"objective": "per_token_p99"})
    assert g is not None and g.value == 1.0
    b = mon.get_registry().get(
        mon.SLO_BREACHES, labels={"objective": "per_token_p99"})
    assert b is not None and b.value >= 1
    # recovery clears the health verdict through the same path
    for _ in range(64):
        h.observe(0.1)
    for _ in range(30):
        fake[0] += 2.0
        tr.evaluate(force=True)
    snap = resilience.health_snapshot()
    assert snap["status"] == "ok" and snap["slo"]["violated"] == []
    tr.uninstall()
    assert slo.ACTIVE is None


def test_single_bad_scrape_at_cold_start_cannot_breach():
    """The evidence floor: with both burn windows holding the same 1-2
    samples (cold start, or a scrape cadence as long as the windows),
    one bad scrape must not page — sustained badness still trips once
    `min_samples` evidence lands."""
    reg = MetricsRegistry()
    h = reg.histogram("lat", reservoir=16)
    for _ in range(16):
        h.observe(100.0)                   # bad from birth
    fake = [0.0]
    tr = _latency_tracker(reg, lambda: fake[0])
    fake[0] += 1.0
    tr.evaluate(force=True)
    assert tr.breaches() == []             # 1 sample: no evidence yet
    for _ in range(tr.min_samples - 1):
        fake[0] += 1.0
        tr.evaluate(force=True)
    assert tr.breaches() == ["per_token_p99"]


def test_finished_timeline_is_immutable():
    """A worker racing the client's timeout (claim vs cancel) must not
    append events after the terminal one — the ring entry's last event
    stays the terminal status."""
    log = RequestLog(capacity=4)
    tl = log.start("inference")
    tl.event("enqueue")
    tl.event("timeout")
    tl.finish("timeout")
    tl.event("dispatch", rows=4)           # the racing worker
    assert [e["event"] for e in tl.events] == ["enqueue", "timeout"]
    assert tl.dropped == 0                 # ignored, not "dropped"


def test_ratio_objective_measures_window_deltas():
    reg = MetricsRegistry()
    replays = reg.counter("gen.replays")
    admits = reg.counter("gen.admissions")
    obj = slo.RatioObjective("replay_rate", num="gen.replays",
                             den="gen.admissions", max_ratio=0.2)
    admits.inc(10)
    assert obj.measure(registry=reg) is None     # first sample arms it
    admits.inc(10)
    replays.inc(1)
    assert obj.measure(registry=reg) is False    # 1/10 <= 0.2
    admits.inc(10)
    replays.inc(9)
    assert obj.measure(registry=reg) is True     # 9/10 this window
    assert obj.last_value == pytest.approx(0.9)
    # replays with ZERO admissions in the window: violation by itself
    replays.inc(1)
    assert obj.measure(registry=reg) is True
    # no activity at all: no evidence either way
    assert obj.measure(registry=reg) is None


def test_throughput_objective_baseline_resists_self_heal():
    obj = slo.ThroughputObjective("steps_rate", max_drop=0.5, ema=0.5)
    rates = iter([10.0, 10.0, 3.0, 3.0, 3.0, 9.0])
    obj._rate = lambda: next(rates)
    assert obj.measure() is False          # first sample sets baseline
    assert obj.measure() is False
    base = obj.baseline
    assert obj.measure() is True           # 3 < 10 * 0.5
    assert obj.measure() is True           # still bad — baseline held
    assert obj.measure() is True
    assert obj.baseline == base            # regression never re-anchors
    assert obj.measure() is False          # recovery updates baseline
    assert obj.baseline != base


def test_standard_objectives_env_knobs(monkeypatch):
    monkeypatch.delenv("DL4J_SLO_PER_TOKEN_P99_MS", raising=False)
    monkeypatch.delenv("DL4J_SLO_STEPS_DROP", raising=False)
    monkeypatch.delenv("DL4J_SLO_REPLAY_RATIO", raising=False)
    assert slo.standard_objectives() == []
    monkeypatch.setenv("DL4J_SLO_PER_TOKEN_P99_MS", "25")
    monkeypatch.setenv("DL4J_SLO_REPLAY_RATIO", "0.2")
    objs = slo.standard_objectives()
    assert [o.name for o in objs] == ["per_token_p99", "replay_rate"]
    assert objs[0].threshold == 25.0
    # explicit args win over env
    objs = slo.standard_objectives(per_token_p99_ms=10, steps_drop=0.5,
                                   replay_ratio=0.1)
    assert [o.name for o in objs] == ["per_token_p99", "steps_rate",
                                      "replay_rate"]


def test_broken_objective_never_takes_down_health():
    class Exploding(slo.Objective):
        def measure(self, registry=None):
            raise RuntimeError("boom")

    tr = slo.SloTracker([Exploding("bad")], min_interval=0.0).install()
    snap = tr.evaluate(force=True)
    assert snap["violated"] == []
    hs = resilience.health_snapshot()
    assert hs["status"] == "ok"
    tr.uninstall()


def test_evaluation_is_rate_limited():
    calls = []

    class Counting(slo.Objective):
        def measure(self, registry=None):
            calls.append(1)
            return False

    fake = [0.0]
    tr = slo.SloTracker([Counting("c")], min_interval=5.0,
                        clock=lambda: fake[0])
    tr.evaluate()
    tr.evaluate()                          # inside min_interval: skipped
    assert len(calls) == 1
    fake[0] += 6.0
    tr.evaluate()
    assert len(calls) == 2


# ===================== cluster metrics plane ===========================
def _coordinator_pair(sync_every=1):
    kv = LocalKV()
    return [PeerCoordinator(sync_every=sync_every, peer_timeout=5.0,
                            client=kv, process_id=i, num_processes=2)
            for i in (0, 1)]


def _drive(coordinators, steps):
    """Step both coordinators in lockstep from two threads (the sync
    point blocks on the peer's heartbeat)."""
    errs = []

    def run(c):
        try:
            for _ in range(steps):
                c.on_step()
        except Exception as e:  # noqa: BLE001 — surfaced by caller
            errs.append(e)

    ts = [threading.Thread(target=run, args=(c,)) for c in coordinators]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not errs, errs
    return coordinators


def test_sync_point_publishes_one_bounded_key_per_host():
    mon.enable()
    reg = mon.get_registry()
    reg.counter("dl4j.test.steps").inc(3)
    cs = _drive(_coordinator_pair(), steps=4)
    kv = cs[0]._client
    keys = [k for k, _ in kv.key_value_dir_get("dl4j/metrics/")]
    # 4 sync rounds, still exactly ONE overwritten key per process
    assert sorted(keys) == ["dl4j/metrics/0", "dl4j/metrics/1"]
    snaps = cluster.gather(cs[0])
    assert sorted(snaps) == [0, 1]
    for pid, snap in snaps.items():
        assert snap["step"] == 4 and "metrics" in snap
        assert "steps_per_s" in snap
    # hb piggyback: the peer table carries per-peer steps/s
    table = cs[0].peer_table()
    assert "steps_per_s" in table[1]


def test_disabled_monitoring_publishes_nothing():
    mon.disable()
    cs = _drive(_coordinator_pair(), steps=2)
    assert cluster.gather(cs[0]) == {}


def test_cluster_prometheus_text_labels_hosts_and_aggregates():
    mon.enable()
    reg = mon.get_registry()
    # the registry is the process's: an earlier module that served
    # requests with monitoring on has counted tokens into it already
    reg.clear()
    reg.counter("dl4j.gen.tokens", help="tokens generated").inc(5)
    reg.gauge("dl4j.gen.active_slots").set(3)
    h = reg.histogram("dl4j.gen.per_token_ms")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    cs = _drive(_coordinator_pair(), steps=1)
    text = cluster.cluster_prometheus_text(cs[0])
    _assert_conformant(text)
    # per-host series from BOTH processes (same registry here, so the
    # values match — the labels are what the fleet view keys off)
    assert 'dl4j_gen_tokens{host="0"} 5' in text
    assert 'dl4j_gen_tokens{host="1"} 5' in text
    # counters aggregate under host="cluster" (summed across hosts)
    assert 'dl4j_gen_tokens{host="cluster"} 10' in text
    # histograms: count/sum aggregate, per-host quantiles survive
    assert 'dl4j_gen_per_token_ms_count{host="cluster"} 6' in text
    assert 'dl4j_gen_per_token_ms_sum{host="cluster"} 12' in text
    assert 'dl4j_gen_per_token_ms{host="0",quantile="0.99"}' in text
    # gauges do NOT aggregate — summing occupancy across hosts lies
    assert 'dl4j_gen_active_slots{host="cluster"}' not in text
    assert 'dl4j_gen_active_slots{host="0"} 3' in text
    # HELP text reused for the per-host-labeled family
    assert "# HELP dl4j_gen_tokens tokens generated" in text
    # staleness gauge: one age per host plus the max under "cluster"
    assert 'dl4j_cluster_snapshot_age_seconds{host="0"}' in text
    assert 'dl4j_cluster_snapshot_age_seconds{host="cluster"}' in text


def test_process0_health_snapshot_carries_cluster_meta():
    mon.enable()
    cs = _drive(_coordinator_pair(), steps=2)
    snap0 = cs[0].snapshot()
    assert snap0["cluster"]["published"] == 2
    hosts = snap0["cluster"]["hosts"]
    assert sorted(hosts) == ["0", "1"]
    for meta in hosts.values():
        assert meta["step"] == 2
        assert meta["snapshot_age_s"] >= 0
    assert snap0["cluster"]["max_snapshot_age_s"] >= 0
    # process 1 is not the serving end: no cluster section
    assert "cluster" not in cs[1].snapshot()


def test_cluster_metrics_endpoint_serves_both_hosts(tmp_path):
    """Process 0's `GET /metrics` switches to the cluster renderer when
    a multi-host coordinator is installed — both hosts' series appear,
    labeled; uninstalling reverts to the local text."""
    from deeplearning4j_tpu.ui.server import UIServer
    mon.enable()
    mon.get_registry().counter("dl4j.test.cluster_probe").inc(2)
    cs = _drive(_coordinator_pair(), steps=1)
    cs[0].install()
    server = UIServer.getInstance()
    server.start(port=0)
    try:
        base = f"http://127.0.0.1:{server.port}"
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=10).read().decode()
        assert 'dl4j_test_cluster_probe{host="0"} 2' in text
        assert 'dl4j_test_cluster_probe{host="1"} 2' in text
        assert 'dl4j_test_cluster_probe{host="cluster"} 4' in text
        _assert_conformant(text)
        # /health carries the per-host cluster meta on process 0
        snap = json.load(urllib.request.urlopen(base + "/health",
                                                timeout=10))
        assert snap["distributed"]["cluster"]["published"] == 2
        cs[0].uninstall()
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=10).read().decode()
        assert 'host="cluster"' not in text
        assert "dl4j_test_cluster_probe 2" in text
    finally:
        server.stop()
        cs[0].uninstall()


# ===================== request/slo/trace endpoints =====================
def test_requests_and_slo_endpoints():
    from deeplearning4j_tpu.ui.server import UIServer
    mon.enable()
    tl = reqmod.start("generation", meta={"prompt_len": 2})
    tl.event("enqueue").event("admit", slot=0).event("block", k=8)
    tl.event("retire", reason="eos")
    tl.finish("eos")
    live = reqmod.start("inference")
    live.event("enqueue")
    reg = mon.get_registry()
    reg.histogram(mon.GEN_PER_TOKEN_MS).observe(123.0,
                                                trace_id=tl.trace_id)
    tr = slo.SloTracker([], min_interval=0.0).install()
    server = UIServer.getInstance()
    server.start(port=0)
    try:
        base = f"http://127.0.0.1:{server.port}"
        doc = json.load(urllib.request.urlopen(base + "/requests",
                                               timeout=10))
        assert [t["trace_id"] for t in doc["active"]] == [live.trace_id]
        assert doc["recent"][-1]["trace_id"] == tl.trace_id
        # p99 exemplars land in the listing — the click-through link
        ex = doc["exemplars"][mon.GEN_PER_TOKEN_MS]
        assert ex[0]["trace_id"] == tl.trace_id
        # ?last=0 bounds the ring tail away entirely
        doc0 = json.load(urllib.request.urlopen(
            base + "/requests?last=0", timeout=10))
        assert doc0["recent"] == []
        # one timeline by id; unknown ids are a 404, not a 200-ish blob
        one = json.load(urllib.request.urlopen(
            base + f"/requests/{tl.trace_id}", timeout=10))
        assert one["status"] == "eos"
        assert [e["event"] for e in one["events"]] == \
            ["enqueue", "admit", "block", "retire"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/requests/nope", timeout=10)
        assert ei.value.code == 404
        # /slo reports the installed tracker
        s = json.load(urllib.request.urlopen(base + "/slo", timeout=10))
        assert s["installed"] is True
        tr.uninstall()
        s = json.load(urllib.request.urlopen(base + "/slo", timeout=10))
        assert s["installed"] is False
        # /trace is the merged Chrome document with request lanes
        t = json.load(urllib.request.urlopen(base + "/trace",
                                             timeout=10))
        metas = [e for e in t["traceEvents"] if e["ph"] == "M"]
        assert any(e["name"] == "process_name" for e in metas)
        assert any(tl.trace_id in str(e["args"].get("name"))
                   for e in metas)
        # the dashboard page carries the new panels
        html = urllib.request.urlopen(base + "/",
                                      timeout=10).read().decode()
        assert 'id="requests"' in html and 'id="slo"' in html
    finally:
        server.stop()
        tr.uninstall()
    live.finish("ok")


# ===================== ParallelInference integration ===================
def test_inference_requests_get_timelines_and_exemplars():
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       NeuralNetConfiguration,
                                       OutputLayer, Sgd)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    conf = (NeuralNetConfiguration.Builder()
            .seed(3).updater(Sgd(0.1)).activation("tanh")
            .list()
            .layer(DenseLayer.Builder().nOut(8).build())
            .layer(OutputLayer.Builder("mcxent").nOut(3)
                   .activation("softmax").build())
            .setInputType(InputType.feedForward(5))
            .build())
    net = MultiLayerNetwork(conf).init()
    x = np.random.default_rng(0).standard_normal((4, 5)).astype(
        np.float32)
    pi = ParallelInference.Builder(net).build()
    try:
        # disabled: no timelines, answers unchanged
        mon.disable()
        want = net.output(x).numpy()
        np.testing.assert_allclose(pi.output(x), want, atol=1e-6)
        assert reqmod.log().snapshot()["recent"] == []
        # enabled: a finished timeline with the dispatch lifecycle and
        # an exemplar linking the latency histogram to it
        mon.enable()
        np.testing.assert_allclose(pi.output(x), want, atol=1e-6)
        snap = reqmod.log().snapshot()
        assert snap["active"] == []
        rec = snap["recent"][-1]
        assert rec["kind"] == "inference" and rec["status"] == "ok"
        names = [e["event"] for e in rec["events"]]
        assert names[0] == "enqueue" and names[-1] == "done"
        assert "dispatch" in names
        h = mon.get_registry().get(mon.INFERENCE_REQUEST_MS)
        assert h is not None and h.count >= 1
        assert h.exemplars()[0]["trace_id"] == rec["trace_id"]
    finally:
        pi.shutdown()


# ===================== fast-path lint coverage (satellite) =============
def test_lint_module_lists_cover_request_tracing():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    import check_fastpath
    rel = "deeplearning4j_tpu/monitoring/requests.py"
    assert rel in check_fastpath.HOT_MODULES
    assert rel in check_fastpath.GENERATION_MODULES
    assert rel in check_fastpath.SERVING_MODULES
    # the timeline close path is walked by the sync rule
    assert {"_finish", "_fail", "_retire_slot"} <= \
        check_fastpath.GENERATION_SYNC_ROOTS


def test_lint_flags_device_sync_hidden_in_timeline_append():
    """A timeline append that materializes device data would smuggle a
    host sync into the decode loop — the walker must flag it."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    import check_fastpath
    bad = textwrap.dedent("""
        import numpy as np

        def _deliver_block(self, blk):
            for rec in blk.recs.values():
                rec.req.trace.event("block", k=blk.k)

        def event(self, name, **fields):
            fields["snapshot"] = np.asarray(fields["tokens"])
            return self
    """)
    v = check_fastpath.check_generation_host_sync({"m.py": bad})
    assert len(v) == 1 and "asarray" in v[0][2]
    # the real module passes the same walk (pure host bookkeeping)
    path = os.path.join(check_fastpath.REPO_ROOT,
                        "deeplearning4j_tpu/monitoring/requests.py")
    with open(path) as f:
        src = {path: f.read()}
    assert check_fastpath.check_generation_host_sync(src) == []
    assert check_fastpath.check_generation_steady_state(src) == []


def test_compact_snapshot_shrinks_histograms_for_the_wire():
    reg = MetricsRegistry()
    reg.counter("c", labels={"k": "v"}).inc(2)
    h = reg.histogram("h")
    for v in range(100):
        h.observe(float(v))
    snap = cluster.compact_snapshot(reg)
    assert snap["c"][0]["value"] == 2
    rec = snap["h"][0]
    assert rec["kind"] == "histogram"
    assert rec["count"] == 100 and rec["sum"] == pytest.approx(4950)
    assert rec["p50"] and rec["p99"]
    assert "min" not in rec                # compact: no full snapshot
    json.dumps(snap)                       # KV-wire JSON-native
