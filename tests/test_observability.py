"""Observability spine: Prometheus round-trip, Chrome trace schema,
batch-lifecycle instrumentation, compile-event accounting, probe-report
envelope."""

import json
import urllib.request

import pytest


def _fresh_registry():
    from lighthouse_tpu.common.metrics import Registry

    return Registry()


@pytest.fixture
def tracer():
    """A private Tracer; the global one stays disabled for other tests."""
    from lighthouse_tpu.observability.trace import Tracer

    t = Tracer()
    t.enable()
    return t


@pytest.fixture
def global_trace():
    """Enable the global tracer for one test, guaranteed re-disabled."""
    from lighthouse_tpu.observability import trace

    trace.TRACER.clear()
    trace.TRACER.enable()
    yield trace.TRACER
    trace.TRACER.disable()
    trace.TRACER.clear()


# ---------------------------------------------------------------------------
# Prometheus text-format round trip (satellite 4a)
# ---------------------------------------------------------------------------


def _parse_exposition(text):
    """Minimal exposition-format parser: {name: {"help", "type",
    "samples": [(name, labels_dict, value)]}}. Unescapes label values."""
    families = {}
    current = None
    for line in text.splitlines():
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_text = rest.partition(" ")
            current = families.setdefault(
                name, {"help": help_text, "type": None, "samples": []})
        elif line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            families[name]["type"] = kind
        elif line and not line.startswith("#"):
            sample, _, value = line.rpartition(" ")
            labels = {}
            if "{" in sample:
                sname, _, rest = sample.partition("{")
                body = rest.rsplit("}", 1)[0]
                # Split on commas not preceded by a backslash escape:
                # values themselves are escaped, so `",` only terminates.
                for part in body.split('",'):
                    if not part:
                        continue
                    lname, _, lval = part.partition('="')
                    lval = lval.rstrip('"')
                    lval = (lval.replace("\\n", "\n").replace('\\"', '"')
                            .replace("\\\\", "\\"))
                    labels[lname] = lval
            else:
                sname = sample
            base = sname
            for suffix in ("_bucket", "_sum", "_count"):
                if sname.endswith(suffix) and sname[:-len(suffix)] in families:
                    base = sname[:-len(suffix)]
            families[base]["samples"].append((sname, labels, float(value)))
    return families


def test_prometheus_round_trip_counters_and_labels():
    reg = _fresh_registry()
    reg.counter("a_total", "plain counter").inc(3)
    vec = reg.counter_vec("b_total", "labeled counter", "kind")
    vec.labels("x").inc()
    vec.labels('we"ird\\label\nvalue').inc(2)
    g = reg.gauge_vec("q_depth", "labeled gauge", "kind")
    g.labels("att").set(7)

    fams = _parse_exposition(reg.gather())
    assert fams["a_total"]["type"] == "counter"
    assert fams["a_total"]["help"] == "plain counter"
    assert fams["a_total"]["samples"] == [("a_total", {}, 3.0)]
    assert fams["b_total"]["type"] == "counter"
    by_label = {s[1]["kind"]: s[2] for s in fams["b_total"]["samples"]}
    # The escaped label value round-trips through parse/unescape.
    assert by_label == {"x": 1.0, 'we"ird\\label\nvalue': 2.0}
    assert fams["q_depth"]["type"] == "gauge"
    assert fams["q_depth"]["samples"] == [("q_depth", {"kind": "att"}, 7.0)]


def test_prometheus_round_trip_histogram_cumulative():
    reg = _fresh_registry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    fams = _parse_exposition(reg.gather())
    fam = fams["lat_seconds"]
    assert fam["type"] == "histogram"
    buckets = [(s[1]["le"], s[2]) for s in fam["samples"]
               if s[0] == "lat_seconds_bucket"]
    # Cumulative and monotone, +Inf == count.
    assert buckets == [("0.1", 1.0), ("1.0", 3.0), ("10.0", 4.0),
                       ("+Inf", 5.0)]
    count = [s for s in fam["samples"] if s[0] == "lat_seconds_count"][0]
    total = [s for s in fam["samples"] if s[0] == "lat_seconds_sum"][0]
    assert count[2] == 5.0
    assert total[2] == pytest.approx(56.05)


def test_prometheus_round_trip_labeled_histogram():
    reg = _fresh_registry()
    h = reg.histogram_vec("stage_seconds", "stage wall",
                          labels=("engine", "stage"), buckets=(1.0, 2.0))
    h.labels(engine="bm", stage="h2g2").observe(0.5)
    h.labels(engine="bm", stage="h2g2").observe(1.5)
    h.labels(engine="major", stage="pairing").observe(3.0)
    fams = _parse_exposition(reg.gather())
    fam = fams["stage_seconds"]
    assert fam["type"] == "histogram"
    bm = [(s[1]["le"], s[2]) for s in fam["samples"]
          if s[0] == "stage_seconds_bucket" and s[1].get("engine") == "bm"]
    assert bm == [("1.0", 1.0), ("2.0", 2.0), ("+Inf", 2.0)]
    major_inf = [s[2] for s in fam["samples"]
                 if s[0] == "stage_seconds_bucket"
                 and s[1].get("engine") == "major" and s[1]["le"] == "+Inf"]
    assert major_inf == [1.0]
    # One HELP/TYPE header total (a family, not one per child).
    text = reg.gather()
    assert text.count("# HELP stage_seconds ") == 1
    assert text.count("# TYPE stage_seconds ") == 1


def test_labels_kwargs_and_positional_agree():
    reg = _fresh_registry()
    vec = reg.counter_vec("c_total", "help", labels=("a", "b"))
    vec.labels("1", "2").inc()
    vec.labels(b="2", a="1").inc()
    assert vec.get("1", "2") == 2.0
    with pytest.raises(ValueError):
        vec.labels("1")                      # wrong arity
    with pytest.raises(ValueError):
        vec.labels(a="1", c="2")             # wrong keyword
    # Single-label back-compat (the aot/router/gossip call sites).
    old = reg.counter_vec("d_total", "help", "outcome")
    old.labels("hit").inc()
    assert old.get("hit") == 1.0
    assert old.get("miss") == 0.0


def test_registry_is_truthy_when_empty():
    # `registry or REGISTRY` is the codebase-wide default idiom; an
    # empty-but-falsy registry would silently retarget the global one.
    reg = _fresh_registry()
    assert bool(reg) and len(reg) == 0


# ---------------------------------------------------------------------------
# Chrome trace export (satellite 4b + tentpole)
# ---------------------------------------------------------------------------


def test_trace_export_valid_chrome_schema(tracer):
    with tracer.span("outer", cat="stage", engine="bm"):
        with tracer.span("inner"):
            pass
        with tracer.span("inner2"):
            pass
    tracer.instant("mark", cat="compile", detail=1)

    doc = json.loads(json.dumps(tracer.export()))   # JSON round-trip
    assert isinstance(doc["traceEvents"], list)
    assert doc["otherData"]["dropped_events"] == 0
    phases = sorted(e["ph"] for e in doc["traceEvents"])
    assert phases == ["X", "X", "X", "i"]
    for ev in doc["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0


def test_trace_nested_spans_balance(tracer):
    with tracer.span("a"):
        with tracer.span("b"):
            with tracer.span("c"):
                pass
        with tracer.span("d"):
            pass
    events = [e for e in tracer.export()["traceEvents"] if e["ph"] == "X"]
    # Any two spans on one thread either nest fully or are disjoint —
    # partial overlap means the spans lost their stack discipline.
    eps = 1e-9
    for i, x in enumerate(events):
        for y in events[i + 1:]:
            if x["tid"] != y["tid"]:
                continue
            x0, x1 = x["ts"], x["ts"] + x["dur"]
            y0, y1 = y["ts"], y["ts"] + y["dur"]
            disjoint = x1 <= y0 + eps or y1 <= x0 + eps
            x_in_y = y0 <= x0 + eps and x1 <= y1 + eps
            y_in_x = x0 <= y0 + eps and y1 <= x1 + eps
            assert disjoint or x_in_y or y_in_x
    # Depth stamps match the lexical nesting.
    depths = {e["name"]: e["args"]["depth"] for e in events}
    assert depths == {"a": 1, "b": 2, "c": 3, "d": 2}


def test_trace_disabled_records_nothing_and_passes_through():
    from lighthouse_tpu.observability.trace import Tracer

    t = Tracer()                               # never enabled
    with t.span("x") as handle:
        assert handle is None
    t.instant("y")
    assert t.export()["traceEvents"] == []


def test_trace_save_atomic(tmp_path, tracer):
    with tracer.span("s"):
        pass
    path = tracer.save(str(tmp_path / "t.trace.json"))
    doc = json.load(open(path))
    assert len(doc["traceEvents"]) == 1
    assert not list(tmp_path.glob("*.tmp.*"))


def test_trace_buffer_cap_counts_drops():
    from lighthouse_tpu.observability.trace import Tracer

    t = Tracer(max_events=3)
    t.enable()
    for i in range(5):
        t.instant(f"e{i}")
    doc = t.export()
    assert len(doc["traceEvents"]) == 3
    assert doc["otherData"]["dropped_events"] == 2


# ---------------------------------------------------------------------------
# Engine cores
# ---------------------------------------------------------------------------


def test_engine_cores_expose_traced_stages():
    """Both engine builders surface `core.stages` (builders only — no
    execution, so no compile cost in tier-1)."""
    from lighthouse_tpu.ops import backend as be
    from lighthouse_tpu.ops.bm import backend as bmb

    core = be._jitted_core(4, 1, False)
    assert len(core.stages) == 3
    core_bm = bmb.jitted_core(4, 1, 4)
    assert len(core_bm.stages) == 4     # + the shared final exponentiation


# ---------------------------------------------------------------------------
# Batch lifecycle (tentpole: scheduler + router spans, margin histograms)
# ---------------------------------------------------------------------------


def _lifecycle_rig(registry):
    from lighthouse_tpu.common.slot_clock import ManualSlotClock
    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.serving.router import CostModelRouter, LatencyTable
    from lighthouse_tpu.serving.scheduler import ContinuousBatchScheduler

    api.register_backend("_test_obs_cpu", lambda sets: True)
    router = CostModelRouter(table=LatencyTable(),
                             cpu_backend="_test_obs_cpu",
                             small_batch_max=64, registry=registry)
    clock = ManualSlotClock(genesis_time=0, seconds_per_slot=12)
    clock.set_slot(5)
    sched = ContinuousBatchScheduler(clock, router=router,
                                     registry=registry)
    return sched


def test_scheduler_margin_and_accumulation_histograms(global_trace):
    import time as _time

    from lighthouse_tpu.serving.scheduler import VerifyJob

    reg = _fresh_registry()
    sched = _lifecycle_rig(reg)
    t_then = _time.perf_counter() - 0.25       # arrived 250ms ago
    for i in range(4):
        sched.submit(VerifyJob("gossip_attestation", f"s{i}",
                               t_arrival=t_then))
    assert sched.run_until_idle() == 1

    margin = reg.histogram("serving_deadline_margin_seconds")
    _, count, total = margin.snapshot()
    assert count == 1
    assert total > 0                           # instant backend: a hit
    accum = reg.histogram("serving_batch_accumulation_seconds")
    _, acount, atotal = accum.snapshot()
    assert acount == 4
    assert atotal >= 4 * 0.25                  # waits include t_arrival
    size = reg.histogram("serving_scheduler_batch_size_sets")
    assert size.snapshot()[1] == 1

    names = [e["name"] for e in global_trace.events()]
    assert "batch:close" in names
    assert "batch:execute" in names
    assert "batch:verdict" in names
    assert "router:decision" in names
    assert "router:verify" in names


def test_margin_histogram_buckets_span_negative():
    from lighthouse_tpu.serving.scheduler import MARGIN_BUCKETS

    assert min(MARGIN_BUCKETS) < 0 < max(MARGIN_BUCKETS)

    reg = _fresh_registry()
    h = reg.histogram("m_seconds", "h", buckets=MARGIN_BUCKETS)
    h.observe(-0.3)                            # a miss lands in a bucket
    counts, total, _ = h.snapshot()
    assert total == 1 and counts[MARGIN_BUCKETS.index(-0.2)] == 1


def test_verify_job_arrival_defaults_to_now():
    import time as _time

    from lighthouse_tpu.serving.scheduler import VerifyJob

    t0 = _time.perf_counter()
    job = VerifyJob("gossip_attestation", "s")
    assert abs(job.t_arrival - t0) < 1.0


# ---------------------------------------------------------------------------
# Beacon processor metrics (satellite 2)
# ---------------------------------------------------------------------------


def test_processor_queue_depth_and_counters():
    from lighthouse_tpu.beacon_processor.processor import (
        BeaconProcessor,
        WorkEvent,
    )

    reg = _fresh_registry()
    proc = BeaconProcessor(registry=reg)
    done = []
    for i in range(5):
        proc.send(WorkEvent("gossip_attestation", i,
                            process_batch=lambda items: done.extend(items)))
    depth = reg.gauge_vec("beacon_processor_queue_depth")
    assert depth.get("gossip_attestation") == 5.0
    proc.run_until_idle()
    assert depth.get("gossip_attestation") == 0.0
    assert sorted(done)[-1] == 4
    processed = reg.counter_vec("beacon_processor_processed_total")
    assert processed.get("gossip_attestation") == 5.0
    assert reg.counter("beacon_processor_batches_total").get() >= 1


def test_processor_dropped_counter_on_overflow():
    from lighthouse_tpu.beacon_processor.processor import (
        QUEUE_CAPS,
        BeaconProcessor,
        WorkEvent,
    )

    reg = _fresh_registry()
    proc = BeaconProcessor(registry=reg)
    cap = QUEUE_CAPS["chain_segment"]          # smallest cap: 64
    accepted = sum(
        proc.send(WorkEvent("chain_segment", i)) for i in range(cap + 3))
    assert accepted == cap
    dropped = reg.counter_vec("beacon_processor_dropped_total")
    assert dropped.get("chain_segment") == 3.0
    assert proc.stats.dropped == 3


# ---------------------------------------------------------------------------
# Compile events (tentpole: provenance)
# ---------------------------------------------------------------------------


def test_compile_event_record_counts_and_traces(global_trace):
    from lighthouse_tpu.common import metrics as m
    from lighthouse_tpu.observability import compile_events

    before = compile_events.counts()["warm_bundle_hit"]
    compile_events.record("warm_bundle_hit", stage="h2g2")
    after = compile_events.counts()["warm_bundle_hit"]
    assert after == before + 1
    assert m.REGISTRY.counter_vec(
        "engine_compile_events_total").get("warm_bundle_hit") == after
    names = [e["name"] for e in global_trace.events()]
    assert "compile:warm_bundle_hit" in names


def test_compile_events_install_idempotent():
    from lighthouse_tpu.observability import compile_events

    first = compile_events.install()
    assert isinstance(first, bool)
    if first:                                  # once live, stays live
        assert compile_events.install() is True


def test_aot_bundle_outcomes_feed_compile_events():
    from lighthouse_tpu.observability import compile_events
    from lighthouse_tpu.serving import aot

    before = compile_events.counts()["bundle_corrupt"]
    aot._count("corrupt")
    assert compile_events.counts()["bundle_corrupt"] == before + 1


# ---------------------------------------------------------------------------
# /health + /metrics endpoints (satellite 1)
# ---------------------------------------------------------------------------


def test_metrics_server_health_endpoint():
    from lighthouse_tpu.common.metrics import MetricsServer

    reg = _fresh_registry()
    reg.counter("up_total", "h").inc()
    srv = MetricsServer(registry=reg).start()
    try:
        with urllib.request.urlopen(f"{srv.url}/health") as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == "application/json"
            body = json.loads(resp.read())
        assert body["status"] == "ok"
        assert body["metrics"] == 1
        assert body["uptime_seconds"] >= 0
        with urllib.request.urlopen(f"{srv.url}/metrics") as resp:
            assert b"up_total 1.0" in resp.read()
        try:
            urllib.request.urlopen(f"{srv.url}/nope")
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Probe-report envelope (satellite 3)
# ---------------------------------------------------------------------------


def test_probe_report_round_trip(capsys):
    from lighthouse_tpu.observability import report

    rep = report.make("probe_test", params={"n": 4})
    line = report.emit(report.finish(rep, ok=True, results={"x": 1}))
    printed = capsys.readouterr().out
    assert line in printed
    docs = report.parse_lines(f"noise\n{line}\n{{bad json\n")
    assert len(docs) == 1
    doc = docs[0]
    assert doc["schema"] == report.SCHEMA
    assert doc["probe"] == "probe_test"
    assert doc["ok"] is True
    assert doc["params"] == {"n": 4}
    assert doc["results"] == {"x": 1}
    assert doc["wall_seconds"] >= 0
    # The line leads with the schema key (the consumer match contract).
    assert line.startswith('{"schema"')


def test_probe_report_env_facts_present():
    from lighthouse_tpu.observability import report

    rep = report.make("probe_env")
    assert rep["env"].get("jax_platform") == "cpu"
    assert rep["env"].get("device_count", 0) >= 1
