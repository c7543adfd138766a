"""Federation flight recorder (DESIGN.md §Observability).

Covers the telemetry bundle in isolation — metrics registry semantics,
span lifecycle + bounded rings, Chrome-trace export, digest anchoring,
the near-free disabled path — the snapshot-aliasing regression at the
board/scheduler boundary, and the acceptance criterion: one full 8-silo
compressed+secure round traced end to end over a simulated WAN exports
valid Chrome-trace JSON with scheduler, phase, per-silo client and
transport RPC spans on both clock lanes, digest on the provenance chain.
"""
import glob
import json
import os
import time

import pytest

from repro.core import (FederationScheduler, MetricsRegistry, Telemetry,
                        WanModel)
from repro.core.jobs import JobCreator
from repro.core.metadata import MetadataStore
from repro.data.synthetic import SiloDataset

ARCH = "fedforecast-100m"


def make_fleet(n_silos=3, capacity=2, **sched_kw):
    sched = FederationScheduler(b"tel-key".ljust(32, b"0"), **sched_kw)
    cids = [sched.bootstrap_silo(
        f"org{i}", SiloDataset(f"silo-{i}", 512, 32, 100 + i),
        capacity=capacity) for i in range(n_silos)]
    return sched, cids


def make_job(sched, **decisions):
    base = {"arch": ARCH, "rounds": 1, "local_steps": 1, "batch_size": 2,
            "lr": 1e-3, "data_schema": None}
    base.update(decisions)
    return JobCreator(sched.metadata).from_admin("admin", base)


def submit_job(sched, cids, job_idx=0, **decisions):
    job = make_job(sched, **decisions)
    datasets = {cid: SiloDataset(f"j{job_idx}-s{i}", 512, 32,
                                 7000 + job_idx * 100 + i)
                for i, cid in enumerate(cids)}
    return sched.submit(job, server=sched.new_server(seed=job_idx),
                        cohort=list(cids), datasets=datasets)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("x.count")
    c.inc()
    c.inc(4)
    assert reg.counter("x.count") is c           # same series every call
    assert c.read() == 5
    reg.gauge("x.depth").set(3.5)
    h = reg.histogram("x.seconds")
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["x.count"] == 5
    assert snap["x.depth"] == 3.5
    assert snap["x.seconds"]["count"] == 3
    assert snap["x.seconds"]["mean"] == pytest.approx(2.0)
    assert snap["x.seconds"]["min"] == 1.0 and snap["x.seconds"]["max"] == 3.0


def test_registry_labeled_series_and_kind_conflict():
    reg = MetricsRegistry()
    reg.counter("bytes_by", actor="a").inc(10)
    reg.counter("bytes_by", actor="b").inc(20)
    assert reg.labeled("bytes_by", "actor") == {"a": 10, "b": 20}
    snap = reg.snapshot()
    assert snap["bytes_by"] == {"actor=a": 10, "actor=b": 20}
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("bytes_by", actor="c")


def test_registry_snapshot_diff_and_detachment():
    reg = MetricsRegistry()
    reg.counter("n").inc(3)
    reg.histogram("h").observe(1.0)
    before = reg.snapshot()
    reg.counter("n").inc(2)
    reg.histogram("h").observe(5.0)
    reg.counter("fresh").inc()
    after = reg.snapshot()
    d = MetricsRegistry.diff(before, after)
    assert d["n"] == 2
    assert d["fresh"] == 1                       # absent before: from zero
    assert d["h"] == {"count": 1, "total": 5.0}  # the window's observation
    # snapshots are plain detached data: mutating one cannot touch the
    # registry or a previously taken snapshot
    before["n"] = 10 ** 9
    assert reg.snapshot()["n"] == 5


def test_registry_collectors_run_at_snapshot():
    reg = MetricsRegistry()
    src = {"v": 1}
    reg.register_collector(lambda r: r.gauge("pulled").set(src["v"]))
    assert reg.snapshot()["pulled"] == 1
    src["v"] = 7
    assert reg.snapshot()["pulled"] == 7


# ---------------------------------------------------------------------------
# span lifecycle + flight recorder
# ---------------------------------------------------------------------------
def test_spans_nest_and_ring_is_bounded():
    tel = Telemetry(enabled=True, recorder_cap=8)
    with tel.span("outer", run_id="r1") as outer:
        with tel.span("inner", run_id="r1") as inner:
            pass
    assert inner.parent_id == outer.span_id
    assert outer.t1 is not None and outer.t1 >= outer.t0
    for i in range(20):
        with tel.span(f"s{i}", run_id="r1"):
            pass
    spans = tel.spans("r1")
    assert len(spans) == 8                       # ring dropped the oldest
    assert spans[-1].name == "s19"


def test_open_close_span_crosses_calls():
    tel = Telemetry(enabled=True)
    sid = tel.open_span("phase:collect", cat="phase", run_id="r1")
    assert tel.spans("r1")[0].t1 is None         # still open, still visible
    tel.close_span(sid, outcome="done")
    (sp,) = tel.spans("r1")
    assert sp.t1 is not None and sp.attrs["outcome"] == "done"
    tel.close_span(sid)                          # double close: no-op
    tel.close_span(0)                            # disabled-path id: no-op


def test_incident_dump_is_bounded():
    tel = Telemetry(enabled=True, max_incidents=3)
    with tel.span("work", run_id="r1"):
        pass
    for i in range(5):
        tel.record_incident("r1", f"pause {i}")
    assert len(tel.incidents) == 3
    assert tel.incidents[-1]["reason"] == "pause 4"
    assert tel.incidents[-1]["spans"][0]["name"] == "work"


class _Recorded:
    """Stand-in for ``TraceAnnotation`` that records what it is given."""

    def __init__(self, log, name):
        self.log, self.name = log, name
        log.append(("made", name))

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


def test_disabled_telemetry_records_nothing(monkeypatch):
    from repro.core import telemetry
    made = []
    monkeypatch.setattr(telemetry, "TraceAnnotation",
                        lambda name: _Recorded(made, name))
    real_span = telemetry.Span
    monkeypatch.setattr(telemetry, "Span",
                        lambda *a, **k: made.append(("span", a[2])))
    tel = Telemetry()                            # default: off
    s1 = tel.span("a", attrs={"k": 1})
    s2 = tel.span("b")
    assert s1 is s2                              # shared no-op singleton
    with s1:
        s1.set(x=1)
    assert tel.open_span("phase:x") == 0
    assert tel.spans("r1") == []
    assert made == []                            # no Span, no annotation
    monkeypatch.setattr(telemetry, "Span", real_span)
    with Telemetry(enabled=True).span("on"):     # the probes are live
        pass
    assert made == [("made", "on"), ("enter", "on"), ("exit", "on")]
    with tel.kernel_span("masked_sum"):
        pass                                     # histogram always feeds
    assert tel.metrics.snapshot()["kernel.seconds"][
        "kernel=masked_sum"]["count"] == 1


# ---------------------------------------------------------------------------
# snapshot aliasing (satellite regression)
# ---------------------------------------------------------------------------
def test_board_stats_snapshot_does_not_alias():
    sched, cids = make_fleet(n_silos=2, capacity=1)
    submit_job(sched, cids)
    sched.run(max_passes=500)
    snap = sched.board.stats
    posted_by = dict(snap["bytes_posted_by"])
    # a second job moves the live counters; the held snapshot must not
    submit_job(sched, cids, job_idx=1)
    sched.run(max_passes=500)
    assert snap["bytes_posted_by"] == posted_by
    assert sched.board.stats["bytes_posted"] > snap["bytes_posted"]
    # and mutating the snapshot must not corrupt the board
    snap["bytes_posted_by"]["server"] = -1
    assert sched.board.stats["bytes_posted_by"]["server"] != -1


def test_scheduler_monitor_snapshot_does_not_alias():
    sched, cids = make_fleet(n_silos=2, capacity=1)
    submit_job(sched, cids)
    mon = sched.monitor()
    stats = dict(mon["stats"])
    leases = {k: list(v) for k, v in mon["leases"].items()}
    sched.run(max_passes=500)
    assert mon["stats"] == stats                 # frozen at snapshot time
    assert mon["leases"] == leases
    mon["capacity"][cids[0]] = 99                # mutation stays local
    assert sched.capacity[cids[0]] != 99


# ---------------------------------------------------------------------------
# acceptance: 8-silo compressed+secure round, traced end to end over a WAN
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_eight_silo_secure_compressed_round_traced_end_to_end():
    tel = Telemetry(enabled=True)
    wan = WanModel(seed=7)
    sched, cids = make_fleet(n_silos=8, capacity=1, wan=wan, telemetry=tel)
    run_id = submit_job(sched, cids, secure_aggregation=True,
                        compression="int8")
    sched.run(max_passes=2000)
    assert sched.entries[run_id].state == "done"

    trace, digest = tel.anchor_trace(sched.metadata, run_id)
    events = trace["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    # all span families present: scheduler, per-phase, per-silo client,
    # transport RPC, kernel timing
    cats = {e["cat"] for e in spans}
    assert {"scheduler", "phase", "client", "rpc", "kernel"} <= cats
    names = {e["name"] for e in spans}
    assert {"sched.pass", "sched.admit", "sched.tick", "client.fetch",
            "client.train", "client.compress", "client.post",
            "board.put", "board.stat_many",
            "kernel:masked_dequant_reduce"} <= names
    phase_names = {e["name"] for e in spans if e["cat"] == "phase"}
    assert {"phase:distribute", "phase:collect",
            "phase:evaluate"} <= phase_names
    # per-silo client spans: every silo shows up as its own trace thread
    tids = {e["tid"] for e in events if e["ph"] == "M"
            and e["name"] == "thread_name"
            and e["args"]["name"].startswith("client-")}
    assert len(tids) == 8
    # both clock lanes: wall (pid 1) and WanModel sim clock (pid 2)
    assert {e["pid"] for e in spans} == {1, 2}
    sim = [e for e in spans if e["pid"] == 2]
    assert any(e["dur"] > 0 for e in sim)        # sim time actually moved
    # Chrome-trace JSON must round-trip and carry valid X events
    parsed = json.loads(json.dumps(trace, default=float))
    assert all(ev["ts"] >= 0 and ev["dur"] >= 0
               for ev in parsed["traceEvents"] if ev["ph"] == "X")
    # the export's digest is anchored on the (intact) provenance chain
    (rec,) = sched.metadata.query(kind="provenance",
                                  operation="trace_export")
    assert rec["subject"] == run_id
    assert rec["details"]["digest"] == digest == Telemetry.trace_digest(
        json.loads(json.dumps(trace, default=float)))
    assert rec["details"]["sim_clock"] is True
    assert sched.metadata.verify_chain()
    # kernel-timing hook observed the masked-quantized reduction
    ks = tel.metrics.snapshot()["kernel.seconds"]
    assert any("masked_dequant_reduce" in k and v["count"] >= 1
               for k, v in ks.items())


def test_pause_dumps_incident_and_run_timeline_reports_phases():
    from repro.core.reporting import run_timeline
    tel = Telemetry(enabled=True)
    sched, cids = make_fleet(n_silos=2, capacity=1, telemetry=tel)
    run_id = submit_job(sched, cids, rounds=2)
    for _ in range(3):
        sched.step()
    sched.preempt(run_id, reason="operator drill")
    assert any(i["run_id"] == run_id and i["spans"]
               for i in tel.incidents)           # flight recorder dumped
    tl = run_timeline(sched.metadata, run_id, telemetry=tel)
    assert any(e.get("operation") == "preempt_job" for e in tl["events"])
    assert any(p["name"].startswith("phase:") for p in tl["phases"])
    seqs = [e["seq"] for e in tl["events"]]
    assert seqs == sorted(seqs)


def test_fleet_report_joins_monitor_and_metrics():
    from repro.core.reporting import fleet_report
    sched, cids = make_fleet(n_silos=2, capacity=1)
    run_id = submit_job(sched, cids)
    sched.run(max_passes=500)
    rep = fleet_report(sched)
    assert rep["runs"][run_id]["state"] == "done"
    assert rep["monitor"]["stats"]["completed"] == 1
    assert rep["metrics"]["board.posts"] > 0
    assert rep["metrics"]["sched.passes"] == rep["monitor"]["stats"]["passes"]


def test_fleet_report_surfaces_streaming_agg_metrics():
    """A secure run folds updates through the streaming sinks; the
    accumulator gauge and fold-batch counter must land in fleet_report
    (DESIGN.md §Sharded streaming aggregation)."""
    from repro.core import Telemetry
    from repro.core.reporting import fleet_report
    sched, cids = make_fleet(n_silos=2, capacity=1,
                             telemetry=Telemetry(enabled=True))
    run_id = submit_job(sched, cids, secure_aggregation=True)
    sched.run(max_passes=500)
    rep = fleet_report(sched)
    assert rep["runs"][run_id]["state"] == "done"
    folds = rep["metrics"]["agg.stream_fold_batches"]
    peak = rep["metrics"]["agg.accumulator_peak_bytes"]
    assert folds["plane=masked_f32"] >= 1
    assert peak["plane=masked_f32"] > 0


def test_metadata_clock_injection():
    ticks = iter(range(100))
    md = MetadataStore(clock=lambda: float(next(ticks)))
    md.record_provenance(actor="a", operation="op", subject="s",
                         outcome="ok")
    md.record_provenance(actor="a", operation="op", subject="s",
                         outcome="ok")
    ts = [r["ts"] for r in md.query(kind="provenance")]
    assert ts == [0.0, 1.0]                      # deterministic under test
    assert md.verify_chain()


# ---------------------------------------------------------------------------
# stage spans of a secure round, and their mirror in the profiler's trace
# ---------------------------------------------------------------------------
STAGE_SPANS = ("wire.pack", "wire.encrypt", "wire.decrypt", "wire.unpack",
               "server.ingest", "server.publish_global", "server.aggregate",
               "client.eval", "client.h2d")


def _ancestors(span, by_id):
    names, pid = [], span.parent_id
    while pid in by_id:
        names.append(by_id[pid].name)
        pid = by_id[pid].parent_id
    return names


def _sibling(span, spans, names, after: bool):
    """The nearest span named in ``names`` with the same parent and actor,
    opened after ``span`` (or before it)."""
    near = [s for s in spans if s.name in names
            and s.parent_id == span.parent_id and s.actor == span.actor
            and (s.span_id > span.span_id if after
                 else s.span_id < span.span_id)]
    return (min if after else max)(near, key=lambda s: s.span_id)


@pytest.mark.parametrize("plane", [
    {"secure_aggregation": True},
    {"secure_aggregation": True, "compression": "int8"},
], ids=["secure_f32", "secure_int8"])
def test_stage_spans_cover_a_secure_round(plane):
    tel = Telemetry(enabled=True)
    sched, cids = make_fleet(n_silos=2, capacity=1, telemetry=tel)
    run_id = submit_job(sched, cids, **plane)
    sched.run(max_passes=500)
    assert sched.entries[run_id].state == "done"

    spans = tel.spans(run_id)
    by_id = {s.span_id: s for s in spans}
    assert {s.run_id for s in spans} == {run_id}
    assert set(STAGE_SPANS) <= {s.name for s in spans}
    wire = [s for s in spans if s.name.startswith("wire.")]
    assert {s.actor for s in wire} == {"server", *cids}

    def nested(name, outer):
        return any(outer in _ancestors(s, by_id)
                   for s in spans if s.name == name)
    for outer in ("server.ingest", "client.fetch", "client.eval"):
        assert nested("wire.decrypt", outer), outer
    for outer in ("server.publish_global", "client.post"):
        assert nested("wire.encrypt", outer), outer
    assert nested("client.h2d", "client.eval")

    posts = [s for s in spans if s.name == "client.post"]
    ingests = [s for s in spans if s.name == "server.ingest"]
    assert len(posts) == len(cids)
    assert sorted(s.attrs["client"] for s in ingests) == sorted(
        s.actor for s in posts)
    assert {s.attrs["round"] for s in ingests} == {0}

    # the cipher's byte counts are the board's, message by message
    for enc in (s for s in spans if s.name == "wire.encrypt"):
        put = _sibling(enc, spans, ("board.put",), after=True)
        assert enc.attrs["bytes"] == put.attrs["bytes"]
    for dec in (s for s in spans if s.name == "wire.decrypt"):
        get = _sibling(dec, spans, ("board.get", "board.get_if_newer"),
                       after=False)
        assert dec.attrs["bytes"] == get.attrs["bytes"]
    update = [s for s in wire if s.name == "wire.encrypt"
              and "client.post" in _ancestors(s, by_id)]
    assert all(s.attrs["compressed"] is False for s in update)


def test_spans_mirror_into_the_profiler_trace(tmp_path):
    """Lexical spans appear on the profiler's host plane with their own
    durations and one constant offset between the two clocks: what the
    benchmark's one-marker mapping of host spans onto the trace assumes.
    Non-lexical spans (``open_span``) are not mirrored."""
    import jax
    from jax.profiler import ProfileData

    tel = Telemetry(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tel.span("t.outer", run_id="r1"):
            with tel.span("t.mid", run_id="r1"):
                time.sleep(0.004)
                with tel.span("t.inner", run_id="r1"):
                    time.sleep(0.002)
            sid = tel.open_span("phase:t", run_id="r1")
            time.sleep(0.001)
            tel.close_span(sid)
            with tel.span("t.after", run_id="r1"):
                time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, []).append(ev)
    spans = {s.name: s for s in tel.spans("r1")}
    lexical = ["t.outer", "t.mid", "t.inner", "t.after"]
    assert "phase:t" not in host
    offsets = []
    for name in lexical:
        (ev,) = host[name]
        sp = spans[name]
        assert ev.duration_ns * 1e-9 == pytest.approx(sp.t1 - sp.t0,
                                                      abs=1e-3)
        offsets.append(ev.start_ns * 1e-9 - sp.t0)
    assert max(offsets) - min(offsets) < 1e-3
