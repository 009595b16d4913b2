"""Observability layer (repro.obs): span/event tracing, the metrics
registry, the sweep scopes, and the predicted-vs-achieved PMS join.

The contract under test: tracing OFF is free (every span site returns the
shared no-op span, no profiler annotation opens and nothing is recorded),
tracing ON records the spans every layer promises (decompose -> job phases
-> drive -> sweep, plan_build, plan-cache events), each single-device sweep
names its kernel, update and fit parts in its compiled program and in
nothing else, and the calibrate join reproduces achieved_pct from a trace
alone."""
import contextlib
import json
import math
import re
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.api import decompose
from repro.core.coo import random_factors
from repro.core.loop import finish_iter
from repro.kernels import ops
from repro.obs import Tracer, metrics, trace
from repro.obs.calibrate import (
    CalibrationRow,
    accuracy_records,
    calibration_row,
    format_table,
    join_trace,
    predicted_sweep_seconds,
)


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts and ends with tracing off and a fresh registry —
    obs state is process-global by design, so tests must not leak it."""
    trace.disable()
    metrics.reset()
    yield
    trace.disable()
    metrics.reset()


# ---------------------------------------------------------------------------
# trace: spans, nesting, export round-trips
# ---------------------------------------------------------------------------


def test_span_nesting_and_roundtrip(tmp_path):
    tr = Tracer()
    trace.install(tr)
    with trace.span("outer", layer="a"):
        with trace.span("inner", layer="b"):
            trace.event("ping", n=1)
        with trace.span("inner", layer="c"):
            pass
    assert len(tr.spans("outer")) == 1
    assert len(tr.spans("inner")) == 2
    outer = tr.spans("outer")[0]
    assert outer["parent"] is None
    for rec in tr.spans("inner"):
        assert rec["parent"] == outer["id"]
        assert rec["dur"] >= 0
    (ping,) = tr.events("ping")
    assert ping["args"] == {"n": 1}
    # events nest under the span that was open when they fired
    inner_b = [r for r in tr.spans("inner") if r["args"]["layer"] == "b"][0]
    assert ping["parent"] == inner_b["id"]

    path = tmp_path / "t.jsonl"
    assert tr.export_jsonl(path) == 4
    loaded = trace.load_jsonl(path)
    assert loaded == tr.records

    chrome = tmp_path / "t.json"
    assert tr.export_chrome(chrome) == 4
    doc = json.loads(chrome.read_text())
    assert {e["ph"] for e in doc["traceEvents"]} == {"X", "i"}
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all("dur" in e and "ts" in e for e in x)


def test_span_set_attaches_mid_span():
    tr = Tracer()
    trace.install(tr)
    with trace.span("s") as sp:
        sp.set(fit=0.5)
    assert tr.spans("s")[0]["args"]["fit"] == 0.5


def test_disabled_calls_are_noops():
    assert trace.active() is None
    sp = trace.span("x", a=1)
    assert sp is trace.span("y")  # the shared null span, no allocation
    with sp as s:
        s.set(b=2)
    trace.event("never")


def test_tracing_scope_restores_previous_tracer(tmp_path):
    outer = trace.enable()
    path = tmp_path / "scoped.jsonl"
    with trace.tracing(str(path)) as tr:
        assert trace.active() is tr
        with trace.span("scoped"):
            pass
    assert trace.active() is outer
    recs = trace.load_jsonl(path)
    assert [r["name"] for r in recs] == ["scoped"]
    assert outer.records == []  # scoped work never leaked into the global


def test_load_jsonl_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"ph": "X", "name": "ok", "ts": 1}\nnot json\n')
    with pytest.raises(ValueError, match="not valid JSON"):
        trace.load_jsonl(bad)
    bad.write_text('{"name": "missing ph", "ts": 1}\n')
    with pytest.raises(ValueError, match="missing field"):
        trace.load_jsonl(bad)


def test_configure_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "1")
    tr = trace.configure_from_env()
    assert trace.active() is tr
    trace.disable()
    out = tmp_path / "env.jsonl"
    monkeypatch.setenv("REPRO_TRACE", str(out))
    tr = trace.configure_from_env()
    with trace.span("from_env"):
        pass
    trace._export_at_exit()
    assert [r["name"] for r in trace.load_jsonl(out)] == ["from_env"]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_counter_gauge_histogram():
    c = metrics.counter("c", kind="x")
    c.inc()
    c.inc(2)
    assert metrics.counter("c", kind="x") is c  # get-or-create
    g = metrics.gauge("g")
    g.set(7.5)
    h = metrics.histogram("h")
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        h.observe(v)
    snap = metrics.snapshot()
    assert snap["counters"]["c{kind=x}"] == 3
    assert snap["gauges"]["g"] == 7.5
    hs = snap["histograms"]["h"]
    assert hs["count"] == 5 and hs["min"] == 1.0 and hs["max"] == 5.0
    assert hs["mean"] == 3.0
    assert h.percentile(50) == 3.0
    with pytest.raises(TypeError):
        metrics.gauge("c", kind="x")  # same series name, different type
    metrics.reset()
    assert metrics.snapshot() == {"counters": {}, "gauges": {},
                                  "histograms": {}}


# ---------------------------------------------------------------------------
# engine integration: decompose -> drive -> sweep spans + metrics
# ---------------------------------------------------------------------------


def test_decompose_trace_records_engine_spans(tiny_tensor, tmp_path):
    path = tmp_path / "cp.jsonl"
    out = decompose(tiny_tensor, 4, iters=3, trace=str(path))
    assert trace.active() is None  # restored after the call
    recs = trace.load_jsonl(path)
    names = [r["name"] for r in recs if r["ph"] == "X"]
    assert names.count("decompose") == 1
    assert names.count("drive") == 1
    assert names.count("sweep") == 3
    assert names.count("plan_build") == tiny_tensor.nmodes
    # nesting: sweep under drive under decompose
    by_id = {r["id"]: r for r in recs}
    sweep = [r for r in recs if r["name"] == "sweep"][0]
    drive = by_id[sweep["parent"]]
    assert drive["name"] == "drive"
    assert by_id[drive["parent"]]["name"] == "decompose"
    # the sweep spans carry the PMS prediction for the offline join
    assert sweep["args"]["predicted_s"] == pytest.approx(
        sum(e.t_total for e in
            ops.make_planned_cp_als(tiny_tensor, 4).pms_estimates().values()),
        rel=1e-6,
    )
    assert len(out.fit_history) == 3
    # the always-on metrics saw the iterations even though trace was scoped
    snap = metrics.snapshot()
    assert snap["counters"]["drive.iterations{label=cp_als}"] == 3
    assert snap["histograms"]["drive.iter_seconds{label=cp_als}"]["count"] == 3
    assert not any(k.startswith("drive.fit_delta") for k in snap["histograms"])


_JOB_KWARGS = {
    "cp": (4, {}),
    "tucker": ((3, 3, 3), {}),
    "tt": ((3, 3), {"init": "random"}),
}


@pytest.mark.parametrize("fmt", sorted(_JOB_KWARGS))
def test_decompose_trace_records_job_phases_in_order(tiny_tensor, fmt):
    """A traced pallas job names its host phases: init, the COO upload (CP
    and TT; Tucker's sweep reads only the plans), the factor pad, the
    iterations, the unpad — in that order, all inside `decompose`."""
    rank, kwargs = _JOB_KWARGS[fmt]
    tr = Tracer()
    decompose(tiny_tensor, rank, format=fmt, method="pallas", iters=2,
              trace=tr, **kwargs)
    spans = sorted(tr.spans(), key=lambda r: r["ts"])
    by_id = {r["id"]: r for r in spans}
    phases = [r["name"] for r in spans if r["name"] in
              ("job.init", "job.upload", "drive.pad", "drive", "drive.unpad")]
    upload = [] if fmt == "tucker" else ["job.upload"]
    assert phases == ["job.init", *upload, "drive.pad", "drive", "drive.unpad"]
    (top,) = [r for r in spans if r["name"] == "decompose"]
    for r in spans:
        if r["name"] in phases:
            assert by_id[r["parent"]] is top
            assert top["ts"] <= r["ts"] and r["ts"] + r["dur"] <= top["ts"] + top["dur"]


def test_plan_build_metrics_recorded(tiny_tensor):
    from repro.core.remap import plan_blocks

    plan = plan_blocks(tiny_tensor, 0)
    snap = metrics.snapshot()
    assert snap["histograms"]["plan.build_seconds{builder=vectorized}"]["count"] == 1
    pad = snap["histograms"]["plan.padding_fraction"]
    assert pad["count"] == 1
    assert pad["mean"] == pytest.approx(plan.padding_fraction())
    # occupancy was 1 - padding_fraction, read by nothing: no longer recorded
    assert "plan.occupancy" not in snap["histograms"]


def test_plan_cache_counters_match_stats(tiny_tensor):
    facs = random_factors(jax.random.PRNGKey(0), tiny_tensor.shape, 4)
    ops.plan_cache_clear()
    metrics.reset()
    tr = trace.enable()
    try:
        ops.mttkrp_auto(tiny_tensor, facs, 0)   # miss
        ops.mttkrp_auto(tiny_tensor, facs, 0)   # hit
        ops.mttkrp_auto(tiny_tensor, facs, 1)   # miss
    finally:
        trace.disable()
    stats = ops.plan_cache_stats()["by_kind"]["mttkrp"]
    snap = metrics.snapshot()
    assert snap["counters"]["plan_cache.misses{kind=mttkrp}"] == stats["misses"] == 2
    assert snap["counters"]["plan_cache.hits{kind=mttkrp}"] == stats["hits"] == 1
    assert snap["histograms"]["plan_cache.miss_build_seconds{kind=mttkrp}"]["count"] == 2
    assert snap["histograms"]["plan_cache.hit_seconds{kind=mttkrp}"]["count"] == 1
    assert len(tr.events("plan_cache_hit")) == 1
    assert len(tr.spans("plan_cache_build")) == 2


def test_plan_cache_eviction_counter(tiny_tensor):
    facs = random_factors(jax.random.PRNGKey(0), tiny_tensor.shape, 4)
    old_cap = ops.plan_cache_config()
    ops.plan_cache_clear()
    metrics.reset()
    try:
        ops.plan_cache_config(1)
        ops.mttkrp_auto(tiny_tensor, facs, 0)
        ops.mttkrp_auto(tiny_tensor, facs, 1)  # evicts mode 0's plan
        ops.mttkrp_auto(tiny_tensor, facs, 0)  # miss again (was evicted)
    finally:
        ops.plan_cache_config(old_cap)
        ops.plan_cache_clear()
    snap = metrics.snapshot()
    assert snap["counters"]["plan_cache.evictions"] == 2
    assert snap["counters"]["plan_cache.misses{kind=mttkrp}"] == 3
    assert "plan_cache.hits{kind=mttkrp}" not in snap["counters"]


def test_nonfinite_fit_event_and_counter():
    tr = trace.enable()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            stop = finish_iter([], float("nan"), 3, None, False, "unit")
    finally:
        trace.disable()
    assert stop is True
    assert metrics.snapshot()["counters"]["resilience.nonfinite_fit{label=unit}"] == 1
    (ev,) = tr.events("nonfinite_fit")
    assert ev["args"]["it"] == 3 and ev["args"]["label"] == "unit"


def test_guard_restart_counted(tiny_tensor):
    from repro.resilience import GuardConfig
    from repro.testing import faults

    ws = ops.make_planned_cp_als(tiny_tensor, 4)
    faults.inject_nan_factor(ws, at_iter=1)
    tr = trace.enable()
    try:
        decompose(tiny_tensor, 4, iters=4, seed=0, planned=ws,
                  guards=GuardConfig(policy="restart", max_restarts=1))
    finally:
        trace.disable()
    snap = metrics.snapshot()
    assert snap["counters"]["resilience.restarts{label=cp_als}"] == 1
    assert len(tr.events("guard_restart")) == 1


def test_admission_metrics(tiny_tensor):
    from repro.resilience import admit, admission_bytes

    ws = ops.make_planned_cp_als(tiny_tensor, 4)
    admit(ws, admission_bytes(ws)["total_bytes"] + 1)
    snap = metrics.snapshot()
    assert snap["counters"]["admission.admitted{outcome=pallas}"] == 1


# ---------------------------------------------------------------------------
# calibrate: the PMS join
# ---------------------------------------------------------------------------


def test_pms_estimates_hooks(tiny_tensor):
    from repro.tt.als import make_planned_tt
    from repro.tucker.hooi import make_planned_tucker

    for ws in (
        ops.make_planned_cp_als(tiny_tensor, 4),
        make_planned_tucker(tiny_tensor, (3, 3, 3)),
        make_planned_tt(tiny_tensor, (2, 2)),
    ):
        pred = predicted_sweep_seconds(ws)
        assert pred > 0 and math.isfinite(pred)
        ests = ws.pms_estimates()
        assert set(ests) == set(range(tiny_tensor.nmodes))


def test_calibration_row_and_records():
    row = CalibrationRow("cp", "small", predicted_s=0.02, measured_s=4.0)
    assert row.achieved_pct == pytest.approx(0.5)
    recs = accuracy_records([row])
    assert [r["metric"] for r in recs] == [
        "predicted_s", "measured_s", "achieved_pct"]
    assert all(r["name"] == "pms_accuracy_cp" and r["preset"] == "small"
               for r in recs)
    with pytest.raises(ValueError):
        calibration_row(object(), 0.0, format="cp", preset="x")


def test_join_trace_on_fixed_fixture(tmp_path):
    """The offline join on a hand-built trace: 1 compile sweep + 3 steady
    sweeps; measured = median of the steady three, achieved = pred/measured."""
    recs = [
        {"ph": "X", "name": "sweep", "ts": i * 100.0, "dur": dur,
         "args": {"label": "cp_als", "preset": "small",
                  "predicted_s": 0.002, "it": i}}
        for i, dur in enumerate([900_000.0, 110_000.0, 100_000.0, 90_000.0])
    ]
    recs.append({"ph": "X", "name": "plan_build", "ts": 0.0, "dur": 5.0,
                 "args": {}})
    path = tmp_path / "fixture.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    rows = join_trace(path)
    assert len(rows) == 1
    r = rows[0]
    assert r["label"] == "cp_als" and r["preset"] == "small"
    assert r["n_sweeps"] == 4
    assert r["measured_s"] == pytest.approx(0.1)   # median excl. first
    assert r["achieved_pct"] == pytest.approx(2.0)  # 100 * 0.002 / 0.1
    table = format_table(rows)
    assert "cp_als" in table and "2.00%" in table


def test_join_trace_without_predictions():
    recs = [{"ph": "X", "name": "sweep", "ts": 0.0, "dur": 50_000.0,
             "args": {"label": "tt_als"}}]
    (row,) = join_trace(recs)
    assert row["predicted_s"] is None and row["achieved_pct"] is None


# ---------------------------------------------------------------------------
# sweep scopes: named in the compiled program, and nothing else changed
# ---------------------------------------------------------------------------


def _scoped_workspace(tiny_tensor, fmt):
    from repro.tt.als import make_planned_tt
    from repro.tucker.hooi import make_planned_tucker

    norm = jnp.float32(1.0)
    stream = (jnp.asarray(tiny_tensor.indices), jnp.asarray(tiny_tensor.values), norm)
    if fmt == "cp":
        return ops.make_planned_cp_als(tiny_tensor, 4), stream
    if fmt == "tucker":
        return make_planned_tucker(tiny_tensor, (3, 3, 3)), (norm,)
    return make_planned_tt(tiny_tensor, (3, 3)), stream


@pytest.mark.parametrize("fmt", ["cp", "tucker", "tt"])
def test_sweep_scopes_name_every_mode_and_the_fit(tiny_tensor, fmt):
    ws, args = _scoped_workspace(tiny_tensor, fmt)
    programs = ws.sweep_scopes(*args)
    assert len(programs) == (2 if fmt == "cp" else 1)  # CP: first and steady
    want = {f"{fmt}.fit"} | {f"{fmt}.m{m}.{part}" for m in range(tiny_tensor.nmodes)
                             for part in ("kernel", "update")}
    for prog in programs:
        assert prog["module"] == "jit_sweep"
        assert set(prog["scopes"].values()) - {None} == want
        # every instruction is listed, the unscoped ones (parameters) too
        assert any(s is None for s in prog["scopes"].values())


@pytest.mark.parametrize("fmt", ["cp", "tucker", "tt"])
def test_sweep_scopes_change_only_metadata(tiny_tensor, fmt, monkeypatch):
    """The compiled sweep with its scopes and without them: the same HLO
    once op_name metadata is set aside."""
    from repro.tt import als
    from repro.tucker import hooi

    def compiled(ws, args):
        facs = tuple(jax.ShapeDtypeStruct(s, jnp.float32)
                     for s in zip(ws.padded_rows, ws.rank_pads))
        return [ws.lower_sweep(facs, *args, **kwargs).compile().as_text()
                for kwargs in ws._sweep_variants()]

    def computation(text):
        body = [ln for ln in text.splitlines() if ln.startswith(("%", " ", "ENTRY", "}"))]
        return [re.sub(r", metadata=\{[^}]*\}", "", ln) for ln in body]

    scoped = compiled(*_scoped_workspace(tiny_tensor, fmt))
    assert all(f'/{fmt}.fit/' in text for text in scoped)
    for mod in (ops, hooi, als):
        monkeypatch.setattr(mod, "sweep_scope", lambda *a, **k: contextlib.nullcontext())
    plain = compiled(*_scoped_workspace(tiny_tensor, fmt))
    assert not any(f'/{fmt}.fit/' in text for text in plain)
    assert [computation(t) for t in plain] == [computation(t) for t in scoped]


# ---------------------------------------------------------------------------
# tracing off: no span, no annotation, no record
# ---------------------------------------------------------------------------


def test_traced_off_drive_overhead_under_2pct(tiny_tensor, monkeypatch):
    """With tracing off, a job of k iterations costs the instrumentation
    nothing that can grow: every span site — decompose, the job phases,
    drive, each sweep — returns the shared no-op span, no
    `jax.profiler.TraceAnnotation` opens, and no tracer records anything.
    (A CPU wall-clock bound stood here; the traced-off cost on the chip is
    the benchmark's `als_iter_s`.)"""
    sites, annotations, records = [], [], []
    real_span = trace.span

    def counted_span(name, **attrs):
        out = real_span(name, **attrs)
        sites.append((name, out))
        return out

    class _Annotation:
        def __init__(self, name):
            annotations.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "span", counted_span)
    monkeypatch.setattr(trace, "_TRACE_ANNOTATION", _Annotation)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    monkeypatch.setattr(Tracer, "_record", lambda self, rec: records.append(rec))
    iters = 3
    for fmt, (rank, kwargs) in sorted(_JOB_KWARGS.items()):
        sites.clear()
        assert trace.active() is None
        decompose(tiny_tensor, rank, format=fmt, method="pallas", iters=iters, **kwargs)
        names = [n for n, _ in sites]
        upload = [] if fmt == "tucker" else ["job.upload"]
        for name in ("decompose", "job.init", *upload, "drive.pad", "drive", "drive.unpad"):
            assert names.count(name) == 1, (fmt, name, names)
        assert names.count("sweep") == iters
        assert all(out is trace._NULL_SPAN for _, out in sites)
    assert annotations == [] and records == []


# ---------------------------------------------------------------------------
# the sharded makespan report
# ---------------------------------------------------------------------------


def test_shard_makespan_report_shape():
    from repro.dist.planned import shard_makespan_report

    class _Stack:
        def __init__(self, mode, nblocks, nnz):
            self.mode = mode
            self.shard_nblocks = nblocks
            self.shard_nnz = nnz

    class _WS:
        stacks = {0: _Stack(0, (4, 2), (100, 50)),
                  1: _Stack(1, (3, 3), (75, 75))}

    rep = shard_makespan_report(_WS())
    assert rep["nshards"] == 2
    m0 = rep["modes"][0]
    assert m0["makespan_blocks"] == 4
    assert m0["block_imbalance"] == pytest.approx(4 * 2 / 6)
    assert m0["busy_fraction"] == (1.0, 0.5)
    assert rep["modes"][1]["block_imbalance"] == pytest.approx(1.0)
    assert rep["worst_block_imbalance"] == pytest.approx(4 * 2 / 6)
    snap = metrics.snapshot()
    assert snap["histograms"]["sharded.block_imbalance{mode=0}"]["count"] == 1
    with pytest.raises(TypeError):
        shard_makespan_report(object())
