"""Telemetry subsystem tests: tracer semantics, disabled fast path, static
counter accounting against a known FusionPlan, overlap-audit math on a
synthetic α-β model, and the telemetry block's round-trips through
`read_metrics` and the batch driver's log scrape."""

import json
import threading
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu.observability import counters as CTR
from dear_pytorch_tpu.observability import overlap as OV
from dear_pytorch_tpu.observability import tracer as T
from dear_pytorch_tpu.ops import fusion as F


@pytest.fixture(autouse=True)
def _isolate_global_tracer():
    """Every test leaves the process-global tracer as it found it."""
    old = T._tracer
    yield
    T.set_tracer(old)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_span_nesting_depth_and_order():
    mem = T.MemoryExporter()
    tr = T.Tracer([mem])
    with tr.span("outer", phase="a"):
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            pass
    # children finish (and export) before the parent
    assert [s.name for s in mem.spans] == ["inner", "inner2", "outer"]
    by_name = {s.name: s for s in mem.spans}
    assert by_name["outer"].depth == 0
    assert by_name["inner"].depth == 1
    assert by_name["inner2"].depth == 1
    assert by_name["outer"].attrs == {"phase": "a"}
    assert by_name["outer"].dur_us >= by_name["inner"].dur_us


def test_tracer_thread_safety():
    mem = T.MemoryExporter()
    tr = T.Tracer([mem])
    n_threads, n_iter = 8, 200
    gate = threading.Barrier(n_threads)  # overlap all threads: distinct
    # OS idents (Python reuses idents of finished threads otherwise)

    def work():
        gate.wait()
        for _ in range(n_iter):
            tr.count("steps")
            tr.count("bytes", 2.5)
            with tr.span("w"):
                pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    counters = tr.counters()
    assert counters["steps"] == n_threads * n_iter
    assert counters["bytes"] == pytest.approx(2.5 * n_threads * n_iter)
    assert len(mem.spans) == n_threads * n_iter
    # every worker thread got its own small tid; depth never leaked across
    assert len({s.tid for s in mem.spans}) == n_threads
    assert {s.depth for s in mem.spans} == {0}


def test_disabled_null_tracer_fast_path():
    tr = T.NullTracer()
    assert tr.enabled is False
    # zero-allocation: every span() call returns the one shared null CM
    assert tr.span("a") is tr.span("b", x=1)
    with tr.span("a"):
        pass
    tr.count("anything", 7)
    tr.event("whatever")
    assert tr.counters() == {}
    with pytest.raises(RuntimeError):
        tr.add_exporter(T.MemoryExporter())


def test_configure_from_env_grammar(tmp_path):
    T.set_tracer(None)
    assert isinstance(T.configure_from_env(""), T.NullTracer)
    T.set_tracer(None)
    assert isinstance(T.configure_from_env("0"), T.NullTracer)
    T.set_tracer(None)
    assert isinstance(T.configure_from_env("1"), T.Tracer)
    T.set_tracer(None)
    tr = T.configure_from_env(f"jsonl:{tmp_path}/t.jsonl")
    assert isinstance(tr, T.Tracer)
    tr.close()
    T.set_tracer(None)
    with pytest.raises(ValueError):
        T.configure_from_env("bogus:/x")
    # a second resolve is a no-op returning the installed tracer
    first = T.configure_from_env("1")
    assert T.configure_from_env("0") is first


def test_chrome_exporter_roundtrip(tmp_path):
    path = str(tmp_path / "trace.json")
    tr = T.Tracer([T.ChromeTraceExporter(path)])
    with tr.span("step", mode="dear"):
        pass
    tr.event("rebuild", buckets=3)
    tr.close()
    data = json.load(open(path))
    names = {e["name"] for e in data["traceEvents"]}
    assert {"step", "rebuild"} <= names
    span = next(e for e in data["traceEvents"] if e["name"] == "step")
    assert span["ph"] == "X" and span["args"] == {"mode": "dear"}


def test_jsonl_exporter_roundtrip_read_metrics(tmp_path):
    from dear_pytorch_tpu.utils import read_metrics

    path = str(tmp_path / "tel.jsonl")
    tr = T.Tracer([T.JsonlExporter(path)])
    with tr.span("pack", bucket=2):
        pass
    tr.event("compile", n=1)
    tr.close()
    recs = read_metrics(path)
    kinds = [r["kind"] for r in recs]
    assert kinds == ["span", "event"]
    assert recs[0]["name"] == "pack" and recs[0]["bucket"] == 2
    assert recs[0]["dur_us"] >= 0
    assert recs[1]["name"] == "compile" and recs[1]["n"] == 1


def test_snapshot_aggregates():
    tr = T.configure()
    with tr.span("step"):
        pass
    with tr.span("step"):
        pass
    tr.count("steps", 2)
    snap = T.snapshot()
    assert snap["enabled"] is True
    assert snap["counters"]["steps"] == 2
    assert snap["spans"]["step"]["count"] == 2
    assert json.loads(json.dumps(snap)) == snap  # JSON-safe


# ---------------------------------------------------------------------------
# counters: static accounting against a known plan
# ---------------------------------------------------------------------------


def _known_plan(world=4):
    # layer a: 110 elems (f32), layer b: 100 elems -> one bucket per layer
    params = {"a": {"w": jnp.zeros((10, 10)), "b": jnp.zeros((10,))},
              "b": {"w": jnp.zeros((10, 10))}}
    return F.plan_by_nearby_layers(params, world=world, k=1)


def test_plan_comm_accounting_dear():
    plan = _known_plan()
    acct = CTR.plan_comm_accounting(plan, mode="dear", comm_itemsize=4)
    # bucket 0: 110 elems padded to 112 (world=4) -> 448 B payload;
    # bucket 1: 100 elems, no pad -> 400 B
    assert [r.leg for r in acct.rows] == [
        "reduce_scatter", "all_gather", "reduce_scatter", "all_gather"]
    assert [r.payload_bytes for r in acct.rows] == [448, 448, 400, 400]
    ring = 3 / 4
    assert [r.wire_bytes for r in acct.rows] == [
        448 * ring, 448 * ring, 400 * ring, 400 * ring]
    assert acct.payload_bytes_per_step == 1696
    assert acct.leg_bytes_per_step("all_gather") == 848
    totals = acct.totals(steps=5, runtime_counters={})
    assert totals["per_leg"]["reduce_scatter"]["payload_bytes"] == 848 * 5


def test_plan_comm_accounting_modes_and_dtypes():
    plan = _known_plan()
    ar = CTR.plan_comm_accounting(plan, mode="allreduce", comm_itemsize=2)
    assert [r.leg for r in ar.rows] == ["all_reduce", "all_reduce"]
    assert ar.rows[0].payload_bytes == 112 * 2
    assert ar.rows[0].wire_bytes == pytest.approx(112 * 2 * 2 * 3 / 4)
    # dear with bf16 grads and f32 gathers: per-leg itemsize differs
    mixed = CTR.plan_comm_accounting(plan, mode="dear", comm_itemsize=2,
                                     gather_itemsize=4)
    by_leg = {r.leg: r.payload_bytes for r in mixed.rows if r.bucket == 0}
    assert by_leg == {"reduce_scatter": 224, "all_gather": 448}
    # compressed gradient leg: priced at the BUFFER itemsize (f32 leaves
    # here), NOT comm_itemsize — the execution path casts back to the
    # buffer dtype before compressing, so a narrower comm dtype never
    # shrinks the compressed payload
    qa = CTR.plan_comm_accounting(plan, mode="dear", comm_itemsize=2,
                                  gather_itemsize=4, compressor="qint8")
    qleg = {r.leg: r for r in qa.rows if r.bucket == 0}
    assert qleg["reduce_scatter"].payload_bytes == round(
        112 * 4 * (112 + 4) / (112 * 4))          # ~1 B/coord + scale
    assert qleg["all_gather"].payload_bytes == 448  # AG leg stays dense
    # world=1 plans carry zero wire bytes (collectives are local copies)
    p1 = F.plan_by_nearby_layers({"a": jnp.zeros((8,))}, world=1, k=1)
    acct1 = CTR.plan_comm_accounting(p1, mode="dear")
    assert all(r.wire_bytes == 0.0 for r in acct1.rows)
    with pytest.raises(ValueError):
        CTR.plan_comm_accounting(plan, mode="nonesuch")


# ---------------------------------------------------------------------------
# overlap: audit math on a synthetic alpha-beta model
# ---------------------------------------------------------------------------


class _StubTS(NamedTuple):
    plan: Any
    mesh: Any = None

    def lower(self, state, batch):  # the audit degrades without a compile
        raise RuntimeError("no backend in this test")


def _one_bucket_plan(world=4, elems=1000):
    return F.plan_by_nearby_layers(
        {"w": jnp.zeros((elems,))}, world=world, k=1)


def test_predict_leg_times_matches_perf_model():
    from dear_pytorch_tpu.utils import perf_model

    alpha, beta = 1e-3, 1e-6
    plan = _one_bucket_plan()
    acct = CTR.plan_comm_accounting(plan, mode="dear", comm_itemsize=4)
    times = OV.predict_leg_times(acct, alpha, beta)
    # each ring leg == the repo's allgather cost model, by construction
    expected = perf_model.allgather_perf_model(4000, 4, alpha, beta)
    assert times == pytest.approx([expected, expected])


def test_audit_math_synthetic():
    alpha, beta = 1e-3, 1e-6
    ts = _StubTS(plan=_one_bucket_plan())
    rep = OV.audit_train_step(
        ts, None, None, alpha=alpha, beta=beta, mode="dear",
        measured_step_s=16e-3, compute_time_s=10e-3, include_hlo=False,
    )
    # rs = ag = 3*(1e-3 + 1e-6*1000) = 6e-3 each -> comm 12e-3
    assert rep.comm_time_s == pytest.approx(12e-3)
    assert rep.serial_step_s == pytest.approx(22e-3)
    assert rep.ideal_step_s == pytest.approx(12e-3)
    assert rep.exposed_comm_s == pytest.approx(6e-3)
    assert rep.hidden_comm_s == pytest.approx(6e-3)
    assert rep.overlap_efficiency == pytest.approx(0.6)
    assert rep.model_note is None
    # per-leg attribution is proportional and sums back to the totals
    assert sum(leg.exposed_s for leg in rep.legs) == pytest.approx(6e-3)
    assert sum(leg.hidden_s for leg in rep.legs) == pytest.approx(6e-3)
    assert json.loads(json.dumps(rep.to_dict()))["mode"] == "dear"


def test_audit_clips_and_notes_model_mismatch():
    ts = _StubTS(plan=_one_bucket_plan())
    # measured beats the ideal -> saturated efficiency + an honest note
    rep = OV.audit_train_step(
        ts, None, None, alpha=1e-3, beta=1e-6, mode="dear",
        measured_step_s=5e-3, compute_time_s=10e-3, include_hlo=False,
    )
    assert rep.overlap_efficiency == 1.0
    assert rep.exposed_comm_s == 0.0
    assert "beat the modeled ideal" in rep.model_note
    # measured worse than fully serial -> clipped to 0 + note
    rep = OV.audit_train_step(
        ts, None, None, alpha=1e-3, beta=1e-6, mode="dear",
        measured_step_s=50e-3, compute_time_s=10e-3, include_hlo=False,
    )
    assert rep.overlap_efficiency == 0.0
    assert "exceeds the serial model" in rep.model_note
    # no measurement -> exposure split honestly absent, never guessed
    rep = OV.audit_train_step(
        ts, None, None, alpha=1e-3, beta=1e-6, mode="dear",
        include_hlo=False,
    )
    assert rep.exposed_comm_s is None and rep.overlap_efficiency is None


def test_render_text_and_comparison():
    from dear_pytorch_tpu.observability import report as R

    ts = _StubTS(plan=_one_bucket_plan())
    rep = OV.audit_train_step(
        ts, None, None, alpha=1e-3, beta=1e-6, mode="dear",
        measured_step_s=16e-3, compute_time_s=10e-3, include_hlo=False,
    )
    text = R.render_text(rep)
    assert "overlap audit: mode=dear" in text
    assert "reduce_scatter" in text and "all_gather" in text
    cmp_text = R.render_comparison({"dear": rep, "allreduce": rep})
    assert "mode comparison" in cmp_text and "allreduce" in cmp_text
    tel = R.render_telemetry({"enabled": True, "counters": {"steps": 3},
                              "spans": {"s": {"count": 1,
                                              "total_us": 12.0}}})
    assert "steps = 3" in tel


# ---------------------------------------------------------------------------
# instrumentation: the train step feeds the tracer
# ---------------------------------------------------------------------------


def test_dear_step_counters_and_spans(mesh):
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import build_train_step

    mem = T.MemoryExporter()
    tr = T.Tracer([mem])
    T.set_tracer(tr)

    params = {"l0": {"w": jnp.zeros((16, 16)), "b": jnp.zeros((16,))},
              "l1": {"w": jnp.zeros((16, 16))}}

    def loss(p, b):
        x = jnp.tanh(b @ p["l0"]["w"] + p["l0"]["b"])
        return jnp.mean((x @ p["l1"]["w"]) ** 2)

    ts = build_train_step(
        loss, params, mesh=mesh, mode="dear", nearby_layers=1,
        optimizer=fused_sgd(lr=0.01), donate=False,
    )
    state = ts.init(params)
    batch = jnp.ones((8, 16))
    for _ in range(3):
        state, _ = ts.step(state, batch)
    counters = tr.counters()
    assert counters["dear.plan_builds"] == 1
    assert counters["dear.steps"] == 3
    assert counters["dear.compiles"] == 1  # one structure -> one program
    acct = CTR.plan_comm_accounting(ts.plan, mode="dear", comm_itemsize=4)
    assert counters["dear.reduce_scatter_bytes"] == (
        3 * acct.leg_bytes_per_step("reduce_scatter"))
    assert counters["dear.all_gather_bytes"] == (
        3 * acct.leg_bytes_per_step("all_gather"))
    assert sum(1 for s in mem.spans if s.name == "dear.step") == 3
    assert any(e.name == "dear.plan_built" for e in mem.events)

    # disabled tracer: the same step path must not record anything
    T.set_tracer(T.NullTracer())
    state, _ = ts.step(state, batch)
    assert sum(1 for s in mem.spans if s.name == "dear.step") == 3


def test_pipeline_span(monkeypatch):
    from dear_pytorch_tpu.runtime import pipeline as P

    mem = T.MemoryExporter()
    T.set_tracer(T.Tracer([mem]))
    pipe = P.NumpyPipeline(P.mnist_spec(4), seed=0)
    batch = pipe.next()
    assert batch["image"].shape == (4, 28, 28, 1)
    assert [s.name for s in mem.spans] == ["pipeline.next"]
    assert T.get_tracer().counters()["pipeline.batches"] == 1


# ---------------------------------------------------------------------------
# telemetry block round-trips
# ---------------------------------------------------------------------------


def test_telemetry_roundtrip_metrics_and_driver(tmp_path):
    from dear_pytorch_tpu.benchmarks import driver
    from dear_pytorch_tpu.utils import MetricsLogger, read_metrics

    snap = {"enabled": True,
            "counters": {"dear.steps": 10, "dear.compiles": 1},
            "spans": {"dear.step": {"count": 10, "total_us": 123.4}}}

    # JSONL leg: the runner writes the block as a JSON string scalar
    mpath = str(tmp_path / "m.jsonl")
    with MetricsLogger(mpath) as ml:
        ml.log(step=9, loss=0.5)
        ml.log(kind="telemetry", telemetry=json.dumps(snap))
    recs = read_metrics(mpath)
    assert json.loads(recs[-1]["telemetry"]) == snap

    # driver leg: the TELEMETRY line is scraped from a cell log
    log = tmp_path / "cell.log"
    log.write_text(
        "Running benchmark...\n"
        f"TELEMETRY {json.dumps(snap)}\n"
        "Total img/sec on 8 CPU(s): 1234.5 +-10.0\n"
    )
    assert driver.extract_telemetry(str(log)) == snap
    assert driver.extract_log(str(log)) == (1234.5, 10.0)
    assert driver.extract_telemetry(str(tmp_path / "missing.log")) is None
    # unparsable telemetry is absent, not fatal
    bad = tmp_path / "bad.log"
    bad.write_text("TELEMETRY {not json}\n")
    assert driver.extract_telemetry(str(bad)) is None


def test_runner_emits_telemetry_line(capsys, tmp_path):
    from dear_pytorch_tpu.benchmarks import runner
    from dear_pytorch_tpu.utils import MetricsLogger, read_metrics

    T.configure()
    T.get_tracer().count("dear.steps", 4)
    mpath = str(tmp_path / "m.jsonl")
    with MetricsLogger(mpath) as ml:
        runner.run_timed(
            lambda: None, batch_size=1, num_warmup_batches=0,
            num_batches_per_iter=1, num_iters=1, metrics=ml,
        )
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("TELEMETRY "))
    snap = json.loads(line[len("TELEMETRY "):])
    assert snap["counters"]["dear.steps"] == 4
    recs = read_metrics(mpath)
    tel = [r for r in recs if r.get("kind") == "telemetry"]
    assert len(tel) == 1 and json.loads(tel[0]["telemetry"]) == snap


def test_run_timed_respects_health_warmup_env(monkeypatch):
    from dear_pytorch_tpu.benchmarks import runner
    from dear_pytorch_tpu.observability import anomaly as AN

    T.configure()
    built = []
    real = AN.AnomalyMonitor.from_env.__func__

    def spy(cls, **kw):
        m = real(cls, **kw)
        built.append(m)
        return m

    monkeypatch.setattr(AN.AnomalyMonitor, "from_env", classmethod(spy))
    kwargs = dict(batch_size=1, num_warmup_batches=0,
                  num_batches_per_iter=1, num_iters=1)
    monkeypatch.delenv("DEAR_HEALTH_WARMUP", raising=False)
    runner.run_timed(lambda: None, **kwargs)
    assert built[-1].warmup == 2  # benchmark default: few iters, arm early
    monkeypatch.setenv("DEAR_HEALTH_WARMUP", "7")
    runner.run_timed(lambda: None, **kwargs)
    assert built[-1].warmup == 7  # the documented env knob wins


# ---------------------------------------------------------------------------
# overhead contract
# ---------------------------------------------------------------------------


def test_overhead_script_fast_and_green(capsys):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "check_telemetry_overhead",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts",
            "check_telemetry_overhead.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--iters", "2000"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["ok"] is True
    # the acceptance bar: the disabled gates are far below 1% of any real
    # step (~1 ms step -> 10 us budget; each gate must sit under 1 us —
    # generous for this container: measured ~100-300 ns)
    assert out["disabled_ns_per_call"] < 1000.0
    assert out["flight_disabled_ns_per_call"] < 1000.0
    # the SDC sentinel's recurring host shape when DEAR_SDC is off (the
    # fingerprint itself is in-program, so this attribute check is the
    # entire disabled cost) sits under the same budget
    assert out["sdc_disabled_ns_per_call"] < 1000.0
    # the profiler span `ts.step` always enters, with no session active
    assert out["step_annotation_idle_ns_per_call"] < 1000.0
    # the enabled flight record stays production-cheap too (micro-seconds)
    assert out["flight_enabled_ns_per_call"] < 100_000.0


# ---------------------------------------------------------------------------
# docs <-> code counter audit — now a dearlint rule on the shared scanner
# ---------------------------------------------------------------------------


def test_counter_docs_in_sync():
    """docs/OBSERVABILITY.md's counter tables are load-bearing: every
    counter the code emits must be documented, and every documented
    counter must exist in code — in both directions, so the tables can't
    rot (the `retry.attempts` incident: a counter documented before it
    was wired). The audit itself lives in the static-analysis suite
    (`analysis.rules_registry.CounterDocsRule`, docs/ANALYSIS.md) so the
    repo has ONE source-walking layer; this test drives that rule over
    the live tree and keeps the historical assertion surface."""
    from dear_pytorch_tpu.analysis.core import Scanner, repo_root
    from dear_pytorch_tpu.analysis.rules_registry import CounterDocsRule

    import os

    scanner = Scanner([os.path.join(repo_root(), "dear_pytorch_tpu")])
    findings = list(CounterDocsRule().check(scanner))
    # scanner-rot sentinels surface as findings too — an empty result
    # really means "both sides parsed and agree"
    assert not findings, "counter <-> docs audit violations:\n" + "\n".join(
        f.render() for f in findings)
