"""Micro-benchmark of the telemetry hot path: disabled vs enabled gates.

The contract (docs/OBSERVABILITY.md) is that a DISABLED tracer costs an
instrumented call site one `get_tracer()` module lookup plus one
``.enabled`` attribute read — so instrumenting the training step is free
when telemetry is off. The flight recorder (`observability.flight`) makes
the SAME promise for its per-step `get_recorder()` gate. This script
measures both gates, the way `parallel/dear.py`'s ``step()`` and
`utils/guard.py`'s step path execute them, and compares against the
enabled paths (counter add + span; ring record) and an UNinstrumented
baseline loop.

Pure host-side Python, no devices, so it runs anywhere in about a second
(tier-1 safe; tests/test_observability.py drives `main` with small
iteration counts). The telemetry machinery is loaded standalone, without
jax; jax itself is imported (no backend is initialised) only for the one
gate that is jax's own: the `jax.profiler.TraceAnnotation("dear.step")`
that `ts.step` enters on every call, measured with no profiler session
active. Prints one JSON line:

  {"disabled_ns_per_call": ..., "enabled_ns_per_call": ...,
   "flight_disabled_ns_per_call": ..., "flight_enabled_ns_per_call": ...,
   "baseline_ns_per_call": ..., "disabled_overhead_ns": ...,
   "budget_ns": 1000.0, "ok": true}

``ok`` asserts EVERY disabled gate costs under ``--budget-ns`` (default
1 µs — three orders of magnitude below a ~1 ms device step, i.e. the
"< 1% of step time, unmeasurable" acceptance bar with huge margin).

Usage: python scripts/check_telemetry_overhead.py [--iters N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import timeit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _bench(fn, iters: int) -> float:
    """Best-of-5 nanoseconds per call (min is the standard micro-bench
    estimator: noise only ever adds time)."""
    best = min(timeit.repeat(fn, repeat=5, number=iters))
    return best / iters * 1e9


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=200_000)
    ap.add_argument("--budget-ns", type=float, default=1000.0,
                    help="max allowed disabled-gate cost per call")
    args = ap.parse_args(argv)

    def _analysis_modules():
        return {m for m in sys.modules
                if m == "dear_pytorch_tpu.analysis"
                or m.startswith("dear_pytorch_tpu.analysis.")}

    def _sim_modules():
        return {m for m in sys.modules
                if m == "dear_pytorch_tpu.observability.sim"
                or m.startswith("dear_pytorch_tpu.observability.sim.")}

    # snapshot before the telemetry machinery loads (the test harness
    # may legitimately have the analyzer imported already — what must
    # be zero is what the HOT-PATH machinery itself drags in)
    analysis_pre = _analysis_modules()
    sim_pre = _sim_modules()

    # Load tracer.py standalone (importlib, not the package): importing
    # dear_pytorch_tpu.observability would execute the package __init__
    # and drag jax + the comm backend into this process, breaking the
    # "no jax, runs anywhere" contract above. tracer.py itself is
    # stdlib-only at module level.
    import importlib.util

    def load_standalone(name: str, filename: str):
        spec = importlib.util.spec_from_file_location(
            name,
            os.path.join(REPO, "dear_pytorch_tpu", "observability",
                         filename),
        )
        mod = importlib.util.module_from_spec(spec)
        # register BEFORE exec: dataclasses resolve string annotations
        # through sys.modules[cls.__module__] (planspace.py needs this)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        return mod

    T = load_standalone("_telemetry_tracer", "tracer.py")
    FL = load_standalone("_telemetry_flight", "flight.py")

    def baseline():
        # the uninstrumented call-site shape: one function call
        time.perf_counter is not None  # noqa: B015

    T.set_tracer(T.NullTracer())

    def disabled_gate():
        tr = T.get_tracer()
        if tr.enabled:  # pragma: no cover - disabled branch
            tr.count("dear.steps")

    live = T.Tracer([T.MemoryExporter()])

    def enabled_site():
        tr = live
        if tr.enabled:
            tr.count("dear.steps")
            with tr.span("dear.step"):
                pass

    # flight recorder gates, the way utils/guard.py's step path runs them
    FL.set_recorder(FL.NullFlightRecorder())

    def flight_disabled_gate():
        fl = FL.get_recorder()
        if fl.enabled:  # pragma: no cover - disabled branch
            fl.record(0)

    live_fl = FL.FlightRecorder(capacity=64, tracer=T.NullTracer())

    def flight_enabled_site():
        fl = live_fl
        if fl.enabled:
            fl.record(0, step_time_s=1e-3)

    # kernel-telemetry gates, the way ops/collective_matmul.py's ring-
    # kernel builders (`_count_build`) and parallel/dear.py's dear-fused
    # per-step launch accounting execute them: count + event under one
    # enabled check. Same disabled-cost contract as the step gates.
    def kernel_disabled_gate():
        tr = T.get_tracer()
        if tr.enabled:  # pragma: no cover - disabled branch
            tr.count("kernel.fused_rs_builds")
            tr.event("kernel.fused_rs_build")

    def kernel_enabled_site():
        tr = live
        if tr.enabled:
            tr.count("kernel.fused_rs_builds")
            tr.event("kernel.fused_rs_build", elements=1024, world=8)

    # serve-path gates, the way serving/{admission,router,engine}.py run
    # them on the request hot path (admission decision, response
    # completion, engine tick): count + event under one enabled check —
    # the serving stack's per-request cost when telemetry is off must be
    # the same two lookups as the training step's.
    def serve_disabled_gate():
        tr = T.get_tracer()
        if tr.enabled:  # pragma: no cover - disabled branch
            tr.count("serve.requests")
            tr.event("serve.shed", depth=3)

    def serve_enabled_site():
        tr = live
        if tr.enabled:
            tr.count("serve.requests")
            tr.event("serve.shed", depth=3, predicted_wait_s=0.01)

    # engine per-tick phase gates, the way serving/engine.py's chunked
    # prefill and decode ticks run them (one counter under one enabled
    # check per tick; the per-phase latency rings are plain deque
    # appends, accounted separately below as the ALWAYS-ON cost of the
    # split admission estimates — they too must stay under the budget)
    def serve_phase_disabled_gate():
        tr = T.get_tracer()
        if tr.enabled:  # pragma: no cover - disabled branch
            tr.count("serve.prefill_steps")

    import collections

    _phase_ring = collections.deque(maxlen=256)

    def serve_phase_ring_append():
        _phase_ring.append(0.00123)

    # online-loop gates, the way online/feedback.py's append (the decode
    # hot path's only feedback cost) and online/ingest.py's per-step
    # cursor accounting run them: count (+ event on the cursor side)
    # under one enabled check. The serving fleet's feedback plumbing and
    # the trainer's ingest must be free when telemetry is off — same
    # 1 µs budget as every other step-path gate.
    def online_append_disabled_gate():
        tr = T.get_tracer()
        if tr.enabled:  # pragma: no cover - disabled branch
            tr.count("online.records_appended")

    def online_append_enabled_site():
        tr = live
        if tr.enabled:
            tr.count("online.records_appended")

    def online_cursor_disabled_gate():
        tr = T.get_tracer()
        if tr.enabled:  # pragma: no cover - disabled branch
            tr.count("online.records_trained", 8)
            tr.count("online.ingest_lag", 3)
            tr.event("online.cursor_restored", consumed=100)

    def online_cursor_enabled_site():
        tr = live
        if tr.enabled:
            tr.count("online.records_trained", 8)
            tr.count("online.ingest_lag", 3)

    # quality-gate admit gate, the way online/quality.py's admit() runs
    # it per ingest step: the per-record check() is pure arithmetic with
    # NO telemetry (rejects accumulate in a plain dict), and one batched
    # count+event block fires per admit call that saw rejects — so the
    # disabled shape on the step path is the standard two lookups.
    def online_quality_disabled_gate():
        tr = T.get_tracer()
        if tr.enabled:  # pragma: no cover - disabled branch
            tr.count("online.records_rejected_schema", 2)
            tr.event("online.quality_rejected", schema=2)

    def online_quality_enabled_site():
        tr = live
        if tr.enabled:
            tr.count("online.records_rejected_schema", 2)
            tr.event("online.quality_rejected", schema=2)

    # canary-gauge gate, the way serving/router.py's response-collection
    # path runs it when a verdict lands (observe() itself is plain dict
    # arithmetic — no telemetry per observation; the count+event pair
    # fires once per VERDICT, but its disabled shape must still budget)
    def canary_disabled_gate():
        tr = T.get_tracer()
        if tr.enabled:  # pragma: no cover - disabled branch
            tr.count("online.canary_verdicts")
            tr.event("online.canary_verdict", version=2, verdict="FAIL")

    def canary_enabled_site():
        tr = live
        if tr.enabled:
            tr.count("online.canary_verdicts")
            tr.event("online.canary_verdict", version=2, verdict="FAIL")

    # degraded-DCN ladder gates, the way comm/dcn.py's exchange runs
    # them on the host leg of every hierarchical step: the per-round
    # accounting (degraded_rounds + skips) and the per-chunk integrity
    # reject (count + event) each fire under one enabled check — in
    # strict healthy rounds neither branch is taken, so the disabled
    # shape on the step path is the standard two lookups.
    def dcn_round_disabled_gate():
        tr = T.get_tracer()
        if tr.enabled:  # pragma: no cover - disabled branch
            tr.count("dcn.degraded_rounds")
            tr.count("dcn.skips", 1)

    def dcn_round_enabled_site():
        tr = live
        if tr.enabled:
            tr.count("dcn.degraded_rounds")
            tr.count("dcn.skips", 1)

    def dcn_reject_disabled_gate():
        tr = T.get_tracer()
        if tr.enabled:  # pragma: no cover - disabled branch
            tr.count("dcn.chunk_rejects")
            tr.event("dcn.chunk_reject", slice=1, bucket=0, chunk=0)

    def dcn_reject_enabled_site():
        tr = live
        if tr.enabled:
            tr.count("dcn.chunk_rejects")
            tr.event("dcn.chunk_reject", slice=1, bucket=0, chunk=0)

    # fleet-trace span-stream gates (observability/dtrace.py), the two
    # hot-path call-site shapes: the engine-tick emission the way
    # serving/engine.py's prefill/decode ticks run it, and the DCN-round
    # shape the way comm/dcn.py's exchange runs it — the latter also
    # builds the deterministic step-trace context + wire header inside
    # the gate, so the DISABLED shape must still be the standard two
    # lookups (no context construction, no clock read). dtrace.py is
    # stdlib-only at module level, same standalone-load contract as the
    # tracer and flight recorder.
    DT = load_standalone("_telemetry_dtrace", "dtrace.py")
    DT.set_stream(DT.NullStream())

    def trace_tick_disabled_gate():
        ds = DT.get_stream()
        if ds.enabled:  # pragma: no cover - disabled branch
            ds.emit("serve.decode_tick", dur_s=1e-3, cat="serve",
                    batch=4)

    def trace_dcn_disabled_gate():
        ds = DT.get_stream()
        if ds.enabled:  # pragma: no cover - disabled branch
            ctx = DT.step_trace(0, 1)
            ds.emit("dcn.round", dur_s=1e-3, cat="comm", trace=ctx,
                    step=1, mem_epoch=0, included=2, world=2)

    live_ds = DT.SpanStream(DT.MemoryWriter(), rank=0)

    def trace_tick_enabled_site():
        ds = live_ds
        if ds.enabled:
            ds.emit("serve.decode_tick", dur_s=1e-3, cat="serve",
                    batch=4)

    def trace_dcn_enabled_site():
        ds = live_ds
        if ds.enabled:
            ctx = DT.step_trace(0, 1)
            ds.emit("dcn.round", dur_s=1e-3, cat="comm", trace=ctx,
                    step=1, mem_epoch=0, included=2, world=2)

    # SDC-sentinel gate, the way utils/guard.py's health-sync path runs
    # it when DEAR_SDC is off: the per-bucket fingerprint itself is
    # IN-PROGRAM (compiled into the step when armed, simply absent from
    # the program otherwise — zero host cost either way, no device
    # sync), so the only recurring host shape is one attribute check on
    # the sentinel slot plus the standard tracer gate for the vote
    # counters. That check must budget like every other step-path gate.
    class _SdcSlot:
        sentinel = None

    _sdc_slot = _SdcSlot()

    def sdc_disabled_gate():
        if _sdc_slot.sentinel is not None:  # pragma: no cover
            tr = T.get_tracer()
            if tr.enabled:
                tr.count("sdc.votes")

    def sdc_enabled_site():
        tr = live
        if tr.enabled:
            tr.count("sdc.votes")
            tr.count("sdc.suspected", 0)

    # plan-tuner decision-loop gate, the way tuning/autotune.py's step
    # path runs it once the search has FINISHED (or never started): the
    # per-step cost must be one attribute check + return — the tuner
    # decision loop stays off the step hot path when disabled. planspace
    # imports lazily (numpy only at module level), so it loads standalone
    # under the same no-jax contract.
    PS = load_standalone(
        "_telemetry_planspace",
        os.path.join("..", "tuning", "planspace.py"),
    )
    space = PS.PlanSpace(modes=("dear",), compressors=(None,),
                         comm_dtypes=(None,), gather_dtypes=(None,),
                         remats=(None,))
    finished_tuner = PS.PlanTuner(space, max_trials=1, interval=5,
                                  log=lambda s: None,
                                  tracer=T.NullTracer(), trial_log=None)
    finished_tuner.finished = True

    def plan_tuner_finished_gate():
        finished_tuner.step()

    # the profiler span around the dispatch in parallel/dear.py's
    # ``step()`` (and the three stretches of ``_hier_step``): always
    # entered, so what it costs with NO profiler session active is part
    # of every step's host time and sits under the same budget
    from jax.profiler import TraceAnnotation

    def step_annotation_idle():
        with TraceAnnotation("dear.step"):
            pass

    baseline_ns = _bench(baseline, args.iters)
    ann_idle_ns = _bench(step_annotation_idle, args.iters)
    disabled_ns = _bench(disabled_gate, args.iters)
    enabled_ns = _bench(enabled_site, max(args.iters // 10, 1))
    fl_disabled_ns = _bench(flight_disabled_gate, args.iters)
    fl_enabled_ns = _bench(flight_enabled_site, max(args.iters // 10, 1))
    k_disabled_ns = _bench(kernel_disabled_gate, args.iters)
    k_enabled_ns = _bench(kernel_enabled_site, max(args.iters // 10, 1))
    s_disabled_ns = _bench(serve_disabled_gate, args.iters)
    s_enabled_ns = _bench(serve_enabled_site, max(args.iters // 10, 1))
    sp_disabled_ns = _bench(serve_phase_disabled_gate, args.iters)
    sp_ring_ns = _bench(serve_phase_ring_append, args.iters)
    oa_disabled_ns = _bench(online_append_disabled_gate, args.iters)
    oa_enabled_ns = _bench(online_append_enabled_site,
                           max(args.iters // 10, 1))
    oc_disabled_ns = _bench(online_cursor_disabled_gate, args.iters)
    oc_enabled_ns = _bench(online_cursor_enabled_site,
                           max(args.iters // 10, 1))
    oq_disabled_ns = _bench(online_quality_disabled_gate, args.iters)
    oq_enabled_ns = _bench(online_quality_enabled_site,
                           max(args.iters // 10, 1))
    cn_disabled_ns = _bench(canary_disabled_gate, args.iters)
    cn_enabled_ns = _bench(canary_enabled_site, max(args.iters // 10, 1))
    dr_disabled_ns = _bench(dcn_round_disabled_gate, args.iters)
    dr_enabled_ns = _bench(dcn_round_enabled_site,
                           max(args.iters // 10, 1))
    dj_disabled_ns = _bench(dcn_reject_disabled_gate, args.iters)
    dj_enabled_ns = _bench(dcn_reject_enabled_site,
                           max(args.iters // 10, 1))
    tt_disabled_ns = _bench(trace_tick_disabled_gate, args.iters)
    tt_enabled_ns = _bench(trace_tick_enabled_site,
                           max(args.iters // 10, 1))
    td_disabled_ns = _bench(trace_dcn_disabled_gate, args.iters)
    td_enabled_ns = _bench(trace_dcn_enabled_site,
                           max(args.iters // 10, 1))
    sdc_disabled_ns = _bench(sdc_disabled_gate, args.iters)
    sdc_enabled_ns = _bench(sdc_enabled_site, max(args.iters // 10, 1))
    tuner_finished_ns = _bench(plan_tuner_finished_gate, args.iters)
    overhead_ns = max(disabled_ns - baseline_ns, 0.0)

    # The static-analysis suite (dear_pytorch_tpu/analysis, docs/
    # ANALYSIS.md) is pure host tooling: no runtime module may import it
    # (tests/test_analysis.py pins the import graph), so loading and
    # exercising every telemetry gate above must have pulled in exactly
    # zero analysis modules — its hot-path cost is zero imports, zero
    # bytes.
    analysis_loaded = bool(_analysis_modules() - analysis_pre)
    # Same contract for the simulator (dearsim is offline tooling: 875
    # threads, event heaps, a virtual-time transport — none of it may
    # ride along when the hot-path gates load)
    sim_loaded = bool(_sim_modules() - sim_pre)

    out = {
        "analysis_imported": analysis_loaded,
        "sim_imported": sim_loaded,
        "baseline_ns_per_call": round(baseline_ns, 1),
        "disabled_ns_per_call": round(disabled_ns, 1),
        "enabled_ns_per_call": round(enabled_ns, 1),
        "flight_disabled_ns_per_call": round(fl_disabled_ns, 1),
        "flight_enabled_ns_per_call": round(fl_enabled_ns, 1),
        "kernel_disabled_ns_per_call": round(k_disabled_ns, 1),
        "kernel_enabled_ns_per_call": round(k_enabled_ns, 1),
        "serve_disabled_ns_per_call": round(s_disabled_ns, 1),
        "serve_enabled_ns_per_call": round(s_enabled_ns, 1),
        "serve_phase_disabled_ns_per_call": round(sp_disabled_ns, 1),
        "serve_phase_ring_ns_per_call": round(sp_ring_ns, 1),
        "online_append_disabled_ns_per_call": round(oa_disabled_ns, 1),
        "online_append_enabled_ns_per_call": round(oa_enabled_ns, 1),
        "online_cursor_disabled_ns_per_call": round(oc_disabled_ns, 1),
        "online_cursor_enabled_ns_per_call": round(oc_enabled_ns, 1),
        "online_quality_disabled_ns_per_call": round(oq_disabled_ns, 1),
        "online_quality_enabled_ns_per_call": round(oq_enabled_ns, 1),
        "canary_disabled_ns_per_call": round(cn_disabled_ns, 1),
        "canary_enabled_ns_per_call": round(cn_enabled_ns, 1),
        "dcn_round_disabled_ns_per_call": round(dr_disabled_ns, 1),
        "dcn_round_enabled_ns_per_call": round(dr_enabled_ns, 1),
        "dcn_reject_disabled_ns_per_call": round(dj_disabled_ns, 1),
        "dcn_reject_enabled_ns_per_call": round(dj_enabled_ns, 1),
        "trace_tick_disabled_ns_per_call": round(tt_disabled_ns, 1),
        "trace_tick_enabled_ns_per_call": round(tt_enabled_ns, 1),
        "trace_dcn_disabled_ns_per_call": round(td_disabled_ns, 1),
        "trace_dcn_enabled_ns_per_call": round(td_enabled_ns, 1),
        "sdc_disabled_ns_per_call": round(sdc_disabled_ns, 1),
        "sdc_enabled_ns_per_call": round(sdc_enabled_ns, 1),
        "tuner_finished_ns_per_call": round(tuner_finished_ns, 1),
        "step_annotation_idle_ns_per_call": round(ann_idle_ns, 1),
        "disabled_overhead_ns": round(overhead_ns, 1),
        "budget_ns": args.budget_ns,
        "ok": (not analysis_loaded
               and not sim_loaded
               and disabled_ns <= args.budget_ns
               and fl_disabled_ns <= args.budget_ns
               and k_disabled_ns <= args.budget_ns
               and s_disabled_ns <= args.budget_ns
               and sp_disabled_ns <= args.budget_ns
               and sp_ring_ns <= args.budget_ns
               and oa_disabled_ns <= args.budget_ns
               and oc_disabled_ns <= args.budget_ns
               and oq_disabled_ns <= args.budget_ns
               and cn_disabled_ns <= args.budget_ns
               and dr_disabled_ns <= args.budget_ns
               and dj_disabled_ns <= args.budget_ns
               and tt_disabled_ns <= args.budget_ns
               and td_disabled_ns <= args.budget_ns
               and sdc_disabled_ns <= args.budget_ns
               and tuner_finished_ns <= args.budget_ns
               and ann_idle_ns <= args.budget_ns),
    }
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
