"""The schedule's timeline: `bucket_timeline` on a made-up compiled text with
one combined all-reduce fed by three buckets, a combined gather, an async
pair, and made-up intervals; the six readers on that run, on a program
without collectives and on one without `TrainStep.comm`; and every reader on
the CPU-compiled 4-device dear step under a trace made from its text."""

import re
import types

import jax
import pytest

from perfbench import bucket_timeline as B
from perfbench import cell as cells
from perfbench import harness, xplane

J = "jit(device_step)/shard_map/"
G = "replica_groups={{0,1,2,3}}"
TEXT = f"""\
HloModule jit_device_step

ENTRY %main.1 (p0: f32[8], p1: f32[8], p2: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  %p1 = f32[8]{{0}} parameter(1)
  %p2 = f32[8]{{0}} parameter(2)
  %all-gather-start.1 = (f32[8]{{0}}, f32[32]{{0}}) all-gather-start(%p0), {G}, dimensions={{0}}, metadata={{op_name="{J}dear/bucket0/gather/all_gather"}}
  %all-gather-done.1 = f32[32]{{0}} all-gather-done(%all-gather-start.1), metadata={{op_name="{J}dear/bucket0/gather/all_gather"}}
  %dus.1 = f32[32]{{0}} dynamic-update-slice(%p1, %p1)
  %dus.2 = f32[32]{{0}} dynamic-update-slice(%p2, %p2)
  %all-reduce.9 = (f32[32]{{0}}, f32[32]{{0}}) all-reduce(%dus.1, %dus.2), {G}, to_apply=%add
  %gte.91 = f32[32]{{0}} get-tuple-element(%all-reduce.9), index=0
  %gte.92 = f32[32]{{0}} get-tuple-element(%all-reduce.9), index=1
  %copy-start.2 = (f32[32]{{0}}, f32[32]{{0}}, u32[]) copy-start(%gte.92)
  %copy-done.2 = f32[32]{{0}} copy-done(%copy-start.2)
  %fusion.u0 = f32[32]{{0}} fusion(%all-gather-done.1), kind=kLoop, calls=%fc, metadata={{op_name="{J}dear/unpack/bucket0/dynamic_slice"}}
  %fusion.u1 = f32[32]{{0}} fusion(%gte.91), kind=kLoop, calls=%fc, metadata={{op_name="{J}dear/unpack/bucket1/dynamic_slice"}}
  %fusion.u2 = f32[32]{{0}} fusion(%copy-done.2), kind=kLoop, calls=%fc, metadata={{op_name="{J}dear/unpack/bucket2/dynamic_slice"}}
  %fusion.c0 = bf16[32]{{0}} fusion(%fusion.u0), kind=kLoop, calls=%fc, metadata={{op_name="{J}jvp(M)/h_0/mlp/convert_element_type"}}
  %fusion.f0 = f32[32]{{0}} fusion(%fusion.c0), kind=kLoop, calls=%fc, metadata={{op_name="{J}jvp(M)/h_0/mlp/dot_general"}}
  %fusion.f1 = f32[32]{{0}} fusion(%fusion.f0, %fusion.u1), kind=kLoop, calls=%fc, metadata={{op_name="{J}jvp(M)/h_1/mlp/dot_general"}}
  %fusion.f2 = f32[32]{{0}} fusion(%fusion.f1, %fusion.u2), kind=kLoop, calls=%fc, metadata={{op_name="{J}jvp(M)/h_2/mlp/dot_general"}}
  %fusion.b2 = f32[32]{{0}} fusion(%fusion.f2), kind=kLoop, calls=%fc, metadata={{op_name="{J}transpose(jvp(M))/h_2/mlp/dot_general"}}
  %fusion.p2 = bf16[32]{{0}} fusion(%fusion.b2), kind=kLoop, calls=%fc, metadata={{op_name="{J}dear/pack/bucket2/concatenate"}}
  %fusion.b1 = f32[32]{{0}} fusion(%fusion.b2), kind=kLoop, calls=%fc, metadata={{op_name="{J}transpose(jvp(M))/h_1/mlp/dot_general"}}
  %fusion.p1 = bf16[32]{{0}} fusion(%fusion.b1), kind=kLoop, calls=%fc, metadata={{op_name="{J}dear/pack/bucket1/convert_element_type"}}
  %bitcast.1 = bf16[32]{{0}} bitcast(%fusion.p1)
  %fusion.b0 = f32[32]{{0}} fusion(%fusion.b1), kind=kLoop, calls=%fc, metadata={{op_name="{J}transpose(jvp(M))/h_0/mlp/dot_general"}}
  %fusion.p0 = bf16[32]{{0}} fusion(%fusion.b0), kind=kLoop, calls=%fc, metadata={{op_name="{J}dear/pack/bucket0/concatenate"}}
  %copy-start.5 = (bf16[32]{{0}}, bf16[32]{{0}}, u32[]) copy-start(%fusion.p0)
  %copy-done.5 = bf16[32]{{0}} copy-done(%copy-start.5)
  %all-reduce.20 = (bf16[32]{{0}}, bf16[32]{{0}}, bf16[32]{{0}}) all-reduce(%fusion.p2, %bitcast.1, %copy-done.5), {G}, to_apply=%add
  %gte.0 = bf16[32]{{0}} get-tuple-element(%all-reduce.20), index=2
  %gte.1 = bf16[32]{{0}} get-tuple-element(%all-reduce.20), index=1
  %gte.2 = bf16[32]{{0}} get-tuple-element(%all-reduce.20), index=0
  %fusion.w0 = f32[8]{{0}} fusion(%gte.0), kind=kLoop, calls=%fc, metadata={{op_name="{J}dear/bucket0/update/sub"}}
  %fusion.w1 = f32[8]{{0}} fusion(%gte.1), kind=kLoop, calls=%fc, metadata={{op_name="{J}dear/bucket1/update/sub"}}
  %fusion.w2 = f32[8]{{0}} fusion(%gte.2), kind=kLoop, calls=%fc, metadata={{op_name="{J}dear/bucket2/update/sub"}}
  ROOT %psum.3 = f32[] all-reduce(%fusion.f2), {G}, to_apply=%add, metadata={{op_name="{J}dear/metrics/psum"}}
}}
"""

#: the account of three buckets of 32 elements on four devices: bf16
#: gradients, f32 parameters, the ring estimate 3/4 of the payload
ASKED = [B.Asked(g, leg, 32 * size, 32 * size * 3 / 4) for g in range(3)
         for leg, size in (("reduce_scatter", 2), ("all_gather", 4))]

#: one program run, nanoseconds from its start: (instruction, start, end)
SYNC = [("all-gather-done.1", 8, 10),          # the core waits 2 ns
        ("all-reduce.9", 10, 20), ("copy-done.2", 20, 22),
        ("fusion.u0", 22, 22.5), ("fusion.c0", 22.5, 23),
        ("fusion.f0", 23, 30),
        ("fusion.u1", 30, 31), ("fusion.f1", 31, 38),
        ("fusion.u2", 38, 39), ("fusion.f2", 39, 46),
        ("fusion.b2", 46, 52), ("fusion.p2", 52, 54),
        ("fusion.b1", 54, 60), ("fusion.p1", 60, 62),
        ("fusion.b0", 62, 68), ("fusion.p0", 68, 70),
        ("copy-done.5", 70, 74), ("all-reduce.20", 74, 90),
        ("fusion.w0", 90, 92), ("fusion.w1", 92, 94), ("fusion.w2", 94, 96),
        ("psum.3", 96, 97)]
ASYNC = [("all-gather-start.1", 2, 10), ("copy-start.5", 70, 74)]
EARLY = [("fusion.x", 0, 8)]     # other work the async gather hides under


def _op(name, start, end):
    return xplane.Op(name, f"%{name} = f32[] op()", start, end)


def _device(index=0, shift=0.0, runs=(0, 1000)):
    def at(events):
        return tuple(_op(n, r + s + shift, r + e + shift)
                     for r in runs for n, s, e in events)
    return xplane.Device(index, tuple(_op("jit_step", r + shift,
                                          r + 100 + shift) for r in runs),
                         at(EARLY + SYNC), at(ASYNC))


def _run(devices=None, text=TEXT, comm=True):
    ts = types.SimpleNamespace()
    if comm:
        ts.comm = types.SimpleNamespace(rows=ASKED)
    return {"trace": xplane.Trace(tuple(devices or (_device(),)), ()),
            "built": {"compiled_text": text, "ts": ts}}


def test_a_combined_all_reduce_carries_every_bucket_it_was_fed():
    found = {c.name: c for c in B.collectives(TEXT)}
    assert set(found) == {"all-gather-start.1", "all-reduce.9",
                          "all-reduce.20", "psum.3"}
    combined = found["all-reduce.20"]
    assert combined.leg == "reduce" and combined.buckets == (0, 1, 2)
    # each bucket's own producer: through a bitcast, through a copy
    assert combined.producers == {2: ("fusion.p2",), 1: ("fusion.p1",),
                                  0: ("fusion.p0",)}
    # ... and behind each pack, the backward pass's operation that feeds it
    assert combined.sources == {2: ("fusion.b2",), 1: ("fusion.b1",),
                                0: ("fusion.b0",)}
    assert combined.result_bytes == 3 * 32 * 2
    assert combined.wire_bytes == 2 * 3 / 4 * 3 * 32 * 2   # an all-reduce's
    gathers = found["all-reduce.9"]
    assert gathers.leg == "gather" and gathers.buckets == (1, 2)
    assert gathers.consumers == {1: ("gte.91",), 2: ("gte.92",)}
    assert found["psum.3"].leg == "other" and not found["psum.3"].buckets


def test_an_async_pair_is_one_collective_and_counted_asynchronous():
    found = {c.name: c for c in B.collectives(TEXT)}
    pair = found["all-gather-start.1"]
    assert pair.is_async and pair.opcode == "all-gather"
    assert pair.events == ("all-gather-start.1", "all-gather-done.1")
    assert pair.buckets == (0,) and pair.leg == "gather"
    assert pair.result_bytes == 32 * 4            # the done's result
    assert pair.wire_bytes == 3 / 4 * 32 * 4
    assert B.count_by_opcode(found.values()) == {
        "all-gather": (1, 1), "all-reduce": (3, 0)}
    t = B.build(_device(), TEXT, ASKED)
    assert t["async_per_step"] == 1 and t["asked_per_step"] == 6


def test_each_bucket_waits_from_its_own_producer():
    t = B.build(_device(), TEXT, ASKED)
    rows = {r["bucket"]: r for r in t["buckets"]}
    # ns -> ms; the all-reduce starts at 74: bucket 2 was packed at 54,
    # bucket 1 at 62 (behind a bitcast), bucket 0 at 70 (before its copy);
    # their gradients were finished by the backward pass at 52, 60 and 68
    assert [rows[g]["packed_ms"] for g in (2, 1, 0)] == pytest.approx(
        [54e-6, 62e-6, 70e-6])
    assert [rows[g]["finished_ms"] for g in (2, 1, 0)] == pytest.approx(
        [52e-6, 60e-6, 68e-6])
    assert [rows[g]["wait_ms"] for g in (2, 1, 0)] == pytest.approx(
        [22e-6, 14e-6, 6e-6])
    assert t["reduce_wait_ms"] == pytest.approx(14e-6)
    assert all(r["reduce_by"] == "all-reduce.20" for r in rows.values())
    # the gathered parameters' first use is the model's matmul, not the
    # unpack's slice, the model's cast of the weight nor the compiler's
    # copy; bucket 2's stands behind both
    assert rows[0]["gather_by"] == "all-gather-start.1"
    assert rows[0]["first_use_ms"] == pytest.approx(23e-6)
    assert rows[0]["slack_ms"] == pytest.approx(13e-6)
    assert rows[1]["gather_by"] == rows[2]["gather_by"] == "all-reduce.9"
    assert rows[1]["first_use_ms"] == pytest.approx(31e-6)
    assert rows[2]["first_use_ms"] == pytest.approx(39e-6)
    assert rows[2]["slack_ms"] == pytest.approx(19e-6)


def test_rates_times_and_the_exposed_share_agree_with_each_other():
    t = B.build(_device(), TEXT, ASKED)
    # reduce: 16 ns a run; gather: the async span 2..10 and 10..20
    assert t["leg_ms"]["reduce"] == pytest.approx(16e-6)
    assert t["leg_ms"]["gather"] == pytest.approx(18e-6)
    assert t["asked_wire_bytes"] == {"reduce": 3 * 48.0, "gather": 3 * 96.0}
    assert t["reduce_wire_gbps"] * t["leg_ms"]["reduce"] * 1e6 == (
        pytest.approx(144.0))
    assert t["gather_wire_gbps"] == pytest.approx(288.0 / 18)
    assert t["compiled_wire_bytes"]["reduce"] == 288.0    # twice the asked
    assert t["compiled_wire_bytes"]["gather"] == 96.0 + 2 * 3 / 4 * 256
    # 34 ns of collectives a run as `exposed_collective_ms` tells them (by
    # the event's name: not ``psum.3``); fusion.x hides 6 of the async
    # gather's 8
    assert t["collective_ms"] == pytest.approx(34e-6)
    assert t["exposed_ms"] == pytest.approx(28e-6)
    dev = _device()
    assert t["exposed_ms"] == pytest.approx(
        xplane.length(dev.exposed_collectives()) * 1e-6 / 2)
    assert t["exposed_share_pct"] == pytest.approx(100 * 28 / 34)
    assert t["exposed_share_pct"] <= 100.0


READERS = ("collectives_asked_per_step", "async_collectives_per_step",
           "reduce_wire_gbps", "gather_wire_gbps", "reduce_wait_ms",
           "exposed_collective_share_pct")


def test_the_readers_and_what_reports_nothing(capsys):
    run = _run()
    got = {name: cells.layer_reader(name)(run) for name in READERS}
    assert got == {
        "collectives_asked_per_step": 6.0,
        "async_collectives_per_step": 1.0,
        "reduce_wire_gbps": pytest.approx(9.0),
        "gather_wire_gbps": pytest.approx(16.0),
        "reduce_wait_ms": pytest.approx(14e-6),
        "exposed_collective_share_pct": pytest.approx(100 * 28 / 34)}
    assert all(isinstance(v, float) for v in got.values())
    logged = capsys.readouterr().out
    assert "[schedule] asked 6 collectives a step; compiled 4: " \
           "1 all-gather (1 asynchronous), 3 all-reduce (0 asynchronous)" \
           in logged
    assert logged.count("[schedule]      ") == 3      # one row a bucket
    # collectives, none asynchronous: 0.0, not nothing
    sync_text = TEXT.replace("all-gather-start(", "all-gather(").replace(
        "all-gather-start.1", "all-gather.1")
    sync_text = re.sub(r".*all-gather-done.*\n", "", sync_text).replace(
        "%all-gather-done.1", "%all-gather.1")
    assert cells.layer_reader("async_collectives_per_step")(
        _run(text=sync_text)) == 0.0
    # the parent's program gives no account: nothing, and nothing raises
    for name in READERS:
        assert cells.layer_reader(name)(_run(comm=False)) is None, name
    # a program without collectives
    bare = "\n".join(line for line in TEXT.splitlines()
                     if "all-" not in line)
    for name in READERS:
        assert cells.layer_reader(name)(_run(text=bare)) is None, name
    assert B.build(_device(), bare, ASKED) is None


def test_the_spread_over_the_chips_needs_a_shared_clock():
    found = B.collectives(TEXT)
    one_clock = [_device(0), _device(1, shift=3.0)]
    spread = B.start_spread(one_clock, found)
    assert spread["program_start_ns"] == 3.0
    assert spread["collective_start_us"] == pytest.approx((3e-3, 3e-3))
    apart = [_device(0), _device(1, shift=5e6)]
    assert B.start_spread(apart, found) == {
        "program_start_ns": 5e6, "collective_start_us": None}
    B.log_schedule(dict(B.build(one_clock[0], TEXT, ASKED), spread=spread),
                   print)


# -- on a compiled step -------------------------------------------------------

_ENTRY = re.compile(r"^ENTRY .*?\{\n(.*?)^\}", re.M | re.S)


def _trace_from_text(text: str, runs: int = 2):
    """A synchronous line made from the compiled text: every instruction of
    the entry computation runs for 10 ns, in the text's (scheduled) order."""
    names = [m.group(1) for line in _ENTRY.search(text).group(1).splitlines()
             if (m := B._INSTRUCTION.match(line))]
    length = 10.0 * len(names)
    ops = [_op(n, r * length + 10.0 * i, r * length + 10.0 * (i + 1))
           for r in range(runs) for i, n in enumerate(names)]
    modules = tuple(_op("jit_step", r * length, (r + 1) * length)
                    for r in range(runs))
    return xplane.Trace((xplane.Device(0, modules, tuple(ops), ()),), ())


def test_every_reader_on_the_cpu_compiled_dear_step(capsys):
    from dear_pytorch_tpu.comm import backend
    from test_cells_tiny import tiny_cell

    backend.shutdown()
    mesh = backend.init(devices=jax.devices()[:4])
    cell = tiny_cell("gpt2-124m", 4)
    cell.config["train"]["threshold_mb"] = 0.01      # several buckets
    try:
        built = harness.build(cell, mesh, seed=11)
    finally:
        backend.shutdown()
    ts, text = built["ts"], built["compiled_text"]
    buckets = ts.plan.num_buckets
    assert buckets >= 2
    run = {"trace": _trace_from_text(text), "built": built}
    got = {name: cells.layer_reader(name)(run) for name in READERS}
    assert got["collectives_asked_per_step"] == 2.0 * buckets
    assert got["async_collectives_per_step"] == float(len(re.findall(
        r" (?:all-reduce|all-gather|reduce-scatter)-start\(", text)))
    t = B.of_run(run)
    by_leg = {leg: [c for c in t["collectives"] if c.leg == leg]
              for leg in ("reduce", "gather")}
    for leg, found in by_leg.items():
        # XLA:CPU keeps one collective a bucket and leg, each under its name
        assert sorted(g for c in found for g in c.buckets) == list(
            range(buckets)), leg
    for leg, account in (("reduce", "reduce_scatter"),
                         ("gather", "all_gather")):
        wire = sum(r.wire_bytes for r in ts.comm.rows if r.leg == account)
        assert got[leg + "_wire_gbps"] * t["leg_ms"][leg] * 1e6 == (
            pytest.approx(wire))
        # XLA:CPU promotes the bf16 reduce-scatters to f32: twice the asked
        assert t["compiled_wire_bytes"][leg] == pytest.approx(
            2 * wire if leg == "reduce" else wire)
    rows = t["buckets"]
    assert [r["bucket"] for r in rows] == list(range(buckets))
    assert all(r["wait_ms"] is not None and r["wait_ms"] >= 0 for r in rows)
    assert all(r["slack_ms"] is not None and r["slack_ms"] >= 0
               for r in rows)
    assert got["reduce_wait_ms"] == pytest.approx(
        sum(r["wait_ms"] for r in rows) / buckets)
    # XLA:CPU keeps JAX's names (``all_gather.3``), by which neither
    # `exposed_collective_ms` nor the share knows a collective
    assert got["exposed_collective_share_pct"] is None
    assert cells.layer_reader("exposed_collective_ms")(run) is None
    assert "[schedule] timeline built in" in capsys.readouterr().out
