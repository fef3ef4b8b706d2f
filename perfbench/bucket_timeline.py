"""The DeAR schedule's own timeline: what each bucket's collective was asked
to move, what the compiled program and the wire did with it, and how long a
finished gradient waited.

Three things are joined, for device 0 of a traced run:

  asked     the program's static account, ``TrainStep.comm`` (one row a
            bucket and leg: payload and ring-estimate wire bytes);
  compiled  the collectives of ``compiled.as_text()``: opcode, whether the
            compiler kept it asynchronous (a ``-start`` / ``-done`` pair),
            the bytes of its result, its leg (`scopes.leg_of`) and the SET
            of buckets it carries: every ``dear/pack/bucket<g>`` /
            ``dear/bucket<g>/reduce`` scope reachable through its operands
            (a reduce), every ``dear/unpack/bucket<g>`` /
            ``dear/bucket<g>/gather`` scope reachable through its users (a
            gather). `perfbench.scopes` gives a combined collective one
            bucket's name; here it keeps all of them;
  ran       the trace's events of those instructions, per program run.

A bucket's gradient is *packed* at the end of the last synchronous-line
event among the collective's operand instructions that belong to that bucket
(walking further back where an operand, a bitcast say, ran no event of its
own). XLA:TPU builds the pack in place, one dynamic-update-slice a leaf as
the backward pass produces it, and sinks the last write (the padding) to
just before the collective, so "packed" says when the compiler chose to
finish the copy, not when the gradient was there to send. The bucket's
gradient is *finished* at the end of the last operation of the model's
backward pass that computes a part of it (the compiler's layout copies of a
weight gradient, which it sinks to the end too, are movement, not
computation); it *waited* from then to the start of the collective that
carries it.

A program that gives no account of itself (``TrainStep.comm`` is new in PR
40) or holds no collective has no timeline: `of_run` returns ``None`` and
every reader reports nothing.

No JAX import; pure functions over text and intervals, like
`perfbench.scopes` (perfbench/tests/test_bucket_timeline.py).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import re
import statistics
import time

from perfbench import scopes, xplane

#: the five collective opcodes, as `harness.count_collectives` lists them
_OPCODE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
# the text's grammar is `perfbench.scopes`': one set of patterns for both
_INSTRUCTION, _COMPUTATION = scopes._INSTRUCTION, scopes._COMPUTATION
_FUSION_CALLS, _REFERENCE = scopes._FUSION_CALLS, scopes._REFERENCE
_OP_NAME = scopes._OP_NAME
_ARRAY = re.compile(r"\b(pred|[sufc]\d+|bf16|f8\w*)\[([\d,]*)\]")
_GROUP = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_IOTA_GROUP = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

#: ``dear/pack/bucket<g>``, ``dear/unpack/bucket<g>``, ``dear/bucket<g>/<leg>``
_COPY_BUCKET = re.compile(r"(?:^|[/(])dear/(pack|unpack)/bucket(\d+)(?:/|$)")
_LEG_BUCKET = re.compile(r"(?:^|[/(])dear/bucket(\d+)/(\w+)")
#: the scopes that say which bucket a collective carries, by leg (looked for
#: among a reduce's operands, among a gather's users)
_CARRIES = {"reduce": frozenset({"pack", "reduce"}),
            "gather": frozenset({"unpack", "gather"})}
#: a row of the account belongs to the gradient's or the parameters' leg
LEG_OF_ROW = {"reduce_scatter": "reduce", "all_reduce": "reduce",
              "reduce": "reduce", "all_gather": "gather",
              "broadcast": "gather"}
_ANY_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
#: opcodes that move or relabel data and compute nothing: the compiler's
#: layout copies keep the op_name of the matmul whose result they move, and
#: XLA:TPU sinks them to the end of the backward pass
_MOVEMENT = frozenset({
    "copy", "copy-start", "copy-done", "bitcast", "reshape", "transpose",
    "convert", "get-tuple-element", "tuple", "slice", "slice-start",
    "slice-done", "dynamic-slice", "dynamic-update-slice", "concatenate",
    "pad", "broadcast", "constant", "parameter"})
_WALK_DEPTH = 8
#: a pack reads a few dozen leaves through a few hundred instructions
_SOURCES_LIMIT = 4096
#: device planes whose programs start within this of each other share a clock
SHARED_CLOCK_NS = 100_000.0


@dataclasses.dataclass(frozen=True)
class Asked:
    """One row of the program's account (`counters.BucketCommRow`)."""

    bucket: int
    leg: str               # the account's name: reduce_scatter, all_gather, …
    payload_bytes: float
    wire_bytes: float


@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective of the compiled step; an async pair is one, named
    after its ``-start``."""

    name: str
    opcode: str
    is_async: bool
    leg: str               # reduce | gather | other
    buckets: tuple         # the bucket indices it carries, sorted
    events: tuple          # instructions whose trace events are its own
    dtype: str             # of its (first) result array: f32, bf16, …
    result_bytes: int      # bytes of its result array(s)
    wire_bytes: float      # the ring estimate for what was compiled
    producers: dict        # {bucket: operand instructions of that bucket}
    sources: dict          # {bucket: the model's instructions its pack reads}
    consumers: dict        # {bucket: instructions that use its result}


# -- the compiled text -------------------------------------------------------

def _dtype_bytes(dtype: str) -> float:
    if dtype == "pred" or dtype.startswith("f8"):
        return 1.0
    return int(re.sub(r"\D", "", dtype)) / 8.0


def _array_bytes(type_text: str) -> int:
    total = 0.0
    for dtype, dims in _ARRAY.findall(type_text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _dtype_bytes(dtype)
    return int(total)


def _group_size(line: str) -> int:
    listed = _GROUP.search(line)
    if listed:
        return len(listed.group(1).split(","))
    iota = _IOTA_GROUP.search(line)
    return int(iota.group(2)) if iota else 1


def _ring_wire_bytes(opcode: str, result_bytes: int, world: int) -> float:
    """`counters._wire_factor`'s ring estimate, from the RESULT's bytes: an
    all-gather's result is the payload, a reduce-scatter's a ``1/world``
    shard of it, an all-reduce moves a reduce-scatter and an all-gather."""
    if world <= 1:
        return 0.0
    ring = (world - 1) / world
    return {"all-gather": ring * result_bytes,
            "reduce-scatter": ring * result_bytes * world,
            "all-reduce": 2.0 * ring * result_bytes,
            "all-to-all": ring * result_bytes,
            "collective-permute": float(result_bytes)}[opcode]


def _bucket_scopes(op_name: str):
    """[(part, bucket)] of the ``dear/…bucket<g>…`` scopes in one op_name."""
    return ([(part, int(g)) for part, g in _COPY_BUCKET.findall(op_name)]
            + [(part, int(g)) for g, part in _LEG_BUCKET.findall(op_name)])


class _Program:
    """The compiled text as a graph: every instruction's line, operands,
    users, and the op_names it answers to (its own, as `scopes` repaired it,
    and for a fusion those of its fused instructions too)."""

    def __init__(self, compiled_text: str):
        self.lines, self.operands, self.opcodes = {}, {}, {}
        self.users = collections.defaultdict(list)
        members, calls = collections.defaultdict(list), {}
        computation = None
        for line in compiled_text.splitlines():
            if not line[:1].isspace():
                header = _COMPUTATION.match(line)
                computation = header.group(1) if header else None
                continue
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            name = m.group(1)
            self.lines[name] = line
            opcode = _ANY_OPCODE.search(line, m.end())
            self.opcodes[name] = opcode.group(1) if opcode else ""
            found = _OP_NAME.search(line, m.end())
            if found:
                members[computation].append(found.group(1))
            fusion = _FUSION_CALLS.search(line, m.end())
            if fusion:
                calls[name] = fusion.group(1)
        for name, line in self.lines.items():
            body = line.split(" = ", 1)[1].split(", metadata=", 1)[0]
            refs = [r for r in dict.fromkeys(_REFERENCE.findall(body))
                    if r in self.lines and r != name]
            self.operands[name] = refs
            for r in refs:
                self.users[r].append(name)
        self.scopes = scopes.instruction_scopes(compiled_text)
        self._members = {name: tuple(dict.fromkeys(members[c]))
                         for name, c in calls.items()}

    def sources(self, producers) -> tuple:
        """The instructions of the model's passes that compute what a
        bucket's pack reads: back from the pack's last instructions
        (``producers``) through the pack's own (``dear/pack/…``), the
        compiler's unnamed copies and dynamic-update-slices and everything
        that only moves data (`_MOVEMENT`), up to the first instruction on
        each path that a pass of the model names and that computes."""
        found, seen, frontier = [], set(producers), list(producers)
        while frontier and len(seen) < _SOURCES_LIMIT:
            nxt = []
            for n in frontier:
                for m in self.operands.get(n, ()):
                    if m in seen:
                        continue
                    seen.add(m)
                    own = self.scopes.get(m, "")
                    if self.opcodes[m] in _MOVEMENT:
                        nxt.append(m)
                    elif _in_a_pass(own):
                        found.append(m)
                    elif "dear/" not in own or "dear/pack/" in own:
                        nxt.append(m)
            frontier = nxt
        return tuple(found)

    def names_of(self, instruction: str) -> tuple:
        return ((self.scopes.get(instruction, ""),)
                + self._members.get(instruction, ()))

    def carried(self, starts, side: str, parts) -> dict:
        """{bucket: [the instructions nearest ``starts`` on ``side`` that
        carry that bucket's scope]}: ``starts`` themselves, then a walk
        through instructions that name neither a ``dear/`` scope nor a pass
        of the model (those belong to other buckets' forward and backward
        work), nearest first."""
        edges = self.operands if side == "operands" else self.users
        found = collections.defaultdict(list)
        seen, frontier = set(starts), list(starts)
        for _ in range(_WALK_DEPTH):
            nxt = []
            for m in frontier:
                hits = {g for name in self.names_of(m)
                        for part, g in _bucket_scopes(name) if part in parts}
                for g in hits:
                    found[g].append(m)
                if hits or _names_a_pass(self.scopes.get(m, "")):
                    continue
                for n in edges.get(m, ()):
                    if n not in seen:
                        seen.add(n)
                        nxt.append(n)
            frontier = nxt
        return dict(found)


def _in_a_pass(op_name: str) -> bool:
    return scopes.classify(op_name)[0] in ("forward", "backward")


def _names_a_pass(op_name: str) -> bool:
    return _in_a_pass(op_name) or "dear/" in op_name


@functools.lru_cache(maxsize=2)
def _collectives(compiled_text: str) -> tuple:
    program = _Program(compiled_text)
    out = []
    for name, line in program.lines.items():
        m = _OPCODE.search(line)
        if m is None or m.group(2) == "-done":
            continue
        opcode, is_async = m.group(1), m.group(2) == "-start"
        done = [u for u in program.users.get(name, ())
                if is_async and f" {opcode}-done(" in program.lines[u]]
        result_of = done[0] if done else name
        result_type = program.lines[result_of].split(" = ", 1)[1]
        result_type = result_type[:_OPCODE.search(result_type).start()]
        result_bytes = _array_bytes(result_type)
        dtype = _ARRAY.search(result_type)
        op_name = program.scopes.get(name, "")
        leg = scopes.leg_of(op_name)
        # its own scope, unless `scopes` lent it a neighbour's
        own = ({g for part, g in _bucket_scopes(op_name) if part == leg}
               if _OP_NAME.search(line) else set())
        producers, consumers = {}, {}
        if leg == "reduce":
            producers = program.carried(program.operands[name], "operands",
                                        _CARRIES["reduce"])
            if own:
                producers = {g: producers.get(g, program.operands[name])
                             for g in own}
        elif leg == "gather":
            users = program.users.get(result_of, ())
            if own:
                consumers = {g: users for g in own}
            else:
                # a combined gather: each user belongs to the buckets whose
                # unpack it leads to
                for u in users:
                    for g in program.carried([u], "users",
                                             _CARRIES["gather"]):
                        consumers.setdefault(g, []).append(u)
        out.append(Collective(
            name=name, opcode=opcode, is_async=is_async, leg=leg,
            buckets=tuple(sorted(own | set(producers) | set(consumers))),
            events=tuple([name] + done),
            dtype=dtype.group(1) if dtype else "", result_bytes=result_bytes,
            wire_bytes=_ring_wire_bytes(opcode, result_bytes,
                                        _group_size(line)),
            producers={g: tuple(v) for g, v in producers.items()},
            sources={g: program.sources(v) for g, v in producers.items()},
            consumers={g: tuple(v) for g, v in consumers.items()}))
    return tuple(out), program


def collectives(compiled_text: str) -> tuple:
    """Every collective of optimized HLO text, as `Collective`s."""
    return _collectives(compiled_text)[0]


def count_by_opcode(found) -> dict:
    """{opcode: (collectives, of them asynchronous)}."""
    out = collections.defaultdict(lambda: [0, 0])
    for c in found:
        out[c.opcode][0] += 1
        out[c.opcode][1] += c.is_async
    return {k: tuple(v) for k, v in sorted(out.items())}


# -- the trace ---------------------------------------------------------------

class _Events:
    """One device's events by instruction name, each list sorted by start."""

    def __init__(self, device):
        self.sync = collections.defaultdict(list)
        self.either = collections.defaultdict(list)
        for o in sorted(device.ops, key=lambda o: o.start):
            self.sync[o.name].append((o.start, o.end))
            self.either[o.name].append((o.start, o.end))
        for o in sorted(device.async_ops, key=lambda o: o.start):
            self.either[o.name].append((o.start, o.end))
        self.runs = sorted((m.start, m.end) for m in device.modules)

    @staticmethod
    def _within(events, lo, hi):
        starts = [s for s, _ in events]
        return events[bisect.bisect_left(starts, lo):
                      bisect.bisect_left(starts, hi)]

    def of(self, names, run, line="either") -> list:
        table = self.sync if line == "sync" else self.either
        return sorted(e for n in names
                      for e in self._within(table.get(n, ()), *run))


def _done(events: _Events, program: _Program, producers, run):
    """The end of the last synchronous-line event, inside one program run,
    among ``producers``; one that ran no event of its own (a bitcast, a
    tuple element) hands over to its operands."""
    ends, seen, frontier = [], set(producers), list(producers)
    for _ in range(_WALK_DEPTH):
        nxt = []
        for n in frontier:
            own = events.of((n,), run, "sync")
            if own:
                ends.append(max(e for _, e in own))
                continue
            for m in program.operands.get(n, ()):
                if m not in seen:
                    seen.add(m)
                    nxt.append(m)
        frontier = nxt
    return max(ends) if ends else None


def _first_use(events: _Events, program: _Program, consumers, run):
    """The start of the first synchronous-line event, inside one program
    run, of an instruction of the model's forward or backward pass that the
    gathered parameters reach: through the unpack's slices, the compiler's
    copies and the model's cast of a weight to its compute dtype, which are
    movement, not use."""
    starts, seen, frontier = [], set(consumers), list(consumers)
    for _ in range(2 * _WALK_DEPTH):
        nxt = []
        for n in frontier:
            name = program.scopes.get(n, "")
            if _in_a_pass(name) and not name.endswith("convert_element_type"):
                own = events.of((n,), run, "sync")
                if own:
                    starts.append(own[0][0])
                    continue
            for m in program.users.get(n, ()):
                if m not in seen:
                    seen.add(m)
                    nxt.append(m)
        frontier = nxt
    return min(starts) if starts else None


def _mean(values):
    values = [v for v in values if v is not None]
    return statistics.fmean(values) if values else None


def build(device, compiled_text: str, asked) -> dict | None:
    """The timeline of one device: see the module's docstring. ``asked`` is
    the account's rows as `Asked`. ``None`` where the program holds no
    collective or the trace ran none."""
    found, program = _collectives(compiled_text)
    events = _Events(device)
    lo, hi = device.window
    steps = len(events.runs)
    if not found or not steps:
        return None
    spans = {c.name: [events.of(c.events, run) for run in events.runs]
             for c in found}
    if not any(per_run for c in found for per_run in spans[c.name]):
        return None

    def per_step_ms(intervals) -> float:
        return xplane.length(xplane.clip(intervals, lo, hi)) * 1e-6 / steps

    # `exposed_collective_ms`' own collectives (told by the event's name,
    # so without a ``psum`` the program named itself): the share's two
    # sides are parts of one set
    everything = [(o.start, o.end) for o in device.ops + device.async_ops
                  if o.is_collective]
    by_leg = {leg: [e for c in found if c.leg == leg
                    for per_run in spans[c.name] for e in per_run]
              for leg in ("reduce", "gather", "other")}
    asked_wire = {leg: sum(a.wire_bytes for a in asked
                           if LEG_OF_ROW.get(a.leg) == leg)
                  for leg in ("reduce", "gather")}
    asked_payload = {(LEG_OF_ROW[a.leg], a.bucket): a.payload_bytes
                     for a in asked if a.leg in LEG_OF_ROW}
    leg_ms = {leg: per_step_ms(v) for leg, v in by_leg.items()}
    collective_ms = per_step_ms(everything)
    exposed_ms = xplane.length(device.exposed_collectives()) * 1e-6 / steps

    # per bucket: the collective that carries it on each leg, per run
    buckets = sorted({a.bucket for a in asked if a.leg in LEG_OF_ROW})
    rows = []
    for g in buckets:
        row = {"bucket": g,
               "reduce_payload_mb": asked_payload.get(("reduce", g), 0) / 1e6,
               "gather_payload_mb": asked_payload.get(("gather", g), 0) / 1e6}
        for leg in ("reduce", "gather"):
            carriers = [c for c in found if c.leg == leg and g in c.buckets]
            row[leg + "_by"] = ",".join(c.name for c in carriers) or None
            timed = collections.defaultdict(list)
            for k, run in enumerate(events.runs):
                ran = [(spans[c.name][k], c) for c in carriers
                       if spans[c.name][k]]
                if not ran:
                    continue
                own, c = min(ran, key=lambda r: r[0][0][0])
                start, end = own[0][0], max(e for _, e in own)
                timed["start"].append((start - run[0]) * 1e-6)
                timed["end"].append((end - run[0]) * 1e-6)
                if leg == "reduce":
                    for key, roots in (("packed", c.producers),
                                       ("finished", c.sources)):
                        at = _done(events, program, roots.get(g, ()), run)
                        if at is not None:
                            timed[key].append((at - run[0]) * 1e-6)
                else:
                    use = _first_use(events, program,
                                     c.consumers.get(g, ()), run)
                    if use is not None:
                        timed["use"].append((use - run[0]) * 1e-6)
            row[leg + "_start_ms"] = _mean(timed["start"])
            row[leg + "_end_ms"] = _mean(timed["end"])
            if leg == "reduce":
                row["finished_ms"] = _mean(timed["finished"])
                row["packed_ms"] = _mean(timed["packed"])
            else:
                row["first_use_ms"] = _mean(timed["use"])
        row["wait_ms"] = (
            None if None in (row["finished_ms"], row["reduce_start_ms"])
            else row["reduce_start_ms"] - row["finished_ms"])
        row["slack_ms"] = (
            None if None in (row["first_use_ms"], row["gather_end_ms"])
            else row["first_use_ms"] - row["gather_end_ms"])
        rows.append(row)

    def gbps(leg):
        ns = leg_ms[leg] * 1e6
        return asked_wire[leg] / ns if ns and asked_wire[leg] else None

    return {
        "steps": steps,
        "collectives": found,
        "asked_per_step": sum(1 for a in asked if a.leg in LEG_OF_ROW),
        "async_per_step": sum(c.is_async for c in found),
        "asked_wire_bytes": asked_wire,
        "compiled_wire_bytes": {
            leg: sum(c.wire_bytes for c in found if c.leg == leg)
            for leg in ("reduce", "gather", "other")},
        "leg_ms": leg_ms,
        "collective_ms": collective_ms,
        "exposed_ms": exposed_ms,
        "reduce_wire_gbps": gbps("reduce"),
        "gather_wire_gbps": gbps("gather"),
        "reduce_wait_ms": _mean(r["wait_ms"] for r in rows),
        "exposed_share_pct": (100.0 * exposed_ms / collective_ms
                              if collective_ms else None),
        "buckets": rows,
    }


def start_spread(devices, found) -> dict:
    """Do the device planes share a clock, and if they do, how far apart the
    chips start each collective: ``program_start_ns`` is the largest
    distance, over the program runs, between the first and the last chip's
    program start; below `SHARED_CLOCK_NS` the planes share a clock and
    ``collective_start_us`` holds the median and the maximum, over every
    collective and run, of the same distance between the collective's
    starts."""
    per_device = [_Events(d) for d in devices]
    runs = min(len(e.runs) for e in per_device)
    if len(per_device) < 2 or not runs:
        return {"program_start_ns": None, "collective_start_us": None}
    program = max(
        max(e.runs[k][0] for e in per_device)
        - min(e.runs[k][0] for e in per_device) for k in range(runs))
    out = {"program_start_ns": program, "collective_start_us": None}
    if program >= SHARED_CLOCK_NS:
        return out
    spreads = []
    for c in found:
        for k in range(runs):
            starts = [own[0][0] for e in per_device
                      if (own := e.of(c.events, e.runs[k]))]
            if len(starts) == len(per_device):
                spreads.append((max(starts) - min(starts)) * 1e-3)
    if spreads:
        out["collective_start_us"] = (statistics.median(spreads),
                                      max(spreads))
    return out


# -- a traced run ------------------------------------------------------------

def of_run(run: dict) -> dict | None:
    """The timeline of device 0 of a traced run, built once a run (kept on
    the ``run`` dict every reader is handed); ``None`` for a program without
    `TrainStep.comm` or without collectives."""
    if "bucket_timeline" not in run:
        t0 = time.perf_counter()
        comm = getattr(run["built"]["ts"], "comm", None)
        timeline = None
        if comm is not None:
            asked = [Asked(r.bucket, r.leg, r.payload_bytes, r.wire_bytes)
                     for r in comm.rows]
            timeline = build(run["trace"].devices[0],
                             run["built"]["compiled_text"], asked)
        if timeline is not None:
            timeline["spread"] = start_spread(run["trace"].devices,
                                              timeline["collectives"])
            timeline["build_s"] = time.perf_counter() - t0
        run["bucket_timeline"] = timeline
    return run["bucket_timeline"]


def read(run: dict, key: str):
    """One number of the run's timeline as a metric: a float, or ``None``."""
    timeline = of_run(run)
    if timeline is None or timeline[key] is None:
        return None
    return float(timeline[key])


def _ms(value) -> str:
    return "      –" if value is None else f"{value:7.3f}"


def log_schedule(timeline: dict, log) -> None:
    """The operator's view, as ``[schedule]`` lines."""
    found = timeline["collectives"]
    by_opcode = ", ".join(
        f"{n} {op} ({a} asynchronous)"
        for op, (n, a) in count_by_opcode(found).items())
    log(f"[schedule] asked {timeline['asked_per_step']} collectives a step; "
        f"compiled {len(found)}: {by_opcode}")
    for leg in ("reduce", "gather"):
        asked = timeline["asked_wire_bytes"][leg]
        compiled = timeline["compiled_wire_bytes"][leg]
        ms = timeline["leg_ms"][leg]
        rate = timeline[leg + "_wire_gbps"]
        kinds = collections.defaultdict(list)
        for c in found:
            if c.leg == leg:
                kinds[c.opcode, c.dtype].append(c)
        log(f"[schedule] {leg}: asked {asked / 1e6:.3f} MB of wire a step "
            f"and chip (ring estimate), compiled {compiled / 1e6:.3f} MB: "
            + "; ".join(
                f"{len(cs)} {dtype} {opcode} of buckets "
                f"{sorted(g for c in cs for g in c.buckets)}, "
                f"{sum(c.wire_bytes for c in cs) / 1e6:.3f} MB"
                for (opcode, dtype), cs in sorted(kinds.items()))
            + f"; {ms:.3f} ms a step in its collectives: "
            + ("–" if rate is None else
               f"{rate:.2f} GB/s of what was asked, "
               f"{compiled / (ms * 1e6):.2f} GB/s of what was compiled"))
    share = timeline["exposed_share_pct"]
    log(f"[schedule] collective time {timeline['collective_ms']:.3f} ms a "
        f"step (union, either line; other leg "
        f"{timeline['leg_ms']['other']:.3f}), exposed "
        f"{timeline['exposed_ms']:.3f} ms"
        + ("" if share is None else f" = {share:.2f}%")
        + "; a finished gradient waited "
        f"{_ms(timeline['reduce_wait_ms']).strip()} ms (mean over buckets)")
    log("[schedule] bucket  reduce MB  gather MB  finished   packed  reduce "
        "start–end     wait  gather start–end  first use    slack   (ms "
        f"from the program's start, mean over {timeline['steps']} runs; "
        "wait = reduce start - finished)")
    for r in timeline["buckets"]:
        log(f"[schedule] {r['bucket']:6d} {r['reduce_payload_mb']:10.3f} "
            f"{r['gather_payload_mb']:10.3f}  {_ms(r['finished_ms'])}  "
            f"{_ms(r['packed_ms'])}  "
            f"{_ms(r['reduce_start_ms'])}–{_ms(r['reduce_end_ms'])}  "
            f"{_ms(r['wait_ms'])}  {_ms(r['gather_start_ms'])}–"
            f"{_ms(r['gather_end_ms'])}    {_ms(r['first_use_ms'])}  "
            f"{_ms(r['slack_ms'])}   reduce by {r['reduce_by']}, gather by "
            f"{r['gather_by']}")
    spread = timeline.get("spread") or {}
    if spread.get("program_start_ns") is not None:
        shared = spread["program_start_ns"] < SHARED_CLOCK_NS
        line = (f"[schedule] the chips' programs start within "
                f"{spread['program_start_ns'] * 1e-3:.1f} us of each other: "
                + ("one clock" if shared else
                   "no shared clock, so no spread over the chips"))
        if spread.get("collective_start_us"):
            median, worst = spread["collective_start_us"]
            line += (f"; a collective's start spreads over the chips by "
                     f"{median:.1f} us (median), {worst:.1f} us (max)")
        log(line)
    if "build_s" in timeline:
        log(f"[schedule] timeline built in {timeline['build_s']:.2f} s "
            "(parse of the compiled text included)")
