"""Join a device trace to the program's named scopes through the compiled
HLO text, and book the step's device time by phase and part.

A device op event of a TPU trace carries its HLO text and its times, and no
op_name of its own (looked at in ``perf/onchip_r04/trace``). But the
compiled text gives every instruction ``metadata={op_name="…"}``,
instruction names are unique in a module, and the event's name is the
instruction's name. So: trace event name -> instruction in
``compiled.as_text()`` -> ``op_name`` -> scope.

The path in ``op_name`` is what JAX and Flax wrote at trace time:
``jit(device_step)/shard_map/jvp(GptLmHeadModel)/h_3/attention/softmax/…``.
``jvp(…)`` marks the forward pass, ``transpose(jvp(…))`` the backward one;
Flax names every module (``h_<i>``, ``ln_1``, ``query``, ``Dropout_<n>``,
``wte``); the program's own `jax.named_scope`s name the rest
(``attention/{scores,softmax,dropout,context}``, ``mlp``, ``loss`` in
``models/``; ``dear/{unpack,rng,pack,clip,sdc_fp,metrics}`` and
``dear/bucket<g>/{gather,reduce,update}`` in ``parallel/dear.py``).

An error of the method, not of the program: a fusion carries the op_name of
its root instruction, so a fusion that straddles two scopes is booked whole
to the root's. `step_table`'s entries still sum to the synchronous line's
busy time exactly, and the ``unattributed`` part bounds what no name claims.
Two repairs of what the compiler leaves without a usable name, both in
`instruction_scopes`: a fusion whose root names no part is named after most
of its members, and a collective without metadata (XLA:TPU's combined ones)
after the scoped instructions it feeds. The compiler's own copies
(``copy-done``, ``slice-done``, padding ``dynamic-update-slice``) stay
``unattributed``.

No JAX import; pure functions over text and intervals (`perfbench.xplane`).
"""

from __future__ import annotations

import collections
import functools
import re

from perfbench import xplane

UNATTRIBUTED = "unattributed"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')

#: the program's own scopes: ``dear/<part>`` and ``dear/bucket<g>/<part>``
_DEAR = re.compile(r"(?:^|[/(])dear/(?:bucket\d+/)?(\w+)")
_UPDATE_PHASE = frozenset({"update", "clip", "sdc_fp"})


def _scope(*names: str):
    """A pattern for one path element, also as the innermost element of a
    transform's name (``jvp(loss)``)."""
    return re.compile(r"(?:^|[/(])(?:%s)(?:[/)]|$)" % "|".join(names))


#: Ordered: the first pattern found in the path names the part. ``dropout``
#: stands before ``attention`` so that ``attention/dropout`` is counted
#: once; ``mlp`` before ``projections`` because BERT's second MLP matmul is
#: a Dense called ``output`` like the attention output projection; the bare
#: ``attention`` scope (the flash and checkpointed impls wrap their whole
#: body in it) after ``projections`` because BERT's self-attention *module*
#: is called ``attention`` and holds its query/key/value/output.
_PARTS = (
    ("dropout", _scope(r"attention/dropout", r"Dropout_\d+")),
    ("attention", _scope(r"attention/(?:scores|softmax|context)")),
    ("mlp", _scope("mlp")),
    ("projections", _scope("query", "key", "value", "output")),
    ("attention", _scope("attention")),
    ("layernorm", _scope(r"ln_\w+", r"\w+_ln", r"LayerNorm_\d+")),
    # the tied LM head (`Embed.attend`), BERT's MLM transform and NSP head
    ("loss", _scope("loss", r"\w+\.attend", "mlm_transform", "pooler",
                    "nsp_classifier")),
    ("embedding", _scope("wte", "wpe", r"\w+_embeddings", r"Embed_\d+")),
)


_REFERENCE = re.compile(r"%([\w.\-]+)")

#: What a neighbour's scope says of an unnamed collective. Gradients flow
#: pack -> reduce -> (clip) -> update, parameters update -> gather -> unpack.
_LEG_FROM_USER = {"update": "reduce", "clip": "reduce", "reduce": "reduce",
                  "unpack": "gather", "gather": "gather"}
_LEG_FROM_OPERAND = {"pack": "reduce", "reduce": "reduce",
                     "update": "gather", "gather": "gather"}
_DEAR_BUCKET = re.compile(r"dear/(bucket\d+)/")


def _name_unnamed_collectives(lines: dict, out: dict) -> None:
    """XLA:TPU builds combined collectives that carry no metadata (on a 2x2
    the dear step's reduce-scatters become two combined all-reduces, and
    some all-gathers an all-reduce of padded shards; PR 29's AOT compile).
    Give each the leg, and one bucket's name, of the nearest instruction
    with a ``dear/`` scope: first among its users (through instructions
    without one, nearest first), then among its operands."""
    unnamed = [n for n, op_name in out.items()
               if not op_name and xplane._COLLECTIVE.search(n)]
    if not unnamed:
        return
    operands = {n: [r for r in _REFERENCE.findall(line.split(" = ", 1)[1])
                    if r in lines and r != n]
                for n, line in lines.items()}
    users = collections.defaultdict(list)
    for n, refs in operands.items():
        for r in refs:
            users[r].append(n)
    for name in unnamed:
        for edges, legs in ((users, _LEG_FROM_USER),
                            (operands, _LEG_FROM_OPERAND)):
            found = _nearest_dear_scope(name, edges, out, legs)
            if found:
                out[name] = found
                break


def _nearest_dear_scope(start, edges, scopes, legs, depth: int = 6):
    seen, frontier = {start}, [start]
    for _ in range(depth):
        nxt = []
        for n in frontier:
            for m in edges.get(n, ()):
                if m in seen:
                    continue
                seen.add(m)
                dear = _DEAR.search(scopes.get(m, ""))
                if dear is None:
                    nxt.append(m)
                elif dear.group(1) in legs:
                    bucket = _DEAR_BUCKET.search(scopes[m])
                    return "/".join(
                        ["dear"] + ([bucket.group(1)] if bucket else [])
                        + [legs[dear.group(1)], "(inferred)"])
        frontier = nxt
    return None


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_FUSION_CALLS = re.compile(r" fusion\(.*\bcalls=%?([\w.\-]+)")


def _name_fusions_after_their_members(calls: dict, members: dict,
                                      out: dict) -> None:
    """A fusion carries its root's op_name, and the root may be an
    instruction the compiler made (a ``convert`` named ``convert.73``), or
    carry none. Where the fusion's own op_name names no part, give it the
    op_name of the part most of its fused instructions belong to."""
    for name, computation in calls.items():
        if classify(out[name])[1] != UNATTRIBUTED:
            continue
        votes = collections.Counter(
            c for c in map(classify, members.get(computation, ()))
            if c[1] != UNATTRIBUTED)
        if votes:
            winner = votes.most_common(1)[0][0]
            out[name] = next(n for n in members[computation]
                             if classify(n) == winner)


@functools.lru_cache(maxsize=2)
def _parse(compiled_text: str) -> tuple:
    """({instruction: op_name}, {fusion instruction: parts of its fused
    instructions}) of optimized HLO text, in one pass. (Kept for the last
    texts seen: every reader of a run asks for it.)"""
    out, lines, calls = {}, {}, {}
    members = collections.defaultdict(list)
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
        found = _OP_NAME.search(line, m.end())
        out[name] = found.group(1) if found else ""
        lines[name] = line
        if found:
            members[computation].append(found.group(1))
        fusion = _FUSION_CALLS.search(line, m.end())
        if fusion:
            calls[name] = fusion.group(1)
    _name_fusions_after_their_members(calls, members, out)
    _name_unnamed_collectives(lines, out)
    held = {name: frozenset(classify(n)[1] for n in members[computation])
            - {UNATTRIBUTED}
            for name, computation in calls.items()}
    return out, held


def instruction_scopes(compiled_text: str) -> dict:
    """{instruction name: op_name} of every instruction in optimized HLO
    text; an instruction without ``op_name`` maps to ``""``. Two repairs of
    what the compiler leaves unnamed: a fusion whose own op_name names no
    part takes its members' (`_name_fusions_after_their_members`), a
    collective without op_name its neighbours'
    (`_name_unnamed_collectives`)."""
    return _parse(compiled_text)[0]


@functools.lru_cache(maxsize=None)
def classify(op_name: str) -> tuple:
    """(phase, part) of one ``op_name`` path."""
    dear = _DEAR.search(op_name)
    if dear:
        part = dear.group(1)
        return ("update" if part in _UPDATE_PHASE else "schedule"), part
    if "transpose(jvp(" in op_name or "rematted_computation" in op_name:
        # rematerialised forward work runs in the backward pass
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    else:
        phase = "other"
    for part, pattern in _PARTS:
        if pattern.search(op_name):
            return phase, part
    return phase, UNATTRIBUTED


def _sync_ops(device):
    """(op, its own nanoseconds inside the window) of the synchronous line.
    The line's operations run one after another, but for containers (a
    ``while`` spans the operations of its body): every instant is booked to
    the innermost operation open at it, so the times sum to the line's busy
    time exactly."""
    lo, hi = device.window
    own = collections.Counter()
    stack, at = [], lo          # open operations, innermost last

    def advance(to):
        nonlocal at
        to = min(max(to, lo), hi)
        if stack and to > at:
            own[stack[-1]] += to - at
        at = max(at, to)
    ops = sorted(device.ops, key=lambda o: (o.start, -o.end))
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            advance(ops[stack[-1]].end)
            stack.pop()
        advance(o.start)
        stack.append(i)
    while stack:
        advance(ops[stack[-1]].end)
        stack.pop()
    for i, ns in own.items():
        yield ops[i], ns


def step_table(device, scopes: dict) -> dict:
    """{(phase, part): ms per step} over the synchronous line clipped to the
    window, divided by the program runs (`xplane.top_device_ops`' time
    base, but that a container's time is its own, not its body's too). The
    entries sum to that line's busy time per step exactly."""
    steps = len(device.modules)
    table = collections.Counter()
    for o, ns in _sync_ops(device):
        table[classify(scopes.get(o.name, ""))] += ns
    return {k: v * 1e-6 / steps for k, v in table.items()}


def total(table: dict, phase=None, parts=None):
    """ms per step of the entries of one phase and/or some parts; ``None``
    where the table has none (a program without those scopes)."""
    found = [ms for (ph, part), ms in table.items()
             if (phase is None or ph == phase)
             and (parts is None or part in parts)]
    return sum(found) if found else None


def run_table(run: dict) -> dict:
    """`step_table` of device 0 of a traced run, as `run.py` hands it to
    every reader."""
    return step_table(run["trace"].devices[0],
                      instruction_scopes(run["built"]["compiled_text"]))


def top_unattributed(device, scopes: dict, n: int = 10) -> list:
    """[[phase, op category, its op_name less the primitive, ms per step]]
    of the ``unattributed`` part, largest first: what to name next."""
    steps = len(device.modules)
    spent = collections.Counter()
    for o, ns in _sync_ops(device):
        op_name = scopes.get(o.name, "")
        phase, part = classify(op_name)
        if part == UNATTRIBUTED:
            spent[phase, o.category, op_name.rpartition("/")[0]] += ns
    return [[*k, v * 1e-6 / steps] for k, v in spent.most_common(n)]


def straddles(device, compiled_text: str, n: int = 6) -> list:
    """[[part booked, part also held, ms per step]], largest first: the time
    of fusions booked (by their root) to one part whose fused instructions
    also belong to another. It bounds the method's error: BERT-Large's
    dropout masks are made inside fusions rooted in the attention core, so
    ``dropout`` reads nothing and ``attention`` holds it (PR 29)."""
    scopes, held = _parse(compiled_text)
    steps = len(device.modules)
    spent = collections.Counter()
    for o, ns in _sync_ops(device):
        part = classify(scopes.get(o.name, ""))[1]
        for other in held.get(o.name, ()):
            if other != part:
                spent[part, other] += ns
    return [[*k, v * 1e-6 / steps] for k, v in spent.most_common(n)]


def leg_of(op_name: str) -> str:
    """``reduce`` or ``gather`` where a ``dear/bucket<g>/…`` scope says so,
    else ``other`` (the loss's ``dear/metrics`` mean, ``dear/clip``'s norm,
    a collective XLA made from an unnamed instruction)."""
    part = classify(op_name)[1]
    return part if part in ("reduce", "gather") else "other"


def exposed_by_leg(device, scopes: dict) -> dict:
    """{"reduce": ms, "gather": ms, "other": ms} per step:
    `Device.exposed_collectives`' arithmetic (collective intervals on either
    line less every other operation of the synchronous line), each
    collective put under the leg its scope names; ``None`` for a leg no
    collective of the trace belongs to. A combined collective that XLA built
    from several buckets carries one bucket's name and goes to that leg
    whole. An instant at which collectives of two legs are exposed is booked
    once, to ``reduce`` before ``gather`` before ``other``, so the legs sum
    to ``exposed_collective_ms``."""
    lo, hi = device.window
    steps = len(device.modules)
    by_leg = {"reduce": [], "gather": [], "other": []}
    for o in device.ops + device.async_ops:
        if o.is_collective:
            by_leg[leg_of(scopes.get(o.name, ""))].append((o.start, o.end))
    booked = [(o.start, o.end) for o in device.ops if not o.is_collective]
    out = {}
    for leg, intervals in by_leg.items():
        exposed = xplane.clip(xplane.subtract(intervals, booked), lo, hi)
        out[leg] = (xplane.length(exposed) * 1e-6 / steps if intervals
                    else None)
        booked = booked + intervals   # the later legs leave these out
    return out


def run_exposed_by_leg(run: dict):
    """`exposed_by_leg` of device 0 of a traced run; ``None`` for a trace
    without collectives."""
    device = run["trace"].devices[0]
    if not any(o.is_collective for o in device.ops + device.async_ops):
        return None
    return exposed_by_leg(
        device, instruction_scopes(run["built"]["compiled_text"]))


def log_table(table: dict, log) -> None:
    """The operator's view: one ``[scopes]`` line per phase and per
    (phase, part), largest first, with its share of the busy time."""
    busy = sum(table.values()) or 1.0
    phases = collections.Counter()
    for (phase, _), ms in table.items():
        phases[phase] += ms
    log(f"[scopes] synchronous line busy {busy:.3f} ms/step; by phase: "
        + ", ".join(f"{p} {ms:.3f}" for p, ms in phases.most_common()))
    for (phase, part), ms in sorted(table.items(), key=lambda kv: -kv[1]):
        log(f"[scopes] {ms:9.3f} ms {100 * ms / busy:6.2f}%  {phase}/{part}")
