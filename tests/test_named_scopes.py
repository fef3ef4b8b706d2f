"""The step program names its parts (`jax.named_scope`): the DeAR legs, the
optimizer, attention, dropout and the loss. Pins, on tiny GPT and BERT models
under ``mode="dear"`` on 4 virtual devices and at world 1: every scope the
mode exercises is in the compiled step's text, ``dear/bucket<g>/…`` for every
bucket, and the scopes change no arithmetic (three steps' losses bit for bit
against a build with `jax.named_scope` patched to a null context). Plus the
host span, the other schedules' scopes, and the compile cache's key."""

import contextlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu import models
from dear_pytorch_tpu.models import data
from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
from dear_pytorch_tpu.parallel import build_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, VOCAB = 16, 61
RNG_SEED = 7


def _gpt():
    cfg = models.GptConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, embd_dropout_prob=0.0,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    model = models.GptLmHeadModel(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, SEQ), jnp.int32), train=False)["params"]

    def loss_fn(p, b, rng):
        logits = model.apply({"params": p}, b["input_ids"], train=True,
                             rngs={"dropout": rng})
        return models.gpt_lm_loss(logits, b["input_ids"], vocab_size=VOCAB)

    def batch(n):
        return data.synthetic_gpt_batch(jax.random.PRNGKey(3), n,
                                        seq_len=SEQ, vocab_size=VOCAB)
    return params, loss_fn, batch


def _bert():
    cfg = models.BertConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64)      # dropout 0.1 kept
    model = models.BertForPreTraining(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, SEQ), jnp.int32), train=False)["params"]

    def loss_fn(p, b, rng):
        logits, nsp = model.apply(
            {"params": p}, b["input_ids"], b["token_type_ids"],
            b["attention_mask"], train=True, rngs={"dropout": rng})
        return models.bert_pretraining_loss(
            logits, nsp, b["masked_lm_labels"], b["next_sentence_labels"])

    def batch(n):
        return data.synthetic_bert_batch(jax.random.PRNGKey(3), n,
                                         seq_len=SEQ, vocab_size=VOCAB)
    return params, loss_fn, batch


FAMILIES = {"gpt": _gpt, "bert": _bert}
#: the scopes of both tables that ``mode="dear"`` exercises in every family
COMMON = ("dear/bucket0/reduce", "dear/bucket0/update", "dear/pack",
          "dear/unpack", "dear/metrics", "attention/scores",
          "attention/softmax", "attention/context", "mlp", "loss")


def _mesh(world: int):
    return jax.sharding.Mesh(np.array(jax.devices()[:world]), ("dp",))


def _build(family: str, world: int, mode: str = "dear", **kw):
    params, loss_fn, batch = FAMILIES[family]()
    ts = build_train_step(
        loss_fn, params, mesh=_mesh(world), mode=mode, threshold_mb=0.01,
        optimizer=fused_sgd(lr=0.05, momentum=0.9), rng_seed=RNG_SEED,
        donate=False, **kw)
    return ts, ts.init(params), batch(2 * world)


def _scopes_in(text: str) -> set:
    """Every path prefix of every op_name, transforms' parentheses opened:
    ``jit(f)/jvp(loss)/x`` holds ``loss``."""
    found = set()
    for op_name in set(re.findall(r'op_name="([^"]*)"', text)):
        parts = [p for p in re.split(r"[/()]", op_name) if p]
        for i in range(len(parts)):
            for j in range(i + 1, min(i + 4, len(parts) + 1)):
                found.add("/".join(parts[i:j]))
    return found


def _losses(ts, state, batch, steps=3):
    out = []
    for _ in range(steps):
        state, metrics = ts.step(state, batch)
        out.append(np.asarray(metrics["loss"]))
    return np.stack(out)


@pytest.mark.parametrize("world", [4, 1], ids=["dear4", "world1"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_compiled_step_holds_every_scope(family, world):
    ts, state, batch = _build(family, world)
    assert ts.plan.num_buckets >= 2
    text = ts.lower(state, batch).compile().as_text()
    found = _scopes_in(text)
    want = set(COMMON)
    if family == "bert":    # the tiny GPT runs without dropout, as its cell
        want |= {"attention/dropout", "dear/rng"}
    for g in range(ts.plan.num_buckets):
        want |= {f"dear/bucket{g}/reduce", f"dear/bucket{g}/update"}
        if world > 1:
            want.add(f"dear/bucket{g}/gather")
    assert not want - found, sorted(want - found)


@pytest.mark.parametrize("world", [4, 1], ids=["dear4", "world1"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_scopes_change_no_arithmetic(family, world, monkeypatch):
    with_scopes = _losses(*_build(family, world))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    ts, state, batch = _build(family, world)
    text = ts.lower(state, batch).compile().as_text()
    assert "dear/pack" not in text and "attention/softmax" not in text
    without = _losses(ts, state, batch)
    assert np.all(np.isfinite(with_scopes))
    assert with_scopes.tobytes() == without.tobytes(), (with_scopes, without)


@pytest.mark.parametrize("mode,kw,want", [
    ("allreduce", {}, {"dear/bucket0/reduce", "dear/bucket1/update"}),
    ("fsdp", {}, {"dear/bucket0/gather", "dear/bucket1/gather",
                  "dear/unpack", "dear/bucket0/update"}),
    ("dear", {"clip_norm": 1.0}, {"dear/clip", "dear/bucket0/gather"}),
    ("dear", {"DEAR_SDC": "1"}, {"dear/sdc_fp"}),
], ids=["allreduce", "fsdp", "clip", "sdc_fp"])
def test_other_schedules_carry_their_scopes(mode, kw, want, monkeypatch):
    for name in [k for k in kw if k.isupper()]:
        monkeypatch.setenv(name, kw.pop(name))
    ts, state, batch = _build("gpt", 4, mode=mode, **kw)
    found = _scopes_in(ts.lower(state, batch).compile().as_text())
    assert not want - found, sorted(want - found)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_pack_and_unpack_name_every_bucket(family):
    """``dear/pack`` and ``dear/unpack`` hold one ``bucket<g>`` a bucket and
    nothing directly: a profile shows pack -> ``dear/bucket<g>/reduce`` ->
    update -> gather -> unpack bucket by bucket."""
    ts, state, batch = _build(family, 4)
    text = ts.lower(state, batch).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    for g in range(ts.plan.num_buckets):
        for copy in ("pack", "unpack"):
            assert any(f"dear/{copy}/bucket{g}/" in n for n in names), (
                copy, g)
    bare = [n for n in names
            if re.search(r"dear/(?:pack|unpack)/(?!bucket\d+/)", n)]
    assert not bare, bare


@pytest.mark.parametrize("mode", ["dear", "allreduce"])
def test_comm_is_the_schedules_account(mode, monkeypatch):
    """`TrainStep.comm` is `plan_comm_accounting` of the plan and mode, and
    what ``dear.<leg>_bytes`` count a step under ``DEAR_TELEMETRY=1``."""
    from dear_pytorch_tpu.observability import counters, tracer

    monkeypatch.setenv("DEAR_TELEMETRY", "1")
    before = tracer.get_tracer()
    tracer.set_tracer(None)            # the next use reads the environment
    try:
        ts, state, batch = _build("gpt", 4, mode=mode)
        assert ts.comm == counters.plan_comm_accounting(
            ts.plan, mode=mode, comm_itemsize=4)
        legs = {r.leg for r in ts.comm.rows}
        assert legs == set(counters.MODE_LEGS[mode])
        assert len(ts.comm.rows) == ts.plan.num_buckets * len(legs)
        for _ in range(2):
            state, _ = ts.step(state, batch)
        counted = tracer.get_tracer().counters()
        assert counted["dear.steps"] == 2
        for leg in legs:
            assert counted[f"dear.{leg}_bytes"] == (
                2 * ts.comm.leg_bytes_per_step(leg)) > 0
    finally:
        tracer.set_tracer(before)


@pytest.mark.parametrize("gather_dtype,gather_itemsize",
                         [(None, 4), (jnp.bfloat16, 2)],
                         ids=["as-stored", "bf16"])
def test_gather_leg_is_priced_at_what_the_step_gathers(gather_dtype,
                                                       gather_itemsize):
    """bf16 gradients over f32 masters: with no ``gather_dtype`` the step
    gathers each shard as it is stored (f32), not in ``comm_dtype``; the
    compiled all-gathers move the dtype the account prices."""
    ts, state, batch = _build("gpt", 4, comm_dtype=jnp.bfloat16,
                              gather_dtype=gather_dtype)
    by = {(r.bucket, r.leg): r for r in ts.comm.rows}
    for b in ts.plan.buckets:
        gather, reduce = by[b.index, "all_gather"], by[b.index,
                                                       "reduce_scatter"]
        assert gather.payload_bytes == b.padded_size * gather_itemsize
        assert gather.wire_bytes == gather.payload_bytes * 3 / 4
        assert reduce.payload_bytes == b.padded_size * 2
    text = ts.lower(state, batch).as_text()
    gathered = set(re.findall(
        r"stablehlo.all_gather.*-> tensor<\d+x(\w+)>", text))
    assert gathered == {"f32" if gather_itemsize == 4 else "bf16"}


def test_checkpointed_and_flash_impls_sit_under_attention():
    from dear_pytorch_tpu.models.gpt import (
        checkpointed_causal_attention_impl, flash_causal_attention_impl)

    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    for impl in (checkpointed_causal_attention_impl(),
                 flash_causal_attention_impl()):
        def f(q, k, v, impl=impl):
            return impl(q, k, v, None, dtype=jnp.float32).sum()

        text = jax.jit(jax.grad(f)).lower(q, q, q).compile().as_text()
        assert "attention" in _scopes_in(text), impl


def test_default_core_kernels_sit_under_attention(monkeypatch):
    """On a TPU the default GPT core is two Pallas kernels a layer (forward
    and fused backward), called under the ``attention`` scope of the forward
    (``jvp``) and the backward (``transpose(jvp)``) pass; the layers share
    one lowered body a kernel. Read off the step as lowered FOR a TPU from
    here (no chip, no TPU compiler: tests/test_chip_compile.py compiles)."""
    monkeypatch.setattr(
        sys.modules["dear_pytorch_tpu.ops.flash_attention"], "_interpret",
        lambda: False)
    cfg = models.GptConfig(
        vocab_size=VOCAB, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=1024, embd_dropout_prob=0.0,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    model = models.GptLmHeadModel(cfg)
    ids = jnp.zeros((1, 1024), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, ids, train=False)["params"])

    def loss(p, ids):
        return models.gpt_lm_loss(model.apply({"params": p}, ids, train=True),
                                  ids, vocab_size=VOCAB)

    text = jax.jit(jax.grad(loss)).trace(params, ids).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    sites = [(kernel, locs[loc]) for kernel, loc in re.findall(
        r"call @(_\w+_call)\(.*loc\((#loc\d+)\)", text)]
    assert sorted(k for k, _ in sites) == (["_bwd_call"] * 2
                                            + ["_fwd_call"] * 2)
    for kernel, name in sites:
        phase = ("/jvp(GptLmHeadModel)/" if kernel == "_fwd_call"
                 else "/transpose(jvp(GptLmHeadModel))/")
        assert phase in name and re.search(r"/h_[01]/attention/", name), name


def test_step_is_annotated_on_the_profilers_clock(tmp_path):
    """`ts.step` wraps its dispatch in `TraceAnnotation("dear.step")` on
    every path, the fast one too: a profiler session shows the span."""
    ts, state, batch = _build("gpt", 1)
    state, _ = ts.step(state, batch)      # compile outside the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            state, metrics = ts.step(state, batch)
        jax.block_until_ready(metrics)
    finally:
        jax.profiler.stop_trace()
    found = list(tmp_path.rglob("*.xplane.pb"))
    assert len(found) == 1
    data_ = jax.profiler.ProfileData.from_file(str(found[0]))
    names = [e.name for plane in data_.planes if plane.name.startswith("/host")
             for line in plane.lines for e in line.events]
    assert names.count("dear.step") == 2


def test_compile_cache_is_keyed_on_the_scopes(tmp_path):
    """`backend.init` keys the persistent cache on instruction metadata:
    otherwise a build with other scope names is served the executable
    compiled first, and its text carries the old names."""
    code = (
        "import contextlib, os, jax, jax.numpy as jnp\n"
        "from dear_pytorch_tpu.comm import backend\n"
        "backend.init()\n"
        "scope = (jax.named_scope('dear/unpack') if os.environ['SCOPED'] "
        "== '1' else contextlib.nullcontext())\n"
        "def f(x):\n"
        "    with scope:\n"
        "        return jnp.sin(x) @ x\n"
        "text = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()\n"
        "assert ('dear/unpack' in text) == (os.environ['SCOPED'] == '1')\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "x"),
               JAX_ENABLE_COMPILATION_CACHE="1",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    for scoped in ("0", "1", "0"):
        subprocess.run([sys.executable, "-c", code], check=True,
                       env=dict(env, SCOPED=scoped), timeout=300,
                       cwd=str(tmp_path))
    entries = [f for f in os.listdir(tmp_path / "x") if f.startswith("jit_f")]
    assert len(entries) == 2, entries
