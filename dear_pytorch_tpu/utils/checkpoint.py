"""Checkpoint / resume for `DearState` — a capability gap in the reference
(SURVEY.md §5: "Checkpoint/resume: none at training level"), filled here
with Orbax.

The carried state is already fully explicit (sharded master buffers,
optimizer state, step counter, model collections, compressor residuals), so
checkpointing is: save the pytree + a fingerprint of the fusion plan it was
packed under. On restore the fingerprint is checked against the live train
step's plan — restoring into a re-bucketed setup is an error with a pointer
to `tuning.autotune.repack_state` (which converts between plans).

Durability hardening (the resilience layer's contract):

  - every synchronous save's sidecar carries a **checksum manifest**
    (per-file sha256 + size over the committed step dir); `verify_checkpoint`
    re-hashes it and `latest_valid_step` walks newest->oldest past corrupted
    payloads, so a bit-flipped or truncated checkpoint degrades to the
    previous valid step instead of a poisoned restore. Async saves commit
    after the sidecar is written — backfill with `write_manifest` once
    `wait_for_checkpoints` returns (`GuardedTrainer.finalize` does).
  - `prune_checkpoints` is the keep-last-k retention GC (shared by
    `GuardedTrainer`), and `prune_orphaned_tmp` clears crash-leftover Orbax
    atomic-write temp dirs on startup — previously they were only excluded
    from listings, never deleted.
  - sidecar I/O goes through `resilience.retry` (transient shared-fs
    failures must not kill the save path the guard depends on).

Storage models: the default is a SHARED checkpoint directory (GCS/NFS —
process 0 owns sidecars and retention, orbax writes shards
cooperatively). ``DEAR_CKPT_SHARED=0`` declares **per-host storage**
(local SSD per host): every process owns its directory outright —
sidecars, manifests and retention run on every rank, and saves use a
dependency-light local format (raw-bytes blob + JSON index, atomic
rename commit) instead of orbax's cooperative writer, whose numpy path
hardcodes a process-0 writer. Per-host views can then genuinely diverge
(one host's disk corrupts a step the others kept) — which is exactly
what the cluster layer's consensus restore
(`resilience.cluster.ClusterCoordinator.consensus_restore_step` over
`valid_steps`) reconciles.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
from typing import Optional

import jax

from dear_pytorch_tpu.ops import fusion as F
from dear_pytorch_tpu.parallel import dear as D
from dear_pytorch_tpu.resilience.retry import RetryError, retry_call

logger = logging.getLogger("dear_pytorch_tpu")


class PlanMismatchError(ValueError):
    """The checkpoint was packed under a different fusion plan than the
    live train step's (another threshold, world size, or membership
    epoch). `GuardedTrainer._restore_step` catches exactly this type to
    route into the `elastic_restore` re-pack path — a ValueError subclass
    so pre-existing callers keep working."""


def plan_fingerprint(plan: F.FusionPlan) -> str:
    """Stable hash of everything that determines buffer layout — including
    the membership epoch for elastically rescaled plans (`F.rescale_plan`),
    so a post-reconfiguration restore can never silently unpack buffers
    packed under a different membership even when the world size happens
    to coincide. Epoch-0 (initial membership) fingerprints are unchanged
    from pre-elastic checkpoints."""
    desc = {
        "world": plan.world,
        "leaves": [(s.name, list(s.shape), str(s.dtype)) for s in plan.leaves],
        "buckets": [
            [list(b.leaf_ids), b.padded_size] for b in plan.buckets
        ],
    }
    epoch = int(getattr(plan, "epoch", 0) or 0)
    if epoch:
        desc["epoch"] = epoch
    return hashlib.sha256(
        json.dumps(desc, sort_keys=True).encode()
    ).hexdigest()[:16]


def plan_desc(plan: F.FusionPlan) -> dict:
    """JSON-serializable description from which the plan's buffer layout
    can be REBUILT (not just checked) — the sidecar payload that makes
    `elastic_restore` possible on a different world size."""
    return {
        "world": plan.world,
        "epoch": int(getattr(plan, "epoch", 0) or 0),
        "leaves": [
            {"name": s.name, "layer": s.layer, "shape": list(s.shape),
             "dtype": str(s.dtype)}
            for s in plan.leaves
        ],
        "groups": [list(b.leaf_ids) for b in plan.buckets],
        # the buffers' lengths: a TPU four's lane-dense schedules pad to
        # XLA:TPU's spans (`F.bucket_length`), not to a multiple of world
        "padded": [b.padded_size for b in plan.buckets],
    }


def plan_from_desc(desc: dict, treedef) -> F.FusionPlan:
    """Rebuild a `FusionPlan` from `plan_desc` output. ``treedef`` comes
    from a live plan over the SAME model (the pytree structure is not
    serializable; leaf order is the flatten order both plans share)."""
    import jax.numpy as jnp

    specs = tuple(
        F.LeafSpec(
            name=d["name"], layer=d["layer"], shape=tuple(d["shape"]),
            dtype=jnp.dtype(d["dtype"]),
            size=int(max(1, _prod(d["shape"]))),
        )
        for d in desc["leaves"]
    )
    plan = F._build_plan(specs, [list(g) for g in desc["groups"]],
                         desc["world"], treedef, desc.get("padded"))
    epoch = int(desc.get("epoch", 0) or 0)
    if epoch:
        import dataclasses as _dc

        plan = _dc.replace(plan, epoch=epoch)
    return plan


def _prod(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


def _ckpt_dir(directory: str, step: int) -> str:
    return os.path.join(directory, f"step_{step:010d}")


# ---------------------------------------------------------------------------
# Per-host (non-shared) checkpoint storage
# ---------------------------------------------------------------------------

SHARED_ENV = "DEAR_CKPT_SHARED"

#: Filenames of the local (per-host) checkpoint format.
_LOCAL_INDEX = "dear_local.json"
_LOCAL_BLOB = "dear_local.bin"
_LOCAL_TMP_MARK = ".local-tmp"


def per_host_storage() -> bool:
    """True when ``DEAR_CKPT_SHARED=0`` declares per-host checkpoint
    directories (local SSD per host, not GCS/NFS): every process owns its
    directory outright, so sidecar/manifest/retention I/O runs on every
    rank and multi-process saves use the local format below."""
    return os.environ.get(SHARED_ENV, "").strip().lower() in (
        "0", "false", "no")


def _owns_directory_io() -> bool:
    """Which process performs sidecar/retention I/O in a checkpoint
    directory: rank 0 on shared storage (one writer), every rank when the
    storage is per-host."""
    return jax.process_index() == 0 or per_host_storage()


def local_save(step_dir: str, state) -> None:
    """Write ``state`` (any pytree of arrays/scalars) in the local
    per-host format: one raw-bytes blob plus a JSON index of
    (dtype, shape, offset) per leaf, committed by atomic directory
    rename. Dependency-light on purpose — orbax's replicated-numpy writer
    hardcodes a process-0 writer, which per-host storage must not have —
    and restores only ever go through a structure *template*, so no
    treedef needs serializing. Handles every jax dtype (bf16 included):
    leaves travel as raw bytes. Overwrites an existing step dir: replay
    after a consensus rollback legitimately re-reaches a step whose
    corrupted dir is still on disk, and that stale dir must not fail the
    fresh save (os.rename onto a non-empty dir raises)."""
    import shutil

    import numpy as np

    host = [np.asarray(jax.device_get(x))
            for x in jax.tree_util.tree_leaves(state)]
    tmp = step_dir + _LOCAL_TMP_MARK
    if os.path.isdir(tmp):
        shutil.rmtree(tmp)  # crash leftover from an interrupted save
    os.makedirs(tmp, exist_ok=True)
    index, off = [], 0
    with open(os.path.join(tmp, _LOCAL_BLOB), "wb") as f:
        for arr in host:
            raw = arr.tobytes()
            index.append({"dtype": str(arr.dtype),
                          "shape": list(arr.shape), "offset": off,
                          "nbytes": len(raw)})
            f.write(raw)
            off += len(raw)
    with open(os.path.join(tmp, _LOCAL_INDEX), "w") as f:
        json.dump({"leaves": index}, f)
    if os.path.isdir(step_dir):
        # stale dir from before a rollback: replace via rename-ASIDE, not
        # rmtree-then-rename — deleting first would open a crash window
        # (seconds for large payloads) in which the only committed copy of
        # this step is gone; two renames narrow it to microseconds
        aside = step_dir + _LOCAL_TMP_MARK + "-old"
        if os.path.isdir(aside):
            shutil.rmtree(aside)
        os.rename(step_dir, aside)
        os.rename(tmp, step_dir)  # the committed step dir appears atomically
        shutil.rmtree(aside, ignore_errors=True)
    else:
        os.rename(tmp, step_dir)  # the committed step dir appears atomically


def is_local_checkpoint(step_dir: str) -> bool:
    return os.path.exists(os.path.join(step_dir, _LOCAL_INDEX))


def local_restore(step_dir: str, template):
    """Restore a `local_save` checkpoint into the structure AND device
    placement of ``template`` (each leaf is `jax.device_put` onto the
    template leaf's sharding)."""
    import numpy as np

    with open(os.path.join(step_dir, _LOCAL_INDEX)) as f:
        index = json.load(f)["leaves"]
    t_leaves, treedef = jax.tree_util.tree_flatten(template)
    if len(t_leaves) != len(index):
        raise ValueError(
            f"local checkpoint under {step_dir} has {len(index)} leaves "
            f"but the template has {len(t_leaves)} — restoring into a "
            "different model/optimizer structure"
        )
    with open(os.path.join(step_dir, _LOCAL_BLOB), "rb") as f:
        blob = f.read()
    out = []
    for ent, t in zip(index, t_leaves):
        n = _prod(ent["shape"]) if ent["shape"] else 1
        arr = np.frombuffer(
            blob, dtype=np.dtype(ent["dtype"]), count=n,
            offset=ent["offset"],
        ).reshape(ent["shape"])
        if isinstance(t, jax.Array):
            arr = jax.device_put(arr, t.sharding)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


_async_ckptr = None


def _get_async_checkpointer():
    """One process-wide AsyncCheckpointer (it owns the writer threads; Orbax
    requires saves to be serialized through a single instance)."""
    global _async_ckptr
    if _async_ckptr is None:
        import orbax.checkpoint as ocp

        _async_ckptr = ocp.AsyncCheckpointer(ocp.PyTreeCheckpointHandler())
    return _async_ckptr


def save_checkpoint(
    directory: str, state: D.DearState, plan: F.FusionPlan,
    *, asynchronous: bool = False,
    pipeline_state: Optional[dict] = None,
    mem_epoch: Optional[int] = None,
    dcn_state: Optional[dict] = None,
) -> str:
    """Write a checkpoint for the state's current step; returns its path.

    ``asynchronous=True`` returns as soon as the on-device arrays are
    snapshotted; serialization to disk proceeds on Orbax's writer threads
    while training continues (the step dir appears atomically when the write
    commits). Call `wait_for_checkpoints` before reading the files or
    exiting the process.

    ``pipeline_state`` (a `runtime.pipeline` ``state_dict()``) and
    ``mem_epoch`` (the elastic membership epoch) ride in the sidecar:
    restoring the model without restoring the input-pipeline position
    silently replays or skips data, so the guard persists both and
    `read_pipeline_state` / `read_mem_epoch` recover them.
    ``dcn_state`` (a `comm.dcn.DcnExchanger` ``state_dict()``) rides the
    same way: the degraded-mode error-feedback residual is deferred
    gradient mass belonging to THIS model state — restoring one without
    the other double-counts or drops it (`read_dcn_state` recovers it).
    """
    import orbax.checkpoint as ocp

    step = int(jax.device_get(state.step))
    path = _ckpt_dir(directory, step)
    if jax.process_count() > 1 and per_host_storage():
        # per-host storage: this process owns the whole directory, so it
        # writes the whole state — through the local format (orbax's
        # replicated-numpy writer hardcodes a process-0 writer). Always
        # synchronous: a per-host save has no cooperative commit to
        # overlap, and the guard's durability contract stays simple.
        if asynchronous:
            logger.warning(
                "checkpoint: per-host storage saves synchronously "
                "(asynchronous=True ignored)")
        local_save(path, state)
    # Hand Orbax the live (possibly sharded) arrays: each process writes its
    # addressable shards. A jax.device_get here would fail on non-addressable
    # shards in multi-host runs and replicate everything through host RAM.
    elif asynchronous:
        _get_async_checkpointer().save(os.path.abspath(path), state)
    else:
        ocp.PyTreeCheckpointer().save(os.path.abspath(path), state)
    if _owns_directory_io():  # one writer per DIRECTORY for the sidecar
        # written eagerly even for async saves: restore only ever reaches a
        # sidecar through a COMMITTED step dir (latest_step scans dirs), so
        # a crash mid-write leaves an orphan sidecar, never a broken restore
        meta = {"plan": plan_fingerprint(plan), "step": step,
                "plan_desc": plan_desc(plan)}
        if pipeline_state is not None:
            meta["pipeline"] = pipeline_state
        if mem_epoch is not None:
            meta["mem_epoch"] = int(mem_epoch)
        if dcn_state is not None:
            meta["dcn"] = dcn_state
        # checksum manifest over the committed files: only the sync paths
        # have them on disk here; async saves backfill via `write_manifest`
        # after `wait_for_checkpoints` (manifest=None verifies vacuously)
        has_files = not asynchronous or is_local_checkpoint(path)
        meta["manifest"] = _build_manifest(path) if has_files else None
        _write_sidecar(directory, step, meta)
    return path


def _file_digest(path: str, chunk: int = 1 << 20) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                break
            h.update(block)
    return h.hexdigest()[:16]


def _build_manifest(step_dir: str) -> dict:
    """``{relpath: {"sha256": h16, "bytes": n}}`` over every regular file
    in the committed step dir."""
    out = {}
    root = os.path.abspath(step_dir)
    for dirpath, _dirnames, filenames in sorted(os.walk(root)):
        for fn in sorted(filenames):
            p = os.path.join(dirpath, fn)
            rel = os.path.relpath(p, root)
            out[rel] = {"sha256": _file_digest(p),
                        "bytes": os.path.getsize(p)}
    return out


def _write_sidecar(directory: str, step: int, meta: dict) -> None:
    """Atomic sidecar write with retry (transient shared-fs failures must
    not kill the save path the guard's recovery depends on)."""
    path = os.path.join(directory, f"meta_{step:010d}.json")

    def _write():
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, path)

    retry_call(_write, name="checkpoint.sidecar_write",
               retry_on=(OSError,), attempts=3, base_delay_s=0.05)


def write_manifest(directory: str, step: int) -> bool:
    """Backfill the checksum manifest for a COMMITTED async save (call
    after `wait_for_checkpoints`). Returns False when the step dir or its
    sidecar is missing (the async write failed) — nothing to manifest."""
    if not _owns_directory_io():
        return False
    step_dir = _ckpt_dir(directory, step)
    meta_path = os.path.join(directory, f"meta_{step:010d}.json")
    if not (os.path.isdir(step_dir) and os.path.exists(meta_path)):
        return False
    with open(meta_path) as f:
        meta = json.load(f)
    meta["manifest"] = _build_manifest(step_dir)
    _write_sidecar(directory, step, meta)
    return True


def read_sidecar(directory: str, step: int) -> Optional[dict]:
    """The sidecar metadata for a step (None when missing/unreadable)."""
    meta_path = os.path.join(directory, f"meta_{step:010d}.json")
    try:
        with open(meta_path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def read_pipeline_state(directory: str, step: int) -> Optional[dict]:
    """The input-pipeline ``state_dict()`` persisted with a checkpoint
    (None when the save predates pipeline sidecars). Feed it to
    `runtime.pipeline.Pipeline.load_state_dict` so a restore resumes the
    data stream at the position the checkpoint was taken — without this,
    every restore silently replays or skips data."""
    meta = read_sidecar(directory, step)
    return meta.get("pipeline") if meta else None


def read_dcn_state(directory: str, step: int) -> Optional[dict]:
    """The cross-slice exchanger ``state_dict()`` persisted with a
    checkpoint (None when the save predates degraded-DCN sidecars or the
    run had no ladder state). Feed it to
    `comm.dcn.DcnExchanger.load_state_dict` so a rollback re-seats the
    error-feedback residual with the parameters it was deferred against —
    without this a restore silently drops (or, after replay, double
    counts) the skipped rounds' gradient mass."""
    meta = read_sidecar(directory, step)
    return meta.get("dcn") if meta else None


def read_mem_epoch(directory: str, step: int) -> Optional[int]:
    """The elastic membership epoch stamped into a checkpoint's sidecar
    (None when absent) — a relaunched rank's "last known epoch" for the
    rejoin protocol (`resilience.membership.ElasticCluster.rejoin`)."""
    meta = read_sidecar(directory, step)
    if meta is None or "mem_epoch" not in meta:
        return None
    return int(meta["mem_epoch"])


def prune_future_steps(directory: str, *, above: int) -> list:
    """Delete every checkpoint step STRICTLY NEWER than ``above``.

    After a restore to an older-than-newest step — a consensus rollback
    past a corrupted checkpoint, or an elastic-membership restore to the
    newest step valid on every member — the newer step dirs belong to an
    ABANDONED timeline: replayed training will re-reach those step numbers
    with different parameters, so leaving the stale dirs in place would
    (a) collide with the replayed saves and (b) let a later restore
    resurrect dead-timeline state (a silent desync across members that
    rolled back together). `GuardedTrainer` calls this after every
    successful restore. Returns the pruned steps (newest first)."""
    import shutil

    from dear_pytorch_tpu.observability import tracer as _telemetry

    if not _owns_directory_io():
        return []
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    stale = sorted(
        (int(name[len("step_"):]) for name in names
         if name.startswith("step_") and name[len("step_"):].isdigit()
         and int(name[len("step_"):]) > above),
        reverse=True)
    for s in stale:
        shutil.rmtree(os.path.join(directory, f"step_{s:010d}"),
                      ignore_errors=True)
        try:
            os.remove(os.path.join(directory, f"meta_{s:010d}.json"))
        except OSError:
            pass
    if stale:
        logger.warning(
            "checkpoint: pruned %d stale future step(s) %s after restore "
            "to step %d (abandoned timeline)", len(stale), stale, above)
        tr = _telemetry.get_tracer()
        if tr.enabled:
            tr.count("ckpt.future_steps_pruned", len(stale))
            tr.event("ckpt.future_steps_prune", above=above,
                     pruned=len(stale))
    return stale


def verify_checkpoint(directory: str, step: int) -> bool:
    """Re-hash a checkpoint against its sidecar manifest.

    False on a missing/unreadable sidecar or any size/digest mismatch.
    True when the manifest matches — or is absent (pre-manifest and
    unfinalized-async checkpoints verify vacuously; they predate the
    durability contract).
    """
    meta_path = os.path.join(directory, f"meta_{step:010d}.json")
    try:
        with open(meta_path) as f:
            meta = json.load(f)
    except (OSError, ValueError):
        return False
    manifest = meta.get("manifest")
    if not manifest:
        return True
    root = _ckpt_dir(directory, step)
    for rel, ent in manifest.items():
        p = os.path.join(root, rel)
        try:
            if os.path.getsize(p) != ent["bytes"]:
                return False
            if _file_digest(p) != ent["sha256"]:
                return False
        except OSError:
            return False
    return True


#: (directory, step) pairs already reported corrupt — a corrupted dir stays
#: on disk until retention rotates it out, and every later restore walk
#: would otherwise re-count the SAME corruption event (bounded: retention
#: keeps the step population small)
_corrupt_reported: set = set()


def _report_corrupt(directory: str, step: int) -> None:
    """Log + count one corruption event per (directory, step, sidecar
    mtime) — the mtime distinguishes a RE-written checkpoint at a reused
    step number (post-rollback replay) from an already-reported event."""
    from dear_pytorch_tpu.observability import tracer as _telemetry

    meta_path = os.path.join(directory, f"meta_{step:010d}.json")
    try:
        stamp = int(os.path.getmtime(meta_path))
    except OSError:
        stamp = 0
    key = (os.path.abspath(directory), step, stamp)
    if key in _corrupt_reported:
        return
    _corrupt_reported.add(key)
    logger.error(
        "checkpoint: step %d failed checksum verification; "
        "falling back to the previous checkpoint", step,
    )
    tr = _telemetry.get_tracer()
    if tr.enabled:
        tr.count("ckpt.corrupt_detected")
        tr.event("ckpt.corrupt", step=step)


def valid_steps(directory: str, *, below: Optional[int] = None,
                limit: Optional[int] = None) -> list[int]:
    """Every committed step whose checkpoint passes checksum verification,
    newest first (at most ``limit`` of them; ``below`` restricts to
    strictly older steps). Corrupted steps are walked past, logged +
    counted ONCE per corrupted step as ``ckpt.corrupt_detected``. This is
    both the guard's fallback walk (via `latest_valid_step`) and one
    host's *local view* for the cluster layer's consensus restore
    (`resilience.cluster.ClusterCoordinator.consensus_restore_step`):
    every process contributes its verified steps and the pod restores the
    newest step valid everywhere."""
    if not os.path.isdir(directory):
        return []
    steps = sorted((
        int(name[len("step_"):])
        for name in os.listdir(directory)
        if name.startswith("step_") and name[len("step_"):].isdigit()
        and (below is None or int(name[len("step_"):]) < below)
    ), reverse=True)
    out: list[int] = []
    for step in steps:
        if verify_checkpoint(directory, step):
            out.append(step)
            if limit is not None and len(out) >= limit:
                break
        else:
            _report_corrupt(directory, step)
    return out


def latest_valid_step(directory: str, *,
                      below: Optional[int] = None) -> Optional[int]:
    """Newest step whose checkpoint verifies (the corruption-fallback
    walk): `valid_steps` stopped at the first hit."""
    steps = valid_steps(directory, below=below, limit=1)
    return steps[0] if steps else None


def wait_for_checkpoints() -> None:
    """Block until every `save_checkpoint(asynchronous=True)` has committed.
    No-op when none are in flight."""
    if _async_ckptr is not None:
        _async_ckptr.wait_until_finished()


def has_async_checkpointer() -> bool:
    """True once any async save ran in this process — after which an
    Orbax tmp dir in a checkpoint directory may be a live in-flight
    write, not a crash leftover."""
    return _async_ckptr is not None


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = [
        int(name[len("step_"):])
        for name in os.listdir(directory)
        # exclude Orbax's atomic-write temp dirs
        # (step_XXXXXXXXXX.orbax-checkpoint-tmp-N) left by a crash mid-save
        if name.startswith("step_") and name[len("step_"):].isdigit()
    ]
    return max(steps) if steps else None


def _default_step(directory: str) -> Optional[int]:
    """Step choice for ``step=None`` restores. Single-host: the newest
    checkpoint passing checksum verification (corruption fallback).
    Multi-host: every process MUST restore the same step, and the
    verification walk decides per process (one host's transient fs read
    error would silently pick an older step there, desynchronizing
    replicas) — so use the newest committed step deterministically and
    let a corrupt payload fail the restore loudly for whole-job
    relaunch."""
    if jax.process_count() > 1:
        return latest_step(directory)
    return latest_valid_step(directory)


def prune_orphaned_tmp(directory: str) -> list[str]:
    """Delete crash-orphaned Orbax atomic-write temp dirs
    (``step_XXXXXXXXXX.orbax-checkpoint-tmp-N``) — call on STARTUP, before
    any async save is in flight (they were previously only excluded from
    listings, accumulating forever after crashes). Returns (and logs) what
    was removed."""
    import shutil

    if not _owns_directory_io() or not os.path.isdir(directory):
        return []
    removed = []
    for name in sorted(os.listdir(directory)):
        if name.startswith("step_") and (
                ".orbax-checkpoint-tmp" in name or _LOCAL_TMP_MARK in name):
            shutil.rmtree(os.path.join(directory, name), ignore_errors=True)
            removed.append(name)
    if removed:
        logger.warning(
            "checkpoint: pruned %d crash-orphaned Orbax tmp dir(s) under "
            "%s: %s", len(removed), directory, ", ".join(removed),
        )
    return removed


def prune_checkpoints(
    directory: str, *, max_keep: int,
    skip_tmp_step: Optional[int] = None,
) -> None:
    """Keep-last-k retention GC (shared with `GuardedTrainer`): keep the
    newest ``max_keep`` committed checkpoints; delete older step dirs and
    their sidecars, crash-leftover Orbax atomic-write temp dirs, and
    orphan sidecars whose save never committed. ``skip_tmp_step`` protects
    a legitimately in-flight async write's temp dir (and its eagerly
    written sidecar) from the sweep."""
    import shutil

    if not _owns_directory_io():
        return
    max_keep = max(int(max_keep), 1)
    try:
        names = os.listdir(directory)
    except OSError:
        return
    steps = sorted(
        int(name[len("step_"):])
        for name in names
        if name.startswith("step_") and name[len("step_"):].isdigit()
    )
    # crash-leftover atomic-write temp dirs (orbax or the local per-host
    # format) are never restorable; delete them too, or a crash-restart
    # loop fills the disk the retention policy exists to protect
    for name in names:
        if name.startswith("step_") and (
                ".orbax-checkpoint-tmp" in name or _LOCAL_TMP_MARK in name):
            if (skip_tmp_step is not None
                    and name.startswith(f"step_{skip_tmp_step:010d}.")):
                continue  # in-flight async write, not a crash leftover
            shutil.rmtree(
                os.path.join(directory, name), ignore_errors=True
            )
    for s in steps[:-max_keep]:
        shutil.rmtree(
            os.path.join(directory, f"step_{s:010d}"),
            ignore_errors=True,
        )
        try:
            os.remove(os.path.join(directory, f"meta_{s:010d}.json"))
        except OSError:
            pass
    # orphan sidecars: meta written eagerly for a save that never
    # committed (async failure / crash mid-write). Restores never read
    # them (they go through committed dirs), but a crash-restart loop
    # would accumulate them unboundedly. Ditto .json.tmp leftovers from a
    # crash between the sidecar tmp write and its atomic rename (safe to
    # sweep: sidecars are written and pruned by process 0 only, and the
    # guard prunes after the write completes).
    committed = set(steps)
    for name in names:
        if name.startswith("meta_") and name.endswith(".json.tmp"):
            try:
                os.remove(os.path.join(directory, name))
            except OSError:
                pass
            continue
        if not (name.startswith("meta_") and name.endswith(".json")):
            continue
        digits = name[len("meta_"):-len(".json")]
        if not digits.isdigit():
            continue
        s = int(digits)
        if s not in committed and s != skip_tmp_step:
            try:
                os.remove(os.path.join(directory, name))
            except OSError:
                pass


def restore_checkpoint(
    directory: str,
    ts: D.TrainStep,
    *,
    step: Optional[int] = None,
    template: Optional[D.DearState] = None,
) -> D.DearState:
    """Restore into the layout of ``ts`` (shardings taken from a template
    state — ``ts.init`` output — or built fresh here). When ``step`` is
    None, restores the newest checkpoint that passes checksum
    verification — a corrupted newest checkpoint degrades to the previous
    valid one instead of a DATA_LOSS error mid-restore (single-host only:
    see `_default_step`).

    Raises if the checkpoint was written under a different fusion plan.
    """
    if step is None:
        step = _default_step(directory)
        if step is None:
            raise FileNotFoundError(
                f"no (valid) checkpoints under {directory}")
    meta_path = os.path.join(directory, f"meta_{step:010d}.json")
    with open(meta_path) as f:
        meta = json.load(f)
    live = plan_fingerprint(ts.plan)
    if meta["plan"] != live:
        raise PlanMismatchError(
            f"checkpoint step {step} was packed under plan {meta['plan']} "
            f"but the train step uses plan {live}; rebuild the step with "
            "the original plan, or restore there and carry across with "
            "tuning.autotune.repack_state"
        )
    if template is None:
        raise ValueError("pass template=ts.init(...) output for shardings")
    if is_local_checkpoint(_ckpt_dir(directory, step)):
        # per-host local format: bytes -> template structure + shardings
        # (no orbax involved — per-host mode must stay usable where
        # orbax's cooperative multihost writer is not)
        return local_restore(_ckpt_dir(directory, step), template)
    import orbax.checkpoint as ocp

    ckptr = ocp.PyTreeCheckpointer()
    # restore INTO the template's structure (a structureless restore returns
    # a dict whose alphabetical key order would scramble DearState fields)
    # and ONTO the template's shardings: each process reads only its own
    # shards — no host-RAM replication, multi-host safe.
    restore_args = ocp.checkpoint_utils.construct_restore_args(template)
    return ckptr.restore(
        os.path.abspath(_ckpt_dir(directory, step)),
        item=template,
        restore_args=restore_args,
    )


class _PlanShim:
    """The one attribute `repack_state` reads from its train steps."""

    def __init__(self, plan):
        self.plan = plan


def elastic_restore(
    directory: str,
    ts: D.TrainStep,
    *,
    step: Optional[int] = None,
) -> D.DearState:
    """Restore a checkpoint written under a DIFFERENT world size or fusion
    plan into ``ts`` — elastic recovery: a world=8 run resumes on 4 chips
    (or vice versa, or after re-bucketing) with parameters, elementwise
    optimizer state, and the step counter carried over exactly.

    The sidecar's ``plan_desc`` rebuilds the original plan's buffer layout;
    the checkpoint is read to host and re-packed/re-sharded through
    `tuning.autotune.repack_state` (compressor residuals reset, scalar
    optimizer leaves carried per that function's contract). Numerics: the
    global batch math is world-independent, so training continues with the
    same loss trajectory it would have had without the resize.

    Single-controller path: the full state passes through host RAM of each
    process (fine for recovery; the fast same-plan path is
    `restore_checkpoint`). Use that one when the plan fingerprints match.
    """
    import numpy as np
    import orbax.checkpoint as ocp

    from dear_pytorch_tpu.tuning.autotune import repack_state

    if step is None:
        step = _default_step(directory)
        if step is None:
            raise FileNotFoundError(
                f"no (valid) checkpoints under {directory}")
    with open(os.path.join(directory, f"meta_{step:010d}.json")) as f:
        meta = json.load(f)
    if "plan_desc" not in meta:
        raise ValueError(
            f"checkpoint step {step} predates plan_desc sidecars; elastic "
            "restore needs the original layout description"
        )
    old_plan = plan_from_desc(meta["plan_desc"], ts.plan.treedef)
    if [s.name for s in old_plan.leaves] != [s.name for s in ts.plan.leaves]:
        raise ValueError(
            "checkpoint parameters do not match the live model "
            "(leaf names differ) — elastic restore resizes worlds, it does "
            "not migrate architectures"
        )

    # Restore to HOST numpy explicitly: a structureless restore would use
    # the SAVED shardings, which reference devices that no longer exist
    # after a genuine downsize (orbax warns exactly about this).
    ckptr = ocp.PyTreeCheckpointer()
    path = os.path.abspath(_ckpt_dir(directory, step))
    # orbax version drift: metadata() returns a StepMetadata with
    # .item_metadata on newer releases and the raw tree (a dict) on the
    # 0.5.x line this container ships — tolerate both
    md = ckptr.metadata(path)
    item_md = getattr(md, "item_metadata", md)
    item_tree = item_md.tree if hasattr(item_md, "tree") else item_md
    restore_args = jax.tree.map(
        lambda _: ocp.RestoreArgs(restore_type=np.ndarray), item_tree
    )
    raw = ckptr.restore(path, restore_args=restore_args)
    # NamedTuples come back as field-name dicts from a structureless
    # restore; tolerate either form
    get = raw.get if isinstance(raw, dict) else \
        (lambda k, d=None: getattr(raw, k, d))

    def host(x):
        return jax.tree.map(np.asarray, x)

    raw_comp = get("comp_state", ())
    comp_state: tuple = ()
    if raw_comp:
        # compressor error-feedback residuals ride the elastic restore
        # too: `repack_state` redistributes the per-device rows mass-
        # preservingly across a world change (and resets on a structural
        # mismatch) — a torn/legacy field degrades to reset, not a crash
        try:
            comp_state = tuple(host(c) for c in _as_sequence(raw_comp))
        except Exception as exc:
            logger.warning(
                "elastic restore: compressor state unreadable (%s); "
                "error-feedback residuals reset", exc)
    state = D.DearState(
        buffers=tuple(host(b) for b in _as_sequence(get("buffers"))),
        opt_state=tuple(
            host(s) for s in _as_sequence(get("opt_state"))
        ),
        step=np.asarray(get("step")),
        model_state=host(get("model_state", ())) or (),
        comp_state=comp_state,
    )
    return repack_state(state, _PlanShim(old_plan), ts)


def _as_sequence(tree):
    """Per-bucket entries of a restored tuple field (dict with stringified
    indices, or an actual sequence)."""
    if isinstance(tree, dict):
        return [tree[k] for k in sorted(tree, key=lambda s: int(s))]
    return list(tree)


# ---------------------------------------------------------------------------
# Durable remote tier: async checkpoint streaming to an object store
# ---------------------------------------------------------------------------

#: Remote key layout (under the store's root/prefix):
#:   steps/<step:010d>/files/<relpath>   the step dir payload
#:   steps/<step:010d>/sidecar.json      the local sidecar metadata
#:   steps/<step:010d>/MANIFEST.json     written LAST — the commit marker
#: A remote step EXISTS iff its manifest does (object stores have no
#: rename; the last-written manifest is the atomic commit point).
_REMOTE_STEPS = "steps"
_REMOTE_MANIFEST = "MANIFEST.json"
_REMOTE_SIDECAR = "sidecar.json"


def _remote_step_key(step: int) -> str:
    return f"{_REMOTE_STEPS}/{int(step):010d}"


def remote_steps(store) -> list[int]:
    """Committed remote steps, newest first — a step counts only once its
    ``MANIFEST.json`` landed (it is uploaded last, so a crash mid-upload
    leaves an invisible partial, never a restorable-looking torn step)."""
    out = set()
    for key in store.list(_REMOTE_STEPS):
        parts = key.split("/")
        if (len(parts) >= 3 and parts[-1] == _REMOTE_MANIFEST
                and parts[1].isdigit()):
            out.add(int(parts[1]))
    return sorted(out, reverse=True)


class CheckpointStreamer:
    """Background uploader: stream committed step dirs to an object store.

    The durable-tier half of the multi-tier retention contract
    (docs/RESILIENCE.md "Autoscaling"):

      - **every-step local** — the checkpoint directory keeps what the
        guard's ``max_keep`` retention decides; nothing here touches it.
      - **every-Nth remote** — `enqueue` uploads steps on the
        ``upload_every`` cadence (upload bandwidth is the scarce resource
        on a training host; N spreads it).
      - **last-K pinned** — remote retention always keeps the newest
        ``pin_last`` uploads; older uploads survive only on the
        ``keep_every`` archive cadence (0 = prune them), bounding remote
        spend for the life of the service.

    Uploads run on ONE daemon thread off the training path: `enqueue` is
    a queue put, the worker waits for the step to commit locally (async
    saves land late), verifies the checksum manifest, uploads files →
    sidecar → manifest (commit marker last), all under
    `resilience.retry` backoff. **An exhausted retry never raises into
    training**: it counts ``ckpt.upload_errors``, logs the fallback to
    local-only retention for that step, and the worker moves on — a dead
    bucket degrades durability, not the run. ``ckpt.uploads`` counts
    committed uploads.

    A fully-lost fleet (or a scale-from-zero cold start) restores from
    the remote tier alone via `restore_from_object_store` — zero loss of
    progress past the newest uploaded step.
    """

    def __init__(
        self,
        directory: str,
        store,
        *,
        upload_every: int = 1,
        pin_last: int = 2,
        keep_every: int = 0,
        attempts: int = 4,
        base_delay_s: float = 0.1,
        max_delay_s: float = 2.0,
        commit_wait_s: float = 60.0,
    ):
        import queue
        import threading

        self.directory = directory
        self._store = store
        self.upload_every = max(int(upload_every), 1)
        self.pin_last = max(int(pin_last), 1)
        self.keep_every = max(int(keep_every), 0)
        self._attempts = max(int(attempts), 1)
        self._base_delay_s = float(base_delay_s)
        self._max_delay_s = float(max_delay_s)
        self._commit_wait_s = float(commit_wait_s)
        self.uploaded: list[int] = []
        self.failed: list[int] = []
        self._q: "queue.Queue" = queue.Queue()
        self._pending = 0
        self._cv = threading.Condition()
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="dear-ckpt-streamer")
        self._thread.start()

    # -- producer side (the training loop) -----------------------------------

    def enqueue(self, step: int, *, force: bool = False) -> bool:
        """Queue one committed (or committing) step for upload; returns
        False when the step is off the remote cadence (``force=True``
        bypasses the cadence — emergency saves must reach the durable
        tier no matter where they land) or the streamer is closed. Never
        blocks the training loop."""
        step = int(step)
        if self._closed or (not force and step % self.upload_every != 0):
            return False
        with self._cv:
            self._pending += 1
        self._q.put(step)
        return True

    def flush(self, timeout_s: Optional[float] = None) -> bool:
        """Wait for every enqueued upload to finish (committed or given
        up); True when the queue drained within the timeout."""
        with self._cv:
            return self._cv.wait_for(lambda: self._pending == 0,
                                     timeout=timeout_s)

    def close(self, timeout_s: float = 30.0) -> None:
        """Drain and stop the worker (call at training end; `flush` first
        if the last upload must be durable)."""
        if self._closed:
            return
        self._closed = True
        self.flush(timeout_s)
        self._q.put(None)
        self._thread.join(timeout=5.0)

    def __enter__(self) -> "CheckpointStreamer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- worker side ---------------------------------------------------------

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            try:
                self._upload(item)
            except Exception:  # the worker must outlive any one upload
                logger.exception(
                    "checkpoint: unexpected streamer failure at step %s "
                    "(local-only retention for it)", item)
                self.failed.append(int(item))
            finally:
                with self._cv:
                    self._pending -= 1
                    self._cv.notify_all()

    def _wait_local_commit(self, step: int) -> Optional[dict]:
        """Block (bounded) until the step is committed AND verified
        locally — an async save's dir appears only on commit, and an
        unverifiable step must never become the durable tier's truth."""
        import time

        deadline = time.monotonic() + self._commit_wait_s
        while True:
            meta = read_sidecar(self.directory, step)
            if (meta is not None
                    and os.path.isdir(_ckpt_dir(self.directory, step))
                    and verify_checkpoint(self.directory, step)):
                return meta
            if time.monotonic() >= deadline:
                return None
            time.sleep(0.1)

    def _upload(self, step: int) -> None:
        from dear_pytorch_tpu.observability import tracer as _telemetry

        tr = _telemetry.get_tracer()
        meta = self._wait_local_commit(step)
        if meta is None:
            logger.error(
                "checkpoint: step %d never committed/verified locally "
                "within %.0fs; not uploaded", step, self._commit_wait_s)
            if tr.enabled:
                tr.count("ckpt.upload_errors")
                tr.event("ckpt.upload_error", step=step,
                         why="local_commit_timeout")
            self.failed.append(step)
            return
        step_dir = _ckpt_dir(self.directory, step)
        # the sidecar manifest was just re-verified by _wait_local_commit
        # — reuse it instead of sha256-hashing the whole step dir a
        # second time (manifest-less sidecars — async saves before their
        # finalize backfill — hash here once)
        files = meta.get("manifest") or _build_manifest(step_dir)
        base = _remote_step_key(step)

        def _put():
            for rel in sorted(files):
                self._store.put_file(f"{base}/files/{rel}",
                                     os.path.join(step_dir, rel))
            self._store.put_bytes(f"{base}/{_REMOTE_SIDECAR}",
                                  json.dumps(meta).encode())
            # the commit marker goes LAST: a reader that sees it can
            # trust every byte above it is fully written
            self._store.put_bytes(
                f"{base}/{_REMOTE_MANIFEST}",
                json.dumps({"step": step, "files": files}).encode())

        try:
            retry_call(_put, name="ckpt.upload", attempts=self._attempts,
                       base_delay_s=self._base_delay_s,
                       max_delay_s=self._max_delay_s,
                       retry_on=(OSError, KeyError))
        except RetryError as exc:
            # the durable tier is best-effort from the run's point of
            # view: training continues on local-only retention and the
            # next cadence step tries the store again
            logger.error(
                "checkpoint: upload of step %d exhausted its retry "
                "budget (%s); falling back to LOCAL-ONLY retention for "
                "it", step, exc)
            if tr.enabled:
                tr.count("ckpt.upload_errors")
                tr.event("ckpt.upload_error", step=step, why="retry_exhausted")
            self.failed.append(step)
            return
        self.uploaded.append(step)
        logger.info("checkpoint: step %d uploaded to the remote tier", step)
        if tr.enabled:
            tr.count("ckpt.uploads")
            tr.event("ckpt.upload", step=step, files=len(files))
        self._prune_remote(step)

    def _prune_remote(self, uploaded_step: int) -> None:
        """Remote retention: newest ``pin_last`` uploads are pinned;
        older ones survive only on the ``keep_every`` archive cadence.
        Remote steps NUMERICALLY NEWER than the one just uploaded are an
        abandoned timeline (uploads are chronological on the one worker
        thread, so a smaller step number after a larger one proves a
        consensus rollback happened in between) — they are pruned
        unconditionally, mirroring `prune_future_steps` locally; leaving
        them would hand a cold start dead-timeline state newer than
        anything the live fleet holds."""
        try:
            steps = remote_steps(self._store)
        except Exception:
            return  # a listing error must not fail the upload that ran
        stale = [s for s in steps if s > uploaded_step]
        if stale:
            logger.warning(
                "checkpoint: pruning %d abandoned-timeline remote step(s) "
                "%s after upload of step %d (post-rollback)", len(stale),
                stale, uploaded_step)
        live = [s for s in steps if s <= uploaded_step]
        for s in stale + live[self.pin_last:]:
            if (s <= uploaded_step and self.keep_every
                    and s % self.keep_every == 0):
                continue
            try:
                self._store.delete_prefix(_remote_step_key(s))
            except Exception:
                pass  # retention is best-effort; retried next upload


def restore_from_object_store(store, directory: str,
                              *, step: Optional[int] = None,
                              ) -> Optional[int]:
    """Cold-start restore: materialize the newest (or given) remote step
    into ``directory`` so the ordinary local restore path
    (`restore_checkpoint` / `elastic_restore` + sidecar reads) works on a
    machine that has NEVER trained — a scale-from-zero start or a
    fully-lost fleet. Every downloaded file is **re-hashed against the
    remote manifest** (a bit-flip in the bucket or on the wire must not
    become a poisoned restore); a corrupted remote step is walked past to
    the next older one, exactly like the local corruption-fallback walk.
    Returns the restored step (None when nothing restorable is remote).
    Counts ``ckpt.remote_restores``."""
    import shutil

    from dear_pytorch_tpu.observability import tracer as _telemetry

    tr = _telemetry.get_tracer()
    candidates = remote_steps(store)
    if step is not None:
        candidates = [s for s in candidates if s == int(step)]
    os.makedirs(directory, exist_ok=True)
    for s in candidates:
        base = _remote_step_key(s)
        try:
            manifest = json.loads(
                store.get_bytes(f"{base}/{_REMOTE_MANIFEST}"))
            meta = json.loads(store.get_bytes(f"{base}/{_REMOTE_SIDECAR}"))
        except (KeyError, ValueError) as exc:
            logger.error(
                "checkpoint: remote step %d unreadable (%s); walking to "
                "the previous upload", s, exc)
            continue
        if not manifest.get("files"):
            # a manifest listing no files is not a checkpoint (torn or
            # rewritten remote object): corrupt, walk past it
            logger.error(
                "checkpoint: remote step %d manifest lists no files; "
                "walking to the previous upload", s)
            if tr.enabled:
                tr.event("ckpt.remote_corrupt", step=s, file="<manifest>")
            continue
        step_dir = _ckpt_dir(directory, s)
        tmp = step_dir + _LOCAL_TMP_MARK  # swept by prune_orphaned_tmp
        if os.path.isdir(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp, exist_ok=True)
        ok = True
        for rel, ent in sorted(manifest.get("files", {}).items()):
            dest = os.path.join(tmp, rel)
            try:
                store.get_file(f"{base}/files/{rel}", dest)
            except KeyError:
                ok = False
            else:
                ok = (os.path.getsize(dest) == ent["bytes"]
                      and _file_digest(dest) == ent["sha256"])
            if not ok:
                logger.error(
                    "checkpoint: remote step %d failed sha256 reverify on "
                    "%s; walking to the previous upload", s, rel)
                if tr.enabled:
                    tr.event("ckpt.remote_corrupt", step=s, file=rel)
                break
        if not ok:
            shutil.rmtree(tmp, ignore_errors=True)
            continue
        if os.path.isdir(step_dir):
            shutil.rmtree(step_dir)
        os.rename(tmp, step_dir)  # the local step dir appears atomically
        if not meta.get("manifest"):
            # an async save's sidecar may predate its manifest backfill;
            # the remote manifest IS the verified truth now
            meta["manifest"] = manifest.get("files", {})
        _write_sidecar(directory, s, meta)
        logger.warning(
            "checkpoint: cold-start restored step %d from the remote "
            "tier into %s", s, directory)
        if tr.enabled:
            tr.count("ckpt.remote_restores")
            tr.event("ckpt.remote_restore", step=s)
        return s
    return None
