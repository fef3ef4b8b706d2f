"""Tensor fusion: bucketing a parameter pytree into flat, padded comm buffers.

Functional redesign of the reference's mutable fusion machinery:

  - ``TensorGroup`` push/pull buffers        (dear/tensorfusion.py:14-200)
  - ``_generate_groups_with_threshold``      (dear/dear_dopt.py:109-139)
  - ``_generate_groups_with_nearby_layers``  (dear/dear_dopt.py:94-107)
  - ``_generate_groups_with_flags``          (dear/dopt_rsag_wt.py; 0/1
    boundary vector splitting, tensorfusion.py:175-192)
  - ``_prepare_tensor_fusion`` offset bookkeeping and pad/shard buffer
    sizing (dear/dear_dopt.py:142-194)

The reference allocates persistent CUDA buffers and copies gradients in from
backward hooks. Here a *plan* is static metadata computed once from shapes
(usable inside jit as trace-time constants), and pack/unpack are pure
functions the compiler fuses into surrounding computation — there is no
persistent buffer to manage and no copy-in race to get wrong.

Layer atomicity: the reference buckets whole *modules* (a module's params
always land in one bucket). Here a "layer" is a group of leaves sharing a
parent path in the pytree (e.g. a flax module's ``{kernel, bias}``), and
plans never split a layer across buckets.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dear_pytorch_tpu.comm.collectives import padded_length


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Static description of one parameter tensor."""

    name: str          # "/"-joined pytree path, e.g. "conv1/kernel"
    layer: int         # index of the atomic layer (module) this leaf belongs to
    shape: tuple[int, ...]
    dtype: Any
    size: int          # number of elements


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One fusion group: a contiguous run of layers packed into a flat buffer.

    ``offsets[i]`` is the element offset of ``leaf_ids[i]`` inside the flat
    buffer (the reference's per-param ``(group_idx, sub_idx, start, end)``
    bookkeeping, dear/dear_dopt.py:176-184).
    """

    index: int
    leaf_ids: tuple[int, ...]
    offsets: tuple[int, ...]
    size: int          # total elements (unpadded)
    padded_size: int   # `bucket_length`: a multiple of world, or of spans
    shard_size: int    # padded_size // world

    @property
    def pad(self) -> int:
        return self.padded_size - self.size


@dataclasses.dataclass(frozen=True)
class FusionPlan:
    """Complete static bucketing of a parameter pytree.

    Each bucket is zero-padded to `bucket_length`: a multiple of ``world``,
    so that it shards evenly, and, where `build_train_step` lays the plan
    out for a schedule whose legs travel lane-dense on a TPU mesh of four
    (`parallel.schedules.Dear.lane_dense`), to whole spans of XLA:TPU's
    reduce-scatter, so that the compiler keeps each bucket's reduce-scatter
    rather than padding it into an all-reduce of twice the traffic. Every
    leg carries the same zeros; `utils.checkpoint.plan_desc` records the
    padded lengths, so a plan rebuilt from a checkpoint keeps them."""

    leaves: tuple[LeafSpec, ...]
    buckets: tuple[Bucket, ...]
    world: int
    treedef: Any = dataclasses.field(compare=False)
    #: membership epoch this plan was (re)built under (elastic runs bump it
    #: on every reconfiguration via `rescale_plan`, so plan-fingerprinted
    #: checkpoint restores can tell a pre-shrink plan from a post-shrink
    #: one even when the surviving world size coincides). 0 = the initial
    #: membership — fingerprints of epoch-0 plans are unchanged.
    epoch: int = 0

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    @property
    def total_size(self) -> int:
        return sum(l.size for l in self.leaves)

    def bucket_of_leaf(self, leaf_id: int) -> int:
        for b in self.buckets:
            if leaf_id in b.leaf_ids:
                return b.index
        raise KeyError(leaf_id)

    def segment_ids(self, bucket: int) -> np.ndarray:
        """int32[padded_size] mapping each flat-buffer element to its
        bucket-local parameter index (padding maps to a trailing dummy
        segment, id == len(leaf_ids)). Static metadata — layerwise
        optimizers (LAMB trust ratios) use it to compute exact per-parameter
        norms on shards via segment-sum + psum, even when a parameter spans
        shard boundaries."""
        b = self.buckets[bucket]
        out = np.full((b.padded_size,), len(b.leaf_ids), np.int32)
        for local, (leaf_id, off) in enumerate(zip(b.leaf_ids, b.offsets)):
            out[off:off + self.leaves[leaf_id].size] = local
        return out

    def describe(self) -> str:
        lines = [
            f"FusionPlan: {len(self.leaves)} tensors, "
            f"{self.num_buckets} buckets, world={self.world}"
        ]
        for b in self.buckets:
            names = [self.leaves[i].name for i in b.leaf_ids]
            mb = sum(
                self.leaves[i].size * jnp.dtype(self.leaves[i].dtype).itemsize
                for i in b.leaf_ids
            ) / 2**20
            lines.append(
                f"  bucket {b.index}: {len(names)} tensors, {mb:.2f} MB "
                f"(pad {b.pad}, shard {b.shard_size}) [{names[0]} .. {names[-1]}]"
            )
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Layer grouping
# ---------------------------------------------------------------------------


def _leaf_specs(params) -> tuple[tuple[LeafSpec, ...], Any]:
    """Flatten params into LeafSpecs in pytree (≈ forward) order, grouping
    leaves that share a parent path into one atomic layer (the reference's
    module granularity, dear/dear_dopt.py:196-240)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    layer_keys: dict[str, int] = {}
    for path, leaf in flat:
        name = _path_str(path)
        parent = name.rsplit("/", 1)[0] if "/" in name else name
        layer = layer_keys.setdefault(parent, len(layer_keys))
        specs.append(
            LeafSpec(
                name=name,
                layer=layer,
                shape=tuple(leaf.shape),
                dtype=jnp.result_type(leaf),
                size=int(np.prod(leaf.shape)) if leaf.shape else 1,
            )
        )
    return tuple(specs), treedef


def layer_sizes(
    params, *, in_bytes: bool = True, comm_itemsize: Optional[int] = None
) -> list[float]:
    """Per-atomic-layer sizes in forward order — bytes (optionally at a
    fixed comm itemsize) or element counts. Shared by every analytic
    bucketizer (MG-WFBP / ASC / MGS) so their layer accounting can never
    drift apart."""
    specs, _ = _leaf_specs(params)
    acc: dict[int, float] = {}
    for s in specs:
        unit = (
            (comm_itemsize or jnp.dtype(s.dtype).itemsize) if in_bytes else 1
        )
        acc[s.layer] = acc.get(s.layer, 0.0) + s.size * unit
    return [acc[k] for k in sorted(acc)]


def _layers(specs: Sequence[LeafSpec]) -> list[list[int]]:
    """Leaf ids grouped by atomic layer, in first-appearance order."""
    out: dict[int, list[int]] = {}
    for i, s in enumerate(specs):
        out.setdefault(s.layer, []).append(i)
    return [out[k] for k in sorted(out)]


# ---------------------------------------------------------------------------
# Partitioning strategies
# ---------------------------------------------------------------------------


def plan_by_threshold(
    params, world: int, threshold_mb: Optional[float] = 25.0
) -> "FusionPlan":
    """Pack consecutive layers into buckets of at most `threshold_mb`.

    Mirrors ``_generate_groups_with_threshold`` (dear/dear_dopt.py:109-139):
    a running byte count packs layers in order; a layer that would push the
    bucket past the threshold starts a new bucket (a single oversized layer
    still gets its own bucket). ``threshold_mb=None`` -> one bucket holding
    everything (the reference's THRESHOLD=None no-fusion-limit mode,
    dopt_rsag.py:37).
    """
    specs, treedef = _leaf_specs(params)
    if threshold_mb is None:
        groups = [[i for layer in _layers(specs) for i in layer]] if specs else []
        return _build_plan(specs, groups, world, treedef)
    limit = threshold_mb * 2**20
    groups: list[list[int]] = []
    current: list[int] = []
    current_bytes = 0.0
    for layer in _layers(specs):
        layer_bytes = sum(
            specs[i].size * jnp.dtype(specs[i].dtype).itemsize for i in layer
        )
        if current and current_bytes + layer_bytes > limit:
            groups.append(current)
            current, current_bytes = [], 0.0
        current.extend(layer)
        current_bytes += layer_bytes
    if current:
        groups.append(current)
    return _build_plan(specs, groups, world, treedef)


def plan_by_nearby_layers(params, world: int, k: int = 4) -> "FusionPlan":
    """Pack every `k` consecutive layers into one bucket
    (``_generate_groups_with_nearby_layers``, dear/dear_dopt.py:94-107).
    ``k=1`` disables fusion (one bucket per layer); ``k=-1`` fuses all
    layers into a single bucket (the wait-time tuner's starting point,
    dopt_rsag_wt.py)."""
    if k < 1 and k != -1:
        raise ValueError(f"nearby_layers must be >= 1 or -1 (fuse all), got {k}")
    specs, treedef = _leaf_specs(params)
    layers = _layers(specs)
    if k == -1:
        k = max(1, len(layers))
    groups = [
        [i for layer in layers[j : j + k] for i in layer]
        for j in range(0, len(layers), k)
    ]
    return _build_plan(specs, groups, world, treedef)


def plan_by_flags(params, world: int, flags: Sequence[int]) -> "FusionPlan":
    """Split at layer boundaries where ``flags[layer] == 1``
    (``update_groups_with_flags`` / ``_generate_groups_with_flags``,
    tensorfusion.py:175-192, dopt_rsag_wt.py). ``flags`` has one entry per
    atomic layer; flag=1 means "this layer STARTS a new bucket"."""
    specs, treedef = _leaf_specs(params)
    layers = _layers(specs)
    if len(flags) != len(layers):
        raise ValueError(
            f"flags has {len(flags)} entries for {len(layers)} layers"
        )
    groups: list[list[int]] = []
    current: list[int] = []
    for flag, layer in zip(flags, layers):
        if flag and current:
            groups.append(current)
            current = []
        current.extend(layer)
    if current:
        groups.append(current)
    return _build_plan(specs, groups, world, treedef)


def plan_by_groups(
    params, world: int, layer_groups: Sequence[Sequence[int]]
) -> "FusionPlan":
    """Plan from explicit groups of atomic-layer indices (each group a
    contiguous run in forward order). Used by analytic bucket-sizing
    strategies (MG-WFBP) that decide merges themselves."""
    specs, treedef = _leaf_specs(params)
    layers = _layers(specs)
    groups = [
        [i for li in grp for i in layers[li]] for grp in layer_groups if grp
    ]
    return _build_plan(specs, groups, world, treedef)


def chunk_bounds(
    n_elements: int, itemsize: int, partition_mb: Optional[float]
) -> list[tuple[int, int]]:
    """Element ranges ``[(start, stop), ...]`` splitting a flat buffer of
    ``n_elements`` into chunks of at most ``partition_mb`` megabytes (at
    ``itemsize`` bytes per element). The ONE bucket-partition rule shared
    by every per-level splitter — the 'bytescheduler' chunked reductions
    (`parallel/dear.py`), the cross-slice DCN exchange
    (`comm.dcn.DcnExchanger`), and the static accounting that prices both
    (`observability.counters.plan_comm_accounting`) — so chunk counts can
    never drift between the schedule, the transport, and the cost model.
    ``partition_mb=None`` (or <= 0) means one chunk."""
    if n_elements <= 0:
        return []
    if partition_mb is None or partition_mb <= 0:
        return [(0, int(n_elements))]
    per = max(int(float(partition_mb) * 2**20) // int(itemsize), 1)
    return [(i, min(i + per, int(n_elements)))
            for i in range(0, int(n_elements), per)]


def make_plan(
    params,
    world: int,
    threshold_mb: Optional[float] = 25.0,
    nearby_layers: Optional[int] = None,
    flags: Optional[Sequence[int]] = None,
) -> "FusionPlan":
    """One-stop plan builder with the reference's precedence: explicit flags
    beat nearby-layer count beats MB threshold (dear/dear_dopt.py:89-139)."""
    if flags is not None:
        return plan_by_flags(params, world, flags)
    if nearby_layers is not None:
        return plan_by_nearby_layers(params, world, nearby_layers)
    return plan_by_threshold(params, world, threshold_mb)


def rescale_plan(plan: FusionPlan, world: int,
                 *, epoch: Optional[int] = None,
                 platform: Optional[str] = None) -> FusionPlan:
    """Rebuild ``plan`` for a NEW replica count (elastic membership change:
    a host is lost or readmitted and the data-parallel world shrinks or
    grows), or for the collectives of ``platform`` (`bucket_length`;
    `build_train_step` passes its mesh's where its schedule's legs are
    lane-dense, None elsewhere, so a plan made anywhere — a tuner's
    groups, an elastic resize — is padded for the program that runs it).
    The leaf specs and bucket grouping are preserved exactly — only
    the per-bucket padding and shard sizes are recomputed — so
    `tuning.autotune.repack_state` can carry a live `DearState` across the
    resize. ``epoch`` stamps the membership epoch into the plan (and
    therefore into `utils.checkpoint.plan_fingerprint`), keeping
    plan-fingerprinted restores coherent across reconfigurations.
    """
    lengths = [bucket_length(b.size, world, platform) for b in plan.buckets]
    if (world == plan.world and (epoch is None or epoch == plan.epoch)
            and lengths == [b.padded_size for b in plan.buckets]):
        return plan
    rebuilt = _build_plan(
        plan.leaves, [list(b.leaf_ids) for b in plan.buckets], world,
        plan.treedef, lengths,
    )
    return dataclasses.replace(
        rebuilt, epoch=plan.epoch if epoch is None else int(epoch))


#: XLA:TPU compiles a bucket's reduce-scatter over a v5e 2x2 (four chips)
#: as one "all-reduce-scatter fusion" that cuts the operand into equal
#: spans of whole quanta, 128 x 128 elements each, at most `_SPAN_QUANTA`
#: of them a span (3.7 MB of a bf16 wire). Where the bucket is no whole
#: number of such spans the compiler pads it itself and the reduce-scatter
#: becomes an all-reduce of the padded operand and a slice: twice the
#: wire, and XLA then combines neighbouring buckets' all-reduces into one
#: that waits for the last of them (PERF.md, PR 41: the span counts and
#: sizes the compiler reports, bisected for each bucket size).
_SPAN_QUANTUM = 128 * 128
_SPAN_QUANTA = 114


def spans_apply(platform: Optional[str], world: int) -> bool:
    """Whether ``bucket_length`` pads to XLA:TPU's reduce-scatter spans: a
    TPU mesh of four, the one reduction world the span rule was read on (a
    v5e 2x2; `parallel.schedules.Dear.lane_dense` adds the rest of what it
    was read on: the dense 'dear' / 'fsdp' legs over a bf16 wire)."""
    return platform == "tpu" and world == 4


def bucket_length(n: int, world: int, platform: Optional[str] = None) -> int:
    """Padded length of a bucket of ``n`` elements over ``world`` devices
    of ``platform`` (a mesh's ``devices.flat[0].platform``). Everywhere but
    `spans_apply`: the smallest multiple of ``world``. There: the fewest
    spans of at most `_SPAN_QUANTA` quanta that hold ``n``, each of the
    same whole number of quanta, so the compiler adds no padding of its own
    and keeps the reduce-scatter (a bf16 wire; an f32 one is cut
    differently and may still be padded). The zeros cost at most one
    quantum a span: 0.9% of a 25 MB bucket."""
    if n == 0 or not spans_apply(platform, world):
        return padded_length(n, world)
    quanta = -(-n // _SPAN_QUANTUM)
    spans = -(-quanta // _SPAN_QUANTA)
    return spans * -(-quanta // spans) * _SPAN_QUANTUM


def _build_plan(specs, groups, world, treedef, lengths=None) -> FusionPlan:
    """The plan of ``groups`` (leaf ids a bucket). ``lengths``: each
    bucket's padded length (`rescale_plan`, a checkpoint's `plan_desc`);
    None pads to the smallest multiple of ``world``."""
    if world < 1:
        raise ValueError(f"world must be >= 1, got {world}")
    buckets = []
    seen: set[int] = set()
    for idx, leaf_ids in enumerate(groups):
        offsets = []
        off = 0
        for i in leaf_ids:
            if i in seen:
                raise ValueError(f"leaf {i} assigned to two buckets")
            seen.add(i)
            offsets.append(off)
            off += specs[i].size
        padded = (padded_length(off, world) if lengths is None
                  else int(lengths[idx]))
        if padded < off or padded % world:
            raise ValueError(
                f"bucket {idx}: padded length {padded} does not hold "
                f"{off} elements in a multiple of world={world}")
        buckets.append(
            Bucket(
                index=idx,
                leaf_ids=tuple(leaf_ids),
                offsets=tuple(offsets),
                size=off,
                padded_size=padded,
                shard_size=padded // world,
            )
        )
    if len(seen) != len(specs):
        missing = [s.name for i, s in enumerate(specs) if i not in seen]
        raise ValueError(f"leaves not covered by any bucket: {missing}")
    return FusionPlan(
        leaves=tuple(specs), buckets=tuple(buckets), world=world, treedef=treedef
    )


# ---------------------------------------------------------------------------
# Pack / unpack (pure; XLA fuses these into neighbouring ops)
# ---------------------------------------------------------------------------


def pack_bucket(
    leaves: Sequence[jax.Array], plan: FusionPlan, bucket: int, dtype=None
) -> jax.Array:
    """Flatten + concatenate + zero-pad one bucket's leaves into the flat
    padded comm buffer (the reference's ``push_tensor`` copy-in,
    tensorfusion.py:85-115, plus ``_get_pad_tensor`` padding,
    dear_dopt.py:186-194)."""
    b = plan.buckets[bucket]
    parts = []
    for leaf_id in b.leaf_ids:
        x = leaves[leaf_id].reshape(-1)
        parts.append(x.astype(dtype) if dtype is not None else x)
    if b.pad:
        pad_dtype = parts[0].dtype if parts else (dtype or jnp.float32)
        parts.append(jnp.zeros((b.pad,), dtype=pad_dtype))
    return jnp.concatenate(parts) if parts else jnp.zeros((0,))


def unpack_bucket(
    buf: jax.Array, plan: FusionPlan, bucket: int, *, wrap=None, cast=False
) -> dict[int, jax.Array]:
    """Slice a flat (padded) buffer back into `{leaf_id: tensor}` views
    (``pull_alltensors``, tensorfusion.py:117-127).

    ``wrap`` is applied to EVERY intermediate (slice, reshape, cast) — the
    fsdp schedule injects `checkpoint_name` here so no unnamed alias of the
    gathered weights is saveable as a remat residual. ``cast=True`` restores
    each leaf's original dtype (what `unpack_all` does by default).
    """
    w = wrap if wrap is not None else (lambda x: x)
    b = plan.buckets[bucket]
    out = {}
    for leaf_id, off in zip(b.leaf_ids, b.offsets):
        spec = plan.leaves[leaf_id]
        x = w(jax.lax.dynamic_slice_in_dim(buf, off, spec.size))
        x = w(x.reshape(spec.shape))
        if cast and x.dtype != spec.dtype:
            x = w(x.astype(spec.dtype))
        out[leaf_id] = x
    return out


def pack_all(tree, plan: FusionPlan, dtype=None) -> list[jax.Array]:
    """Pack every bucket from a pytree with the plan's structure. Each
    bucket's copy is named ``bucket<g>`` (under the step's ``dear/pack``: a
    profile shows which bucket a copy belongs to)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != len(plan.leaves):
        raise ValueError(
            f"tree has {len(leaves)} leaves, plan expects {len(plan.leaves)}"
        )
    out = []
    for b in plan.buckets:
        with jax.named_scope(f"bucket{b.index}"):
            out.append(pack_bucket(leaves, plan, b.index, dtype))
    return out


def unpack_all(buffers: Sequence[jax.Array], plan: FusionPlan, *, wrap=None,
               cast=True):
    """Rebuild the original pytree from per-bucket flat buffers, restoring
    each leaf's shape and (with ``cast=True``, the default) dtype. ``wrap``
    and ``cast=False`` serve the fsdp schedule — see `unpack_bucket`. Each
    bucket's slices are named ``bucket<g>``, as `pack_all`'s copies are."""
    if len(buffers) != plan.num_buckets:
        raise ValueError(
            f"{len(buffers)} buffers for {plan.num_buckets} buckets"
        )
    flat: list[Optional[jax.Array]] = [None] * len(plan.leaves)
    for b, buf in zip(plan.buckets, buffers):
        with jax.named_scope(f"bucket{b.index}"):
            pieces = unpack_bucket(buf, plan, b.index, wrap=wrap, cast=cast)
        for leaf_id, x in pieces.items():
            flat[leaf_id] = x
    return jax.tree_util.tree_unflatten(plan.treedef, flat)
