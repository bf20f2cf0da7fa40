"""Flat-buffer gossip bus: one bulk collective per Birkhoff permutation.

The naive ``ppermute`` gossip backend issues one tiny ``jax.lax.ppermute``
per *parameter leaf* per permutation — for a transformer that is hundreds of
latency-bound collectives per consensus step, exactly the regime the paper's
wall-clock argument assumes away (sparse topologies only win when the
per-iteration exchange is bandwidth-bound; see EXPERIMENTS.md §Perf).

The bus instead:

1. lays the whole parameter pytree (and, in the fused train step, the
   optimizer-update pytree) out as one buffer of 128-lane rows per dtype
   group, each leaf row-major in a slot of its own, with a cached two-pass
   layout plan (`BusLayout`, "layout v2"):

   * **pass 1 — row planning**: every leaf's slot is planned in whole
     sublane tiles of one-lane-tile-wide rows *per model shard* (8/16/32
     sublanes for 4/2/1-byte dtypes), so each slot starts on a tile
     boundary, the group satisfies ``rows % (sublane(dtype) · k) == 0`` for
     shard factor k, and padding stays under one sublane tile per slot;
   * **pass 2 — leaf assignment**: *every* leaf is assigned a row range of
     the flat buffer and split over the model axis **by buffer rows** — the
     bus never needed tensor structure. Leaves whose logical axes shard over
     the model axis pack their local 1/k tensor shard; leaves whose axes do
     NOT divide by k (GQA kv-projections at k=16) are **row-split**: shard s
     packs elements ``[s·⌈n/k⌉, (s+1)·⌈n/k⌉)`` of the flat leaf, so nothing
     rides the inter-worker collectives replicated. Row-split leaves sit at
     the HEAD of each group's payload and are re-assembled after the mix by
     one intra-worker (fast ICI) all-gather per dtype group over the model
     axis — issued off the head chunks of the ``nchunks`` pipeline, so the
     gather overlaps the remaining chunks' fused VMEM passes.

2. runs consensus as **one bulk collective per non-identity permutation** of
   the Birkhoff decomposition ``A = Σ_p w_p·P_p`` — collective count per
   gossip step drops from ``leaves × perms`` to ``perms``, and per-device
   collective bytes are ``bytes(params)/k`` with zero replicated-leaf bytes
   (HLO-asserted in tests/test_bus_layout.py and benchmarks/bench_groups.py);
3. consumes the neighbor buffers directly with the fused Pallas
   ``gossip_mix`` kernel, so mix + weighted self term + ``−η·update`` is a
   single VMEM pass over the flat buffer ((k+2) reads + 1 write per element
   instead of 3(k+2) accesses for the unfused axpy chain);
4. optionally splits the buffer into pipeline chunks: chunk *c*'s ppermute
   is issued before chunk *c−1*'s fused compute, so on hardware with async
   collectives the permute of the next chunk overlaps the mix of the current
   one (double-buffered software pipeline; ``nchunks=1`` keeps the
   one-collective-per-permutation guarantee).

Without a mesh the bus runs a single-process emulation: the kernel reads each
permutation's neighbor rows in place from the other workers' rows of the
buffer, numerically identical to the distributed path (same kernel, same
summation order) — this is what the fp32-exactness tests pin down.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
# The row-split re-assembly is the same on every model shard, so its gather
# must type as Invariant over the model axis for jax.shard_map's vma check;
# jax.lax.all_gather types its result Varying, and JAX 0.9 exports no
# public invariant gather.
from jax._src.lax.parallel import all_gather_invariant
from jax.sharding import PartitionSpec as P

from repro import telemetry
from repro.kernels.gossip_mix.kernel import (
    DEFAULT_BLOCK_C,
    DEFAULT_BLOCK_R,
    gossip_mix_2d,
)
from repro.kernels.quant_pack.kernel import quantize_pack_2d

PyTree = Any

__all__ = ["BusLayout", "plan_layout", "pack", "unpack", "mix_bus",
           "mix_bus_compressed", "mix_and_update_time_varying",
           "bulk_collectives_per_step", "sublane_rows", "sharded_leaf_flags",
           "quantize_wire", "dequantize_wire", "wire_dtype_for",
           "WIRE_DTYPES", "LANE"]

# Bus rows are exactly one lane tile wide: padding granularity is one
# sublane tile (sublane(dtype) × 128 elements) per slot and model shard
# instead of a full 32×block_c block.
LANE = 128


def sublane_rows(dtype) -> int:
    """Native sublane tile height for ``dtype``: 8 fp32, 16 bf16, 32 int8."""
    return max(8, 32 // max(jnp.dtype(dtype).itemsize, 1))


# Wire dtypes the compressed (DCI) lane supports. bf16 is a plain cast;
# int8 carries one fp32 scale per 128-lane bus row (absmax/127 rounding).
WIRE_DTYPES = ("bfloat16", "int8")

# int8 wire rows ship one fp32 scale each (the quantize-pack side buffer).
_SCALE_BYTES_PER_ROW = 4


def wire_dtype_for(dtype, wire_dtype) -> jnp.dtype | None:
    """The dtype a ``dtype`` bus group ships at on a compressed lane.

    ``None`` → the group stays exact: the lane is off (``wire_dtype=None``),
    the group is not floating point (int/bool state never quantizes), or
    compression would not shrink it (bf16 → bf16). Raises on wire dtypes
    outside :data:`WIRE_DTYPES`.
    """
    if wire_dtype is None:
        return None
    wt = jnp.dtype(wire_dtype)
    if str(wt) not in WIRE_DTYPES:
        raise ValueError(
            f"unsupported wire dtype {wire_dtype!r}; expected one of "
            f"{WIRE_DTYPES}")
    dt = jnp.dtype(dtype)
    # jnp.issubdtype, not dt.kind: ml_dtypes (bfloat16) report kind 'V'
    if not jnp.issubdtype(dt, jnp.floating) or dt.itemsize <= wt.itemsize:
        return None
    return wt


def quantize_wire(x: jax.Array, wire_dtype) -> tuple[jax.Array, jax.Array | None]:
    """Quantize one array for the lossy wire: ``(payload, scale-or-None)``.

    bf16 wire is a cast (``scale=None``); int8 wire uses a per-row absmax
    scale over the LAST axis (``scale = absmax/127``, fp32, shape
    ``x.shape[:-1] + (1,)``) so ``|x − payload·scale| ≤ scale/2``
    elementwise and all-zero rows round-trip exactly. This is the generic
    (pytree-leaf) twin of the fused bus-buffer kernel
    (`repro.kernels.quant_pack.quantize_pack_2d`).
    """
    wt = jnp.dtype(wire_dtype)
    if str(wt) == "bfloat16":
        return x.astype(jnp.bfloat16), None
    xf = jnp.asarray(x, jnp.float32)
    squeeze = xf.ndim == 0
    if squeeze:
        xf = xf[None]
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.round(xf / scale).astype(jnp.int8)
    if squeeze:
        return q[0], scale[0]
    return q, scale


def dequantize_wire(payload: jax.Array, scale: jax.Array | None,
                    dtype) -> jax.Array:
    """Inverse of :func:`quantize_wire` up to the quantization error."""
    if scale is None:
        return payload.astype(dtype)
    return (payload.astype(jnp.float32) * scale).astype(dtype)


@dataclasses.dataclass(frozen=True)
class _LeafSlot:
    """Pass-2 assignment of one leaf to a row range of the flat buffer."""

    leaf_id: int      # index into the flattened pytree
    size: int         # element count of the leaf as seen locally
    chunk: int        # per-model-shard element count the slot carries
    offset: int       # first row of the slot in the per-shard buffer
    rows: int         # per-shard rows: whole sublane tiles holding ``chunk``
    sharded: bool     # True → local value is already the 1/k tensor shard


@dataclasses.dataclass(frozen=True)
class _Group:
    """Leaves of one dtype packed into one (lead..., R, C) buffer."""

    dtype: jnp.dtype
    slots: tuple[_LeafSlot, ...]   # buffer order (row-split first)
    n: int                         # per-shard payload elements (un-padded)
    rows: int                      # R per shard — multiple of sublane(dtype)
    cols: int                      # C — one lane tile (LANE)
    block_r: int                   # tile rows actually used by the kernel
    split_off: int                 # first row of the row-split slots
    split_end: int = 0             # row where the row-split slots end


@dataclasses.dataclass(frozen=True)
class BusLayout:
    """Cached flatten/unflatten plan for a parameter pytree.

    ``shards`` is the model-parallel factor k the buffer rows are split
    over; every per-shard row count is a whole number of sublane tiles, so
    the *global* rows satisfy ``rows % (sublane(dtype)·k) == 0`` per group.
    """

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]   # trailing (per-worker) local shapes
    groups: tuple[_Group, ...]
    shards: int = 1

    @property
    def n_buffers(self) -> int:
        return len(self.groups)

    def padded_elements(self) -> int:
        """Per-shard buffer elements (incl. tile padding)."""
        return sum(g.rows * g.cols for g in self.groups)

    def payload_elements(self) -> int:
        """Per-shard payload elements."""
        return sum(g.n for g in self.groups)

    def padded_bytes(self, wire_dtype=None) -> int:
        """Per-shard buffer bytes — the exact per-device payload of one bulk
        collective (what the HLO byte-efficiency tests predict against).

        ``wire_dtype`` prices the compressed lane (per-link-class variant):
        floating groups wider than the wire dtype ship at the wire width —
        int8 additionally carries one fp32 scale per buffer row — while
        every other group stays at its exact bytes. ``None`` (default) is
        the exact lane, unchanged.
        """
        total = 0
        for g in self.groups:
            wt = wire_dtype_for(g.dtype, wire_dtype)
            if wt is None:
                total += g.rows * g.cols * jnp.dtype(g.dtype).itemsize
            else:
                total += g.rows * g.cols * wt.itemsize
                if wt == jnp.dtype(jnp.int8):
                    total += g.rows * _SCALE_BYTES_PER_ROW
        return total


def _pick_block_r(rows: int, block_r: int, sub: int) -> int:
    """Largest tile height ≤ block_r dividing rows (a multiple of sub)."""
    b = (min(block_r, rows) // sub) * sub
    while b > sub and rows % b:
        b -= sub
    return max(b, sub)  # rows % sub == 0 by construction


def sharded_leaf_flags(param_specs: PyTree, model_axis: str | None,
                       treedef=None) -> tuple[bool, ...]:
    """Per-leaf: does the leaf's PartitionSpec shard over ``model_axis``?

    True → the local value inside a worker+model-manual shard_map is already
    the 1/k tensor shard (the bus packs it whole); False → the leaf is
    replicated over the model axis and the bus row-splits it (layout v2)
    instead of shipping it in full through every bulk ppermute.
    """
    is_p = lambda s: s is None or isinstance(s, P)
    if treedef is not None:
        specs = treedef.flatten_up_to(param_specs)
    else:
        specs = jax.tree.leaves(param_specs, is_leaf=is_p)

    def on_model(sp) -> bool:
        if model_axis is None or sp is None:
            return False
        for entry in sp:
            names = entry if isinstance(entry, tuple) else (entry,)
            if model_axis in names:
                return True
        return False

    return tuple(on_model(sp) for sp in specs)


_LAYOUT_CACHE: dict[Any, BusLayout] = {}


def plan_layout(tree: PyTree, *, lead_ndim: int = 1,
                block_r: int = DEFAULT_BLOCK_R,
                shards: int = 1,
                leaf_sharded: Sequence[bool] | None = None) -> BusLayout:
    """Build (or fetch from cache) the layout-v2 bus plan for ``tree``.

    ``lead_ndim`` leading dims of every leaf (the worker dim in gossip mode)
    are kept out of the buffer rows; each leaf's trailing elements fill a
    slot of rows, row-major, grouped by dtype, in two passes:

    * pass 1 plans every slot as whole sublane tiles of LANE-wide rows, so
      each slot starts on a tile boundary and the group's per-shard
      ``rows % sublane(dtype) == 0`` — the global buffer satisfies
      ``rows % (sublane·shards) == 0`` — with padding under one sublane
      tile per slot;
    * pass 2 assigns every leaf an (offset, rows) row range of the buffer,
      splitting it over the model axis by buffer rows.
      ``leaf_sharded[i]`` (flatten order) marks leaves whose *local* value is
      already the 1/k tensor shard; all other leaves are row-split —
      shard s owns elements ``[s·rows·LANE, (s+1)·rows·LANE)`` of the flat
      leaf (``rows`` holding ``⌈n/shards⌉`` elements, zero-padded).

    Layout v2 fixes the row width to one lane tile (``LANE``) so tail
    padding is minimal; kernel tile width is a mix-time knob (``block_c`` on
    :func:`mix_bus`), not a layout property.
    """
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(x.shape[lead_ndim:]) for x in leaves)
    dtypes = tuple(jnp.dtype(x.dtype) for x in leaves)
    if shards <= 1:
        flags = (True,) * len(leaves)       # 1 shard: every leaf packs whole
    elif leaf_sharded is None:
        flags = (False,) * len(leaves)      # row-split everything
    else:
        flags = tuple(bool(f) for f in leaf_sharded)
        assert len(flags) == len(leaves), (len(flags), len(leaves))
    key = (treedef, shapes, dtypes, lead_ndim, block_r, shards, flags)
    cached = _LAYOUT_CACHE.get(key)
    if cached is not None:
        return cached

    by_dtype: dict[jnp.dtype, list[int]] = {}
    for i, dt in enumerate(dtypes):
        by_dtype.setdefault(dt, []).append(i)
    groups = []
    for dt, ids in by_dtype.items():
        sub = sublane_rows(dt)
        # pass 2 (leaf → row-range assignment). Row-split leaves FIRST so
        # the span the post-mix intra-worker all-gather needs is a contiguous
        # HEAD span per group: the gather depends only on the buffer's first
        # chunks and overlaps the later chunks' fused VMEM passes in the
        # nchunks pipeline (`_mix_group_chunked`).
        ids = sorted(ids, key=lambda i: (flags[i],))
        slots, off, n, split_lo, split_hi = [], 0, 0, None, None
        for i in ids:
            size = int(np.prod(shapes[i], dtype=np.int64))
            whole = flags[i] or size == 0   # nothing to row-split in 0 elems
            chunk = size if whole else -(-size // shards)
            # pass 1 (row planning): every slot is whole sublane tiles, so
            # it starts on a tile boundary — padding < sub·LANE per slot
            slot_rows = -(-chunk // (sub * LANE)) * sub
            if not whole:
                split_lo = off if split_lo is None else split_lo
                split_hi = off + slot_rows
            slots.append(_LeafSlot(leaf_id=i, size=size, chunk=chunk,
                                   offset=off, rows=slot_rows, sharded=whole))
            off += slot_rows
            n += chunk
        rows = max(off, sub)   # a group of empty leaves still has one tile
        groups.append(_Group(dtype=dt, slots=tuple(slots), n=n, rows=rows,
                             cols=LANE,
                             block_r=_pick_block_r(rows, block_r, sub),
                             split_off=0 if split_lo is None else split_lo,
                             split_end=0 if split_hi is None else split_hi))
    layout = BusLayout(treedef=treedef, shapes=shapes, groups=tuple(groups),
                       shards=shards)
    _LAYOUT_CACHE[key] = layout
    return layout


def _slot_view(shape: tuple[int, ...]) -> tuple[tuple[int, ...], bool, int]:
    """How a leaf of trailing ``shape`` is viewed in its slot, from its shape.

    Returns ``(view, swap, grow)``: the slot holds ``view`` row-major, then
    zeros. Where the leaf's minor dim is not whole 128-lane tiles but the
    one before it is (mamba2's in_proj, 10576 × 2560 per layer), the TPU
    keeps the leaf with those two dims swapped, so the view swaps them too
    and the leaf enters and leaves the bus without a transposing copy
    (``swap``). Where a matrix's row count is not whole groups of 8
    sublanes (the 49155-row granite vocab table), the TPU puts the worker
    dim inside its tiles, and relaying it out to or from bus rows in one
    piece takes the compiler over a minute and a half for that one leaf;
    the view pads its rows to whole groups (``grow`` rows of zeros after
    the leaf, so the slot still starts with the leaf row-major).
    """
    swap = len(shape) >= 2 and shape[-1] % LANE != 0 and shape[-2] % LANE == 0
    if swap:
        shape = shape[:-2] + (shape[-1], shape[-2])
    grow = 0
    if len(shape) == 2 and shape[0] % 8 and shape[1] % 16 == 0:
        grow = -shape[0] % 8
        shape = (shape[0] + grow, shape[1])
    return shape, swap, grow


def _fit_rows(x: jax.Array, rows: int) -> jax.Array:
    """``x`` (lead..., r, LANE) cut or zero-padded to ``rows`` rows."""
    have = x.shape[-2]
    if have > rows:
        return jax.lax.slice_in_dim(x, 0, rows, axis=x.ndim - 2)
    if have < rows:
        return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, rows - have), (0, 0)])
    return x


def _to_rows(x: jax.Array, lead_ndim: int, rows: int) -> jax.Array:
    """``x``'s trailing elements in their slot view, as ``lead + (rows, LANE)``.

    Zero-padded to ``rows``. A leaf of whole 128-lane rows reshapes straight
    into its rows, so the TPU compiler writes it to the bus in one tiled
    copy; only a leaf with a ragged last row (a few hundred elements of
    per-head scalars) goes through a flat row on the way.
    """
    lead = x.shape[:lead_ndim]
    view, swap, grow = _slot_view(x.shape[lead_ndim:])
    if swap:
        x = jnp.swapaxes(x, -1, -2)
    if grow:
        x = jnp.pad(x, [(0, 0)] * lead_ndim + [(0, grow), (0, 0)])
    n = int(np.prod(view, dtype=np.int64))
    if n % LANE:
        x = jnp.pad(x.reshape(lead + (n,)),
                    [(0, 0)] * lead_ndim + [(0, -n % LANE)])
    return _fit_rows(x.reshape(lead + (-(-n // LANE), LANE)), rows)


def _from_rows(piece: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`_to_rows`: ``lead + (rows, LANE)`` → ``lead + shape``."""
    lead = piece.shape[:-2]
    view, swap, grow = _slot_view(shape)
    n = int(np.prod(view, dtype=np.int64))
    piece = _fit_rows(piece, -(-n // LANE))
    if n % LANE:
        piece = jax.lax.slice_in_dim(piece.reshape(lead + (-1,)), 0, n,
                                     axis=len(lead))
    x = piece.reshape(lead + view)
    if grow:
        x = jax.lax.slice_in_dim(x, 0, view[0] - grow, axis=len(lead))
    return jnp.swapaxes(x, -1, -2) if swap else x


def pack(tree: PyTree, layout: BusLayout, *, lead_ndim: int = 1,
         shard_index: Any = 0) -> list[jax.Array]:
    """Lay ``tree`` out as one (lead..., R, C) buffer per dtype group.

    Each leaf becomes its slot's rows directly and the slots are joined
    along the row axis, so no ``lead + (elements,)`` row of the whole group
    is ever built. With ``layout.shards > 1``, ``shard_index`` (python int
    or traced ``lax.axis_index``) selects which row range of each row-split
    leaf this shard packs; tensor-sharded leaves pack their local value
    whole.
    """
    leaves = layout.treedef.flatten_up_to(tree)
    bufs = []
    for g in layout.groups:
        parts = []
        for slot in g.slots:
            x = leaves[slot.leaf_id]
            if slot.sharded or layout.shards == 1:
                parts.append(_to_rows(x, lead_ndim, slot.rows))
            else:
                full = _to_rows(x, lead_ndim, layout.shards * slot.rows)
                parts.append(jax.lax.dynamic_slice_in_dim(
                    full, shard_index * slot.rows, slot.rows, axis=lead_ndim))
        used = sum(slot.rows for slot in g.slots)
        if used < g.rows:   # a group of empty leaves: one tile of zeros
            lead = leaves[g.slots[0].leaf_id].shape[:lead_ndim]
            parts.append(jnp.zeros(lead + (g.rows - used, g.cols), g.dtype))
        bufs.append(parts[0] if len(parts) == 1 else
                    jnp.concatenate(parts, axis=lead_ndim))
    # Fence the packed buffers (and, in unpack, the mixed ones) off from
    # their producers and consumers: fused with the leaf reshapes, a
    # granite-width bus took the TPU compiler over a minute and 12 GB of
    # host memory without finishing; fenced, it compiles in 10 s.
    return jax.lax.optimization_barrier(bufs)


def unpack(bufs: Sequence[jax.Array], layout: BusLayout, *,
           lead_ndim: int = 1,
           gather: Callable[[jax.Array], jax.Array] | None = None) -> PyTree:
    """Inverse of :func:`pack` (padding is dropped).

    With ``layout.shards > 1``, row-split leaves need the other shards'
    rows back: ``gather`` maps this shard's row-split rows
    ``buf[split_off:split_end]`` to a ``(shards, span, LANE)`` array stacked
    in shard order (in the distributed path: ``lax.all_gather`` over the
    model axis — intra-worker ICI, never the inter-worker gossip links).
    """
    leaves: list[jax.Array | None] = [None] * len(layout.shapes)
    bufs = jax.lax.optimization_barrier(list(bufs))   # see pack
    for g, buf in zip(layout.groups, bufs):
        gathered = None
        if layout.shards > 1 and g.split_off < g.split_end:
            assert gather is not None, "row-split leaves need a gather fn"
            assert lead_ndim == 0, "row-split unpack is per-shard (lead_ndim=0)"
            gathered = gather(jax.lax.slice_in_dim(buf, g.split_off,
                                                   g.split_end, axis=0))
        for slot in g.slots:
            if slot.sharded or layout.shards == 1:
                piece = jax.lax.slice_in_dim(
                    buf, slot.offset, slot.offset + slot.rows, axis=lead_ndim)
            else:
                off = slot.offset - g.split_off
                piece = jax.lax.slice_in_dim(
                    gathered, off, off + slot.rows, axis=1
                ).reshape(layout.shards * slot.rows, g.cols)
            leaves[slot.leaf_id] = _from_rows(piece,
                                              layout.shapes[slot.leaf_id])
    return layout.treedef.unflatten(leaves)


# ---------------------------------------------------------------------------
# Bulk consensus over packed buffers
# ---------------------------------------------------------------------------


def _ambient_mesh(mesh):
    """``mesh``, else the mesh set by ``jax.set_mesh``; None when neither
    exists (the bus then runs its single-process emulation)."""
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _split_perms(spec) -> tuple[float, list[tuple[float, np.ndarray]]]:
    """(identity weight, non-identity (weight, perm) list) of spec's A."""
    M = spec.topology.M
    ident = np.arange(M)
    a0 = 0.0
    others = []
    for w, perm in spec.permutations:
        if np.array_equal(perm, ident):
            a0 += w
        else:
            others.append((w, perm))
    return a0, others


def bulk_collectives_per_step(spec, nchunks: int = 1) -> int:
    """Bulk collectives one bus gossip step issues (vs leaves × perms)."""
    _, others = _split_perms(spec)
    return len(others) * max(nchunks, 1)


def _chunk_starts(rows: int, block_r: int, nchunks: int) -> list[tuple[int, int]]:
    """Split ``rows`` into ≤ nchunks (start, size) tiles of whole blocks."""
    nblocks = rows // block_r
    nchunks = max(1, min(nchunks, nblocks))
    base, extra = divmod(nblocks, nchunks)
    out, start = [], 0
    for c in range(nchunks):
        size = (base + (1 if c < extra else 0)) * block_r
        out.append((start, size))
        start += size
    return out


def _mix_group_chunked(x2, u2, rows, block_r, block_c, weights, eta, pairs,
                       axes, nchunks, interpret, donate, *,
                       gather=None, span=None):
    """Mix one (rows, cols) buffer: pipelined bulk ppermutes + fused kernel.

    With ``nchunks > 1`` the buffer is software-pipelined: the permutes for
    chunk c+1 are issued *before* the fused kernel for chunk c, so async
    collectives (TPU collective-permute-start/-done) overlap the previous
    chunk's VMEM pass — the classic double-buffered pattern, two chunks of
    neighbor data live at a time.

    ``gather``/``span``: the model-sharded path's post-mix re-assembly of
    row-split leaves folds into the same pipeline. ``span`` is the
    (start, end) row range of the row-split slots — a HEAD span since
    layout v2 packs row-split leaves first — and ``gather`` maps those rows
    to the (shards, span, cols) stack (one ``all_gather`` over the model
    axis). The gather is issued as soon as the chunks covering the span
    have run, so its operand depends only on the EARLY chunks: the
    intra-worker ICI gather overlaps the remaining chunks' fused VMEM passes
    instead of waiting for the whole buffer. Returns (mixed, gathered) when
    a gather is requested, else just the mixed buffer.
    """
    chunks = _chunk_starts(rows, min(block_r, rows), nchunks)

    def permute(c):
        start, size = chunks[c]
        x_c = jax.lax.slice_in_dim(x2, start, start + size, axis=0)
        return [jax.lax.ppermute(x_c, axes, pr) for pr in pairs]

    nbrs = permute(0)
    pieces, gathered = [], None
    for c, (start, size) in enumerate(chunks):
        nxt = permute(c + 1) if c + 1 < len(chunks) else None
        w_c = jax.lax.slice_in_dim(x2, start, start + size, axis=0)
        u_c = None if u2 is None else jax.lax.slice_in_dim(
            u2, start, start + size, axis=0)
        pieces.append(gossip_mix_2d(
            w_c, nbrs, weights, u_c, eta,
            block_r=min(block_r, size), block_c=block_c,
            interpret=interpret, donate=donate))
        if gather is not None and gathered is None and start + size >= span[1]:
            head = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, 0)
            gathered = gather(jax.lax.slice_in_dim(head, span[0], span[1],
                                                   axis=0))
        nbrs = nxt
    out = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, 0)
    return out if gather is None else (out, gathered)


def _perm_pairs(spec, perms):
    M = spec.topology.M
    return [[(int(perm[j]), j) for j in range(M)] for _, perm in perms]


def _mix_buffers_sharded(bufs, upd_bufs, spec, mesh, weights, eta, perms,
                         nchunks, interpret, donate, groups, block_c):
    """Distributed path: bulk ppermute per permutation inside shard_map.

    The worker dim of every (M, R, C) buffer is manual over the worker axes;
    each worker's whole replica buffer lives (replicated) on its model group.
    For model-sharded replicas use :func:`_mix_pytree_model_sharded` instead —
    it never materializes the full replica on one device.
    """
    axes = spec.worker_axes if len(spec.worker_axes) > 1 else spec.worker_axes[0]
    pairs = _perm_pairs(spec, perms)

    in_specs = tuple(P(spec.worker_axes) for _ in bufs)
    if upd_bufs is not None:
        in_specs = in_specs + tuple(P(spec.worker_axes) for _ in upd_bufs)

    def f(*args):
        xs = args[:len(bufs)]
        us = args[len(bufs):] if upd_bufs is not None else [None] * len(xs)
        outs = []
        for x, u, g in zip(xs, us, groups):
            x2 = x[0]                        # per-shard worker dim is 1
            u2 = None if u is None else u[0]
            out = _mix_group_chunked(x2, u2, g.rows, g.block_r, block_c,
                                     weights, eta, pairs, axes, nchunks,
                                     interpret, donate)
            outs.append(out[None])
        return tuple(outs)

    out = jax.shard_map(
        f, mesh=mesh, in_specs=in_specs,
        out_specs=tuple(P(spec.worker_axes) for _ in bufs),
        axis_names=set(spec.worker_axes), check_vma=not interpret,
    )(*(tuple(bufs) + tuple(upd_bufs or ())))
    return list(out)


def _mix_pytree_model_sharded(params, updates, spec, mesh, param_specs,
                              weights, eta, perms, nchunks, interpret, donate,
                              block_r, block_c):
    """Worker-group path: gossip composed with model-parallel replicas.

    ``param_specs`` carries each leaf's full PartitionSpec (leading worker
    entry + any 'model' sharding of heads/ff/vocab). The shard_map makes the
    worker axes AND the model axis manual, so every device sees only its
    local 1/k model shard of each tensor-sharded leaf. The body packs the
    layout-v2 bus: tensor-sharded leaves contribute their local shard, every
    other leaf is **row-split** over the model axis by buffer rows (pass 2),
    and per-shard rows are whole sublane tiles (pass 1) — so the bulk
    Birkhoff ppermutes over the worker axes move exactly ``bytes(params)/k``
    per device with zero replicated-leaf bytes. Row-split leaves are
    re-assembled by one all-gather per dtype group over the *model* axis
    (intra-worker ICI — never the slow inter-worker links the paper's
    comm-cost argument charges). Worker j's shard exchanges with the
    same-coordinate shard of its neighbors, which is exactly elementwise
    consensus on the full replica.
    """
    axes = spec.worker_axes if len(spec.worker_axes) > 1 else spec.worker_axes[0]
    pairs = _perm_pairs(spec, perms)
    manual = set(spec.worker_axes)
    k = 1
    if spec.model_axis:
        manual = manual | {spec.model_axis}
        k = int(dict(mesh.shape)[spec.model_axis])

    def f(p, u):
        local = jax.tree.map(lambda x: x[0], p)      # strip worker dim (=1)
        u_loc = None if u is None else jax.tree.map(lambda x: x[0], u)
        flags = sharded_leaf_flags(param_specs, spec.model_axis,
                                   treedef=jax.tree.structure(p))
        layout = plan_layout(local, lead_ndim=0, block_r=block_r,
                             shards=k, leaf_sharded=flags)
        s = jax.lax.axis_index(spec.model_axis) if k > 1 else 0
        with jax.named_scope("pack"):
            bufs = pack(local, layout, lead_ndim=0, shard_index=s)
            upd_bufs = None if u_loc is None else pack(
                u_loc, layout, lead_ndim=0, shard_index=s)
        ici_gather = lambda x: all_gather_invariant(x, spec.model_axis)
        outs, gathered = [], []
        with jax.named_scope("mix"):
            for gi, g in enumerate(layout.groups):
                u2 = None if upd_bufs is None else upd_bufs[gi]
                if k > 1 and g.split_off < g.split_end:
                    # fold the row-split re-assembly gather into the chunk
                    # pipeline: it runs off the head chunks, overlapping the
                    # remaining chunks' fused passes (still ONE gather per
                    # group)
                    out, gat = _mix_group_chunked(
                        bufs[gi], u2, g.rows, g.block_r, block_c, weights,
                        eta, pairs, axes, nchunks, interpret, donate,
                        gather=ici_gather, span=(g.split_off, g.split_end))
                    gathered.append(gat)
                else:
                    out = _mix_group_chunked(
                        bufs[gi], u2, g.rows, g.block_r, block_c, weights,
                        eta, pairs, axes, nchunks, interpret, donate)
                outs.append(out)
        gat_iter = iter(gathered)
        with jax.named_scope("unpack"):
            mixed = unpack(outs, layout, lead_ndim=0,
                           gather=(lambda _span: next(gat_iter)) if gathered
                           else None)
        return jax.tree.map(lambda x: x[None], mixed)

    if updates is None:
        return jax.shard_map(
            lambda p: f(p, None), mesh=mesh, in_specs=(param_specs,),
            out_specs=param_specs, axis_names=manual,
            check_vma=not interpret)(params)
    return jax.shard_map(
        f, mesh=mesh, in_specs=(param_specs, param_specs),
        out_specs=param_specs, axis_names=manual,
        check_vma=not interpret)(params, updates)


def _permute_workers(x: jax.Array, perm) -> jax.Array:
    """``x[perm]`` along the leading worker dim, as static slices.

    A static permutation is a few contiguous runs (two for a ring shift);
    slicing them keeps XLA from lowering an indexed gather, which the TPU
    compiler splits into thousands of small gathers on a multi-GB bus.
    """
    perm = [int(p) for p in perm]
    runs, start = [], 0
    for j in range(1, len(perm) + 1):
        if j == len(perm) or perm[j] != perm[j - 1] + 1:
            runs.append(jax.lax.slice_in_dim(x, perm[start],
                                             perm[j - 1] + 1, axis=0))
            start = j
    return runs[0] if len(runs) == 1 else jnp.concatenate(runs, axis=0)


def _mix_buffers_local(bufs, upd_bufs, weights, eta, perms, nchunks,
                       interpret, groups, block_c):
    """Single-process emulation: the kernel reads each permutation's
    neighbor rows in place from the other workers' rows of the buffer.

    Numerically identical to the sharded path — same kernel, same summation
    order — and mirrors its chunking (each chunk of rows runs through its
    own kernel call) so the pipelined slicing is exercised without a mesh.
    """
    outs = []
    for gi, (x, g) in enumerate(zip(bufs, groups)):
        M = x.shape[0]
        chunks = _chunk_starts(g.rows, min(g.block_r, g.rows), nchunks)
        pieces = []
        for start, size in chunks:
            x_c = jax.lax.slice_in_dim(x, start, start + size, axis=1)
            w2 = x_c.reshape(M * size, g.cols)
            u2 = None
            if upd_bufs is not None:
                u2 = jax.lax.slice_in_dim(
                    upd_bufs[gi], start, start + size, axis=1
                ).reshape(M * size, g.cols)
            # neighbors are read in place from w2: row block of worker m
            # for permutation p comes from worker perm[m]
            pieces.append(gossip_mix_2d(
                w2, [w2] * len(perms), weights, u2, eta,
                block_r=min(g.block_r, size), block_c=block_c,
                interpret=interpret,
                sources=[perm for _, perm in perms]).reshape(M, size, g.cols))
        outs.append(pieces[0] if len(pieces) == 1 else
                    jnp.concatenate(pieces, 1))
    return outs


def mix_bus(params: PyTree, spec, mesh=None, *, updates: PyTree | None = None,
            eta: float | jax.Array = 1.0, nchunks: int = 1,
            interpret: bool | None = None, block_r: int = DEFAULT_BLOCK_R,
            block_c: int = DEFAULT_BLOCK_C,
            param_specs: PyTree | None = None) -> PyTree:
    """Consensus (+ optional fused update) over the flat parameter bus.

    Computes ``P_j ← Σ_i A[i,j]·P_i − eta·U_j`` for every worker j in one
    fused pass per dtype group. ``updates=None`` is the pure-mix path used by
    ``mix_pytree(backend='fused')``; the train step passes the optimizer
    deltas (which already include −lr) with ``eta=-1.0`` so the fused pass
    lands exactly on ``mix(params) + update``.

    With a mesh, the worker dim must be sharded over ``spec.worker_axes`` and
    each non-identity Birkhoff permutation becomes ONE bulk ``ppermute`` of
    the whole buffer (`nchunks` > 1 splits it into that many pipelined
    collectives). Without a mesh, a numerically-identical gather emulation
    runs single-process.

    ``param_specs`` (the per-leaf PartitionSpecs, leading worker entry plus
    any model-axis sharding — ``shardings.param_pspecs`` output) switches the
    sharded path to the per-model-shard layout-v2 bus: each device packs
    exactly ``1/k`` of the replica by buffer rows — tensor-sharded leaves as
    local shards, everything else row-split — so the bulk ppermutes move
    ``1/k`` the bytes with zero replicated-leaf traffic. Required whenever
    the replicas are tensor/FSDP-sharded over ``spec.model_axis``.

    ``interpret=None`` (default) auto-selects: the compiled Pallas kernel on
    TPU, interpret (Python-emulation, correctness-only) mode elsewhere.
    The compiled path keeps ``jax.shard_map``'s vma check on; interpret mode
    turns it off, because the Pallas interpreter (JAX 0.9) re-evaluates the
    kernel body with literals that carry no vma, which the check rejects.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    a0, others = _split_perms(spec)
    # Telemetry fires at TRACE time (mix_bus runs inside jit): one emit per
    # compile, zero per-step cost, and the counters are exactly the per-step
    # collective counts (`bulk_collectives_per_step`) tests cross-check.
    tel = telemetry.get()
    if tel.active:
        tel.counter("bus.mix_calls")
        tel.counter("bus.collectives", bulk_collectives_per_step(spec, nchunks))
    weights = jnp.asarray([a0] + [w for w, _ in others], jnp.float32)
    eta_arr = jnp.asarray([eta], jnp.float32) if updates is not None else None

    if not others:  # degenerate (M == 1): no communication at all
        if updates is None:
            return params
        return jax.tree.map(
            lambda b, u: (b * weights[0] - eta_arr[0] * u).astype(b.dtype),
            params, updates)

    mesh = _ambient_mesh(mesh)
    if mesh is not None and param_specs is not None:
        # pack, mix and unpack are scoped inside the shard_map body
        return _mix_pytree_model_sharded(params, updates, spec, mesh,
                                         param_specs, weights, eta_arr,
                                         others, nchunks, interpret,
                                         donate=not interpret,
                                         block_r=block_r, block_c=block_c)

    layout = plan_layout(params, lead_ndim=1, block_r=block_r)
    if tel.active:
        # the per-device wire payload one gossip round ships on every
        # non-identity permutation — the number the sim's per-class byte
        # accounting charges (MeshSpec.payload_bytes)
        tel.gauge("bus.padded_bytes", layout.padded_bytes())
    with jax.named_scope("pack"):
        bufs = pack(params, layout)
        upd_bufs = None
        if updates is not None:
            upd_bufs = pack(updates, layout)
    with jax.named_scope("mix"):
        if mesh is not None:
            mixed = _mix_buffers_sharded(bufs, upd_bufs, spec, mesh, weights,
                                         eta_arr, others, nchunks, interpret,
                                         donate=not interpret,
                                         groups=layout.groups, block_c=block_c)
        else:
            mixed = _mix_buffers_local(bufs, upd_bufs, weights, eta_arr,
                                       others, nchunks, interpret,
                                       groups=layout.groups, block_c=block_c)
    with jax.named_scope("unpack"):
        return unpack(mixed, layout)


# ---------------------------------------------------------------------------
# Compressed (lossy) consensus lane — the DCI stage of hierarchical gossip
# ---------------------------------------------------------------------------


def _quantize_rows(xe: jax.Array, block_r: int, interpret: bool):
    """Fused int8 quantize-pack of a (lead..., R, C) fp32 buffer.

    Returns ``(values int8, scales fp32 (lead..., R, 1))`` — one scale per
    128-lane bus row, computed by the Pallas quantize-pack kernel over the
    row-flattened view (``block_r`` divides R, so it divides lead·R).
    """
    C = xe.shape[-1]
    x2 = xe.reshape(-1, C)
    q, s = quantize_pack_2d(x2, block_r=min(block_r, x2.shape[0]),
                            interpret=interpret)
    return q.reshape(xe.shape), s.reshape(xe.shape[:-1] + (1,))


def _dequant_f32(v: jax.Array, s: jax.Array | None) -> jax.Array:
    return v.astype(jnp.float32) if s is None else v.astype(jnp.float32) * s


def _mix_buffers_local_compressed(bufs, res_bufs, weights, perms, groups,
                                  wire_dtype, interpret):
    """Single-process emulation of the compressed lane (row-gather permute).

    Permuting the dequantized buffer is elementwise-identical to permuting
    (values, scales) and dequantizing at the receiver — which is what the
    sharded path does on the wire — so this emulation is numerically exact
    against it, mirroring `_mix_buffers_local` vs `_mix_buffers_sharded`.
    """
    outs, new_res = [], []
    for gi, (x, g) in enumerate(zip(bufs, groups)):
        wt = wire_dtype_for(g.dtype, wire_dtype)
        if wt is None:   # exact group: int/bool state never quantizes
            acc = x.astype(jnp.float32) * weights[0]
            for i, (_, perm) in enumerate(perms):
                acc += _permute_workers(x, perm).astype(jnp.float32) * weights[i + 1]
            outs.append(acc.astype(g.dtype))
            new_res.append(None)
            continue
        r = res_bufs[gi]
        xe = x.astype(jnp.float32) + r
        if str(wt) == "bfloat16":
            deq = xe.astype(jnp.bfloat16).astype(jnp.float32)
        else:
            v, s = _quantize_rows(xe, g.block_r, interpret)
            deq = _dequant_f32(v, s)
        acc = deq * weights[0]
        for i, (_, perm) in enumerate(perms):
            acc += _permute_workers(deq, perm) * weights[i + 1]
        outs.append(acc.astype(g.dtype))
        new_res.append(xe - deq)
    return outs, new_res


def _mix_buffers_sharded_compressed(bufs, res_bufs, spec, mesh, weights,
                                    perms, groups, wire_dtype, interpret):
    """Distributed compressed lane: ppermute the WIRE image, not the buffer.

    Each non-identity Birkhoff permutation moves the int8 values plus the
    narrow fp32 scales (or the bf16 cast) — per-device collective bytes are
    exactly ``BusLayout.padded_bytes(wire_dtype)``, the per-class prediction
    the HLO tests pin. Every worker mixes DEQUANTIZED values (its own
    included), so the consensus mean is preserved over the dequantized
    estimates and the quantization error stays in the local EF residual.
    """
    axes = spec.worker_axes if len(spec.worker_axes) > 1 else spec.worker_axes[0]
    pairs = _perm_pairs(spec, perms)
    n = len(bufs)
    res_in = [r for r in res_bufs if r is not None]
    in_specs = tuple(P(spec.worker_axes) for _ in range(n + len(res_in)))

    def f(*args):
        xs, rs = args[:n], iter(args[n:])
        outs, news = [], []
        for x, g in zip(xs, groups):
            x2 = x[0]                      # per-shard worker dim is 1
            wt = wire_dtype_for(g.dtype, wire_dtype)
            if wt is None:
                acc = x2.astype(jnp.float32) * weights[0]
                for i, pr in enumerate(pairs):
                    acc += jax.lax.ppermute(
                        x2, axes, pr).astype(jnp.float32) * weights[i + 1]
                outs.append(acc.astype(g.dtype)[None])
                continue
            xe = x2.astype(jnp.float32) + next(rs)[0]
            if str(wt) == "bfloat16":
                v, s = xe.astype(jnp.bfloat16), None
            else:
                v, s = quantize_pack_2d(xe, block_r=g.block_r,
                                        interpret=interpret)
            deq = _dequant_f32(v, s)
            acc = deq * weights[0]
            for i, pr in enumerate(pairs):
                vn = jax.lax.ppermute(v, axes, pr)
                sn = None if s is None else jax.lax.ppermute(s, axes, pr)
                acc += _dequant_f32(vn, sn) * weights[i + 1]
            outs.append(acc.astype(g.dtype)[None])
            news.append((xe - deq)[None])
        return tuple(outs) + tuple(news)

    n_res = len(res_in)
    out = jax.shard_map(
        f, mesh=mesh, in_specs=in_specs,
        out_specs=tuple(P(spec.worker_axes) for _ in range(n + n_res)),
        axis_names=set(spec.worker_axes), check_vma=not interpret,
    )(*(tuple(bufs) + tuple(res_in)))
    mixed = list(out[:n])
    news = iter(out[n:])
    new_res = [None if r is None else next(news) for r in res_bufs]
    return mixed, new_res


def mix_bus_compressed(params: PyTree, spec, mesh=None, *, wire_dtype,
                       residual: list | None = None,
                       interpret: bool | None = None,
                       block_r: int = DEFAULT_BLOCK_R) -> tuple[PyTree, list | None]:
    """Lossy bulk consensus with error feedback — the compressed DCI lane.

    Computes the same ``P_j ← Σ_i A[i,j]·P_i`` consensus as :func:`mix_bus`,
    but every floating dtype group wider than ``wire_dtype`` rides the wire
    quantized (bf16 cast, or int8 with one fp32 scale per 128-lane bus row
    via the fused quantize-pack kernel). CHOCO-SGD-style error feedback:
    the residual ``r ← (x + r) − dequant(quant(x + r))`` is carried across
    calls, so the quantization error is re-injected instead of lost and the
    consensus mean of the dequantized estimates is preserved (all workers —
    self term included — mix dequantized values).

    Returns ``(mixed_params, new_residual)``. ``residual`` is an opaque
    per-dtype-group buffer list (``None`` on the first call → zeros);
    thread it through successive calls. ``wire_dtype=None`` delegates to
    the exact :func:`mix_bus` bit-identically and passes ``residual``
    through untouched.
    """
    if wire_dtype is None:
        return mix_bus(params, spec, mesh, interpret=interpret,
                       block_r=block_r), residual
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    a0, others = _split_perms(spec)
    weights = jnp.asarray([a0] + [w for w, _ in others], jnp.float32)
    layout = plan_layout(params, lead_ndim=1, block_r=block_r)
    wts = [wire_dtype_for(g.dtype, wire_dtype) for g in layout.groups]
    tel = telemetry.get()
    if tel.active:
        tel.counter("bus.mix_calls")
        # int8 groups ship values + scales: two collectives per permutation
        tel.counter("bus.collectives", len(others) * sum(
            0 if wt is None else (2 if wt == jnp.dtype(jnp.int8) else 1)
            for wt in wts) + len(others) * sum(1 for wt in wts if wt is None))
    if not others:   # degenerate (M == 1): nothing rides the wire
        return params, residual

    with jax.named_scope("pack"):
        bufs = pack(params, layout)
    res_bufs = residual
    if res_bufs is None:
        res_bufs = [None if wt is None else jnp.zeros(b.shape, jnp.float32)
                    for b, wt in zip(bufs, wts)]
    assert len(res_bufs) == len(bufs), "residual does not match the layout"

    mesh = _ambient_mesh(mesh)
    with jax.named_scope("mix"):
        if mesh is not None:
            mixed, new_res = _mix_buffers_sharded_compressed(
                bufs, res_bufs, spec, mesh, weights, others, layout.groups,
                wire_dtype, interpret)
        else:
            mixed, new_res = _mix_buffers_local_compressed(
                bufs, res_bufs, weights, others, layout.groups,
                wire_dtype, interpret)
    with jax.named_scope("unpack"):
        return unpack(mixed, layout), new_res


def mix_and_update_time_varying(params: PyTree, spec, updates: PyTree,
                                step: jax.Array, mesh=None, *,
                                eta: float = -1.0, **kw) -> PyTree:
    """Fused mix+update under 'one_peer_exp' time-varying gossip.

    ``lax.switch`` over the log2(M) one-peer rounds; every branch is the
    fused bus pass for that round's pairwise permutation topology (a single
    bulk collective — degree 1). ``kw`` (incl. ``param_specs``) forwards to
    :func:`mix_bus`."""
    import dataclasses as _dc

    from repro.core.topology import one_peer_exponential

    M = spec.topology.M
    tau = int(np.log2(M))
    assert 1 << tau == M, "one_peer_exp needs M a power of two"
    branches = []
    for k in range(tau):
        sub = _dc.replace(spec, topology=one_peer_exponential(M, k),
                          time_varying=None)
        branches.append(lambda p, u, s=sub: mix_bus(
            p, s, mesh, updates=u, eta=eta, **kw))
    return jax.lax.switch(step % tau, branches, params, updates)
