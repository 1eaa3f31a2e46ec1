"""Shard planning: partition a parameter pytree across S server shards.

A ``ShardPlan`` is a static, deterministic description of which pieces of
which pytree leaves live on which shard.  The plan format (also in
``ps/sharded/README.md``):

  * the pytree is flattened once (``jax.tree_util.tree_flatten`` order is
    the canonical leaf numbering),
  * every leaf is cut into one or more ``LeafSlice``s.  A slice is either
    the *whole* leaf, or a contiguous ``[start, stop)`` range along the
    leaf's **leading axis** (only leaves bigger than the per-shard target
    are split, and scalars / single-row leaves are never split),
  * slices are greedily bin-packed into ``n_shards`` size-balanced
    ``Shard``s: largest piece first, always into the currently lightest
    shard (ties toward the lowest shard index) — the classic LPT
    heuristic, ≤ 4/3·OPT imbalance,
  * within a shard, slices are kept sorted by ``(leaf, start)`` so the
    shard's wire layout is deterministic and reproducible across runs.

The plan is pure metadata: ``split`` / ``assemble`` cut a tree into
per-shard piece lists and join them back (each other's inverse for any
tree matching the plan's structure) — what building the shard stores,
the live reshard and ``snapshot()`` use.

Packed wire format
------------------
Every push and pull speaks the *packed* wire format: the lane-aligned
``(rows, 512)`` buffer is the native representation, not a piece list:
the whole tree lives in ONE flat buffer laid out shard-by-shard (each
shard's slices contiguous in ``(leaf, start)`` order, each shard region
zero-padded to a multiple of 8 rows so a Pallas ``(8, 512)`` tile grid
lands exactly).  Every slice is a contiguous run in both orders, so the
conversion is static slices and one concatenate each way — no index
array, which at published widths would be as large as the parameters
and live on the device beside them:

    ``pack(tree)``      1 concatenate of the slices in wire order,
                        with each shard's zero padding     [jittable]
    ``unpack(wire)``    per-leaf slices of the flat wire (a concatenate
                        only for a leaf split across shards) [jittable]
    ``shard_wire``      a row-slice view — NO per-leaf work at all.

A worker packs its gradients once inside its jitted step; every later
hop (push, per-shard apply, snapshot, pull) stays in wire layout.  The
layout is cached per wire dtype on the plan (``wire_layout``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.perfcount import WIRE
from repro.wireformat import (WIRE_LANES, WIRE_ROWS, pack_flat,
                              resolve_wire_dtype)

Tree = Any


@dataclasses.dataclass(frozen=True)
class LeafSlice:
    """One contiguous piece of one pytree leaf."""

    leaf: int        # index into the canonical flattened-leaf list
    start: int       # leading-axis start row (0 for whole leaves)
    stop: int        # leading-axis stop row (shape[0], or 1 for scalars)
    whole: bool      # the entire leaf (no slicing needed on the wire)
    size: int        # element count of the piece


@dataclasses.dataclass(frozen=True)
class Shard:
    index: int
    slices: Tuple[LeafSlice, ...]
    size: int        # total element count

    def __len__(self) -> int:
        return len(self.slices)


@dataclasses.dataclass(frozen=True)
class WireLayout:
    """Precomputed flat offsets of a plan's packed wire format.

    One layout per wire dtype (cached on the plan).  All fields are
    host-side metadata, so ``pack``/``unpack`` trace to static slices.
    """

    dtype: Any                                # wire buffer dtype
    total_elems: int                          # real elements (no padding)
    total_rows: int                           # wire buffer rows (512 lanes)
    shard_row_start: Tuple[int, ...]          # first wire row of each shard
    shard_rows: Tuple[int, ...]               # rows per shard (8-aligned)
    slice_offsets: Tuple[Tuple[int, ...], ...]  # per shard: element offset
                                              # of each slice in the shard's
                                              # flat region
    leaf_segments: Tuple[Tuple[Tuple[int, int], ...], ...]
                                              # per leaf, in leading-axis
                                              # order: (wire element offset,
                                              # size) of each of its slices

    @property
    def total_wire(self) -> int:
        return self.total_rows * WIRE_LANES


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    n_shards: int
    treedef: Any
    leaf_shapes: Tuple[Tuple[int, ...], ...]
    shards: Tuple[Shard, ...]
    leaf_dtypes: Tuple[Any, ...] = ()
    _wire_layouts: Dict[Any, WireLayout] = dataclasses.field(
        default_factory=dict, compare=False, repr=False)

    # -- data movement -----------------------------------------------------
    def split(self, tree: Tree) -> List[List[jax.Array]]:
        """Cut ``tree`` (params or grads) into per-shard piece lists."""
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != len(self.leaf_shapes):
            raise ValueError(
                f"tree has {len(leaves)} leaves, plan was built for "
                f"{len(self.leaf_shapes)}")
        out: List[List[jax.Array]] = []
        for shard in self.shards:
            pieces = []
            for sl in shard.slices:
                leaf = leaves[sl.leaf]
                pieces.append(leaf if sl.whole else leaf[sl.start:sl.stop])
            out.append(pieces)
        return out

    def shard_pieces(self, tree: Tree, shard: int) -> List[jax.Array]:
        """``split`` restricted to one shard (what a worker pushes to it)."""
        leaves = jax.tree_util.tree_leaves(tree)
        return [leaves[sl.leaf] if sl.whole
                else leaves[sl.leaf][sl.start:sl.stop]
                for sl in self.shards[shard].slices]

    def assemble(self, pieces_per_shard: Sequence[Sequence[jax.Array]]) -> Tree:
        """Inverse of ``split``: rebuild the full pytree from shard pieces."""
        parts: Dict[int, Dict[int, jax.Array]] = {}
        for shard, pieces in zip(self.shards, pieces_per_shard):
            if len(pieces) != len(shard.slices):
                raise ValueError(
                    f"shard {shard.index}: got {len(pieces)} pieces, "
                    f"plan has {len(shard.slices)} slices")
            for sl, piece in zip(shard.slices, pieces):
                parts.setdefault(sl.leaf, {})[sl.start] = piece
        leaves = []
        for i, shape in enumerate(self.leaf_shapes):
            by_start = parts.get(i)
            if by_start is None:
                raise ValueError(f"leaf {i} missing from shard pieces")
            if len(by_start) == 1:
                (leaf,) = by_start.values()
            else:
                WIRE.leaf_concats += 1
                leaf = jnp.concatenate(
                    [by_start[s] for s in sorted(by_start)], axis=0)
            leaves.append(leaf)
        return self.treedef.unflatten(leaves)

    # -- packed wire format --------------------------------------------------
    def piece_shape(self, sl: LeafSlice) -> Tuple[int, ...]:
        """Array shape of one slice as it travels on the wire."""
        shape = self.leaf_shapes[sl.leaf]
        if sl.whole:
            return shape
        return (sl.stop - sl.start,) + shape[1:]

    def _resolve_wire_dtype(self, dtype) -> Any:
        """None -> the shared ``repro.wireformat`` rule: a uniform tree
        keeps its dtype on the wire, mixed trees promote to f32."""
        if dtype is not None:
            return jnp.dtype(dtype)
        return resolve_wire_dtype((jnp.dtype(d) for d in self.leaf_dtypes),
                                  default=jnp.dtype(jnp.float32))

    def wire_layout(self, dtype=None) -> WireLayout:
        """The (cached) packed layout for one wire dtype."""
        wdt = self._resolve_wire_dtype(dtype)
        layout = self._wire_layouts.get(wdt)
        if layout is None:
            layout = self._build_wire_layout(wdt)
            self._wire_layouts[wdt] = layout
        return layout

    def _build_wire_layout(self, wdt) -> WireLayout:
        total = sum(math.prod(s) if s else 1 for s in self.leaf_shapes)

        def shard_region_rows(n_elems: int) -> int:
            if n_elems == 0:
                return 0
            raw = -(-n_elems // WIRE_LANES)              # ceil to full lanes
            return -(-raw // WIRE_ROWS) * WIRE_ROWS      # ceil to 8-row tiles

        rows = tuple(shard_region_rows(s.size) for s in self.shards)
        row_start = tuple(int(x) for x in
                          np.concatenate([[0], np.cumsum(rows)])[:-1])
        total_rows = int(sum(rows))
        segments: List[List[Tuple[int, int, int]]] = [
            [] for _ in self.leaf_shapes]
        slice_offsets: List[Tuple[int, ...]] = []
        for j, shard in enumerate(self.shards):
            base = row_start[j] * WIRE_LANES
            off = 0
            offs = []
            for sl in shard.slices:
                segments[sl.leaf].append((sl.start, base + off, sl.size))
                offs.append(off)
                off += sl.size
            slice_offsets.append(tuple(offs))
        return WireLayout(dtype=wdt, total_elems=total,
                          total_rows=total_rows,
                          shard_row_start=row_start, shard_rows=rows,
                          slice_offsets=tuple(slice_offsets),
                          leaf_segments=tuple(
                              tuple((w, n) for _, w, n in sorted(segs))
                              for segs in segments))

    def pack(self, tree: Tree, dtype=None) -> jax.Array:
        """Tree -> one (total_rows, 512) wire buffer.

        The slices in wire order plus each shard region's zero padding,
        concatenated once.  Jittable; inside a jit the whole thing fuses
        into a single pass over the data.
        """
        layout = self.wire_layout(dtype)
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != len(self.leaf_shapes):
            raise ValueError(
                f"tree has {len(leaves)} leaves, plan was built for "
                f"{len(self.leaf_shapes)}")
        for i, (x, shape) in enumerate(zip(leaves, self.leaf_shapes)):
            # Size mismatches must not reach the gather: jnp.take's
            # default clip mode would silently clamp out-of-range
            # indices into a well-shaped but garbage wire buffer.
            if tuple(x.shape) != shape:
                raise ValueError(f"leaf {i}: shape {tuple(x.shape)} does "
                                 f"not match plan shape {shape}")
        WIRE.packs += 1
        if len(leaves) > 1:
            WIRE.leaf_concats += 1
        flats = [x.reshape(-1).astype(layout.dtype) for x in leaves]
        parts = []
        for shard, rows in zip(self.shards, layout.shard_rows):
            for sl in shard.slices:
                flat = flats[sl.leaf]
                if sl.whole:
                    parts.append(flat)
                else:  # a leading-axis range: contiguous in row-major
                    first = sl.start * math.prod(self.leaf_shapes[sl.leaf][1:])
                    parts.append(flat[first:first + sl.size])
            pad = rows * WIRE_LANES - shard.size
            if pad:
                parts.append(jnp.zeros((pad,), layout.dtype))
        return jnp.concatenate(parts).reshape(layout.total_rows, WIRE_LANES)

    def unpack(self, wire: jax.Array, dtype=None) -> Tree:
        """Inverse of ``pack``: per-leaf slices of the flat wire."""
        layout = self.wire_layout(dtype)
        if wire.shape != (layout.total_rows, WIRE_LANES):
            raise ValueError(
                f"wire buffer {wire.shape} does not match layout "
                f"({layout.total_rows}, {WIRE_LANES})")
        WIRE.unpacks += 1
        flat = wire.reshape(-1)
        leaves = []
        dtypes = self.leaf_dtypes or (jnp.float32,) * len(self.leaf_shapes)
        for shape, dt, segs in zip(self.leaf_shapes, dtypes,
                                   layout.leaf_segments):
            pieces = [flat[w:w + n] for w, n in segs]
            leaf = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
            leaves.append(leaf.reshape(shape).astype(dt))
        return self.treedef.unflatten(leaves)

    def shard_wire(self, wire: jax.Array, shard: int, dtype=None) -> jax.Array:
        """Shard ``shard``'s (rows, 512) region — a pure row-slice view."""
        layout = self.wire_layout(dtype)
        start = layout.shard_row_start[shard]
        return wire[start:start + layout.shard_rows[shard]]

    def shard_wires(self, wire: jax.Array, dtype=None) -> List[jax.Array]:
        return [self.shard_wire(wire, j, dtype) for j in range(self.n_shards)]

    def split_packed(self, tree: Tree, dtype=None) -> List[jax.Array]:
        """``pack`` + per-shard views: the packed analogue of ``split``."""
        return self.shard_wires(self.pack(tree, dtype), dtype)

    def assemble_packed(self, shard_bufs: Sequence[jax.Array],
                        dtype=None) -> Tree:
        """Inverse of ``split_packed``: concat shard regions + ``unpack``."""
        layout = self.wire_layout(dtype)
        for j, buf in enumerate(shard_bufs):
            if buf.shape != (layout.shard_rows[j], WIRE_LANES):
                raise ValueError(
                    f"shard {j}: buffer {buf.shape} does not match layout "
                    f"({layout.shard_rows[j]}, {WIRE_LANES})")
        bufs = [b for b in shard_bufs if b.shape[0]]
        wire = bufs[0] if len(bufs) == 1 else jnp.concatenate(bufs)
        return self.unpack(wire, dtype)

    def shard_pieces_from_wire(self, buf: jax.Array, shard: int,
                               dtype=None) -> List[jax.Array]:
        """One shard's piece list (the ``split`` format) out of its packed
        region — per-slice views, no concatenation."""
        layout = self.wire_layout(dtype)
        WIRE.unpacks += 1
        flat = buf.reshape(-1)
        dtypes = self.leaf_dtypes or (jnp.float32,) * len(self.leaf_shapes)
        out = []
        for sl, off in zip(self.shards[shard].slices,
                           layout.slice_offsets[shard]):
            shape = self.piece_shape(sl)
            out.append(flat[off:off + sl.size].reshape(shape)
                       .astype(dtypes[sl.leaf]))
        return out

    def pack_shard_pieces(self, pieces: Sequence[jax.Array], shard: int,
                          dtype=None) -> jax.Array:
        """One shard's piece list -> its (rows, 512) packed region."""
        layout = self.wire_layout(dtype)
        rows = layout.shard_rows[shard]
        if not pieces:
            return jnp.zeros((rows, WIRE_LANES), layout.dtype)
        return pack_flat(pieces, layout.dtype, rows=rows)

    def rebuild(self, n_shards: int, *,
                split_oversized: bool = True) -> "ShardPlan":
        """The SAME tree re-planned at a new arity — metadata only.

        ``build_shard_plan`` touches nothing but ``.shape``/``.dtype``,
        so a tree of ``jax.ShapeDtypeStruct``s suffices: a live reshard
        (``repro.ft.reshard``) re-plans without materializing params.
        Note the per-shard size target depends on ``n_shards``, so the
        new plan may slice leaves differently — the migration map, not
        slice identity, is what relates the two layouts.
        """
        dtypes = self.leaf_dtypes or (jnp.float32,) * len(self.leaf_shapes)
        structs = [jax.ShapeDtypeStruct(s, d)
                   for s, d in zip(self.leaf_shapes, dtypes)]
        tree = jax.tree_util.tree_unflatten(self.treedef, structs)
        return build_shard_plan(tree, n_shards,
                                split_oversized=split_oversized)

    # -- introspection -------------------------------------------------------
    @property
    def total_size(self) -> int:
        return sum(s.size for s in self.shards)

    def imbalance(self) -> float:
        """max shard size / mean shard size (1.0 = perfectly balanced)."""
        sizes = [s.size for s in self.shards]
        mean = sum(sizes) / len(sizes)
        return max(sizes) / mean if mean else 1.0

    def describe(self) -> str:
        lines = [f"ShardPlan: {self.n_shards} shards, "
                 f"{len(self.leaf_shapes)} leaves, "
                 f"{self.total_size:,} elements, "
                 f"imbalance {self.imbalance():.3f}"]
        for s in self.shards:
            split = sum(1 for sl in s.slices if not sl.whole)
            lines.append(f"  shard {s.index}: {s.size:,} elements in "
                         f"{len(s.slices)} pieces ({split} split)")
        return "\n".join(lines)


def _leaf_pieces(leaf_idx: int, shape: Tuple[int, ...], target: int,
                 split_oversized: bool) -> List[LeafSlice]:
    size = math.prod(shape) if shape else 1
    lead = shape[0] if shape else 1
    row = size // lead if lead else size
    can_split = (split_oversized and len(shape) >= 1 and lead > 1
                 and size > target and row > 0)
    if not can_split:
        return [LeafSlice(leaf_idx, 0, lead, whole=True, size=size)]
    rows_per_piece = max(1, target // row)
    pieces = []
    for start in range(0, lead, rows_per_piece):
        stop = min(lead, start + rows_per_piece)
        pieces.append(LeafSlice(leaf_idx, start, stop,
                                whole=False, size=(stop - start) * row))
    return pieces


def build_shard_plan(tree: Tree, n_shards: int, *,
                     split_oversized: bool = True) -> ShardPlan:
    """Greedy LPT bin-packing of pytree leaves into size-balanced shards."""
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        raise ValueError("cannot shard an empty pytree")
    shapes = tuple(tuple(x.shape) for x in leaves)
    total = sum(math.prod(s) if s else 1 for s in shapes)
    target = max(1, -(-total // n_shards))  # ceil

    pieces: List[LeafSlice] = []
    for i, shape in enumerate(shapes):
        pieces.extend(_leaf_pieces(i, shape, target, split_oversized))

    # Largest-first into the lightest shard; deterministic tie-breaks.
    pieces.sort(key=lambda sl: (-sl.size, sl.leaf, sl.start))
    bins: List[List[LeafSlice]] = [[] for _ in range(n_shards)]
    sizes = [0] * n_shards
    for sl in pieces:
        j = min(range(n_shards), key=lambda k: (sizes[k], k))
        bins[j].append(sl)
        sizes[j] += sl.size

    shards = tuple(
        Shard(index=j,
              slices=tuple(sorted(bins[j], key=lambda sl: (sl.leaf, sl.start))),
              size=sizes[j])
        for j in range(n_shards))
    return ShardPlan(n_shards=n_shards, treedef=treedef,
                     leaf_shapes=shapes, shards=shards,
                     leaf_dtypes=tuple(jnp.dtype(x.dtype) for x in leaves))
