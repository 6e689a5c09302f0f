"""Paged weights (paper Appendix A.1, Fig. 11).

Layer weights are chunked into fixed-size *pages*; a page table maps
(layer, leaf) → page span.  The serving engine keeps a 2×W_L double
buffer: while layer i computes out of buffer (i % 2), the pages of layer
i+1 stream into buffer ((i+1) % 2), interleaved with hidden-state
transfers per CGOPipe.  On TPU the backing store lives in host memory
(``memory_kind='pinned_host'``) and pages move with device_put; on the
CPU-only validation platform the same code paths run with plain arrays.

The page pool layout is (num_pages, page_elems) so a layer fetch is a
single contiguous gather — the TPU analogue of the paper's paged
cudaMemcpyAsync batches, and the unit the Pallas MoE-FFN kernel's page
table indexes into.

Two manifest granularities:

  * whole-layer (``pack_layer_stack`` / ``pack_block_groups``): one flat
    span per layer; every page streams every layer — the paper's baseline
    layout, kept as the reference path;
  * split (``pack_layer_stack_split`` / ``pack_block_groups_split``): each
    layer's manifest is divided into a *shared* span (attention / norm /
    router / shared-expert leaves, streamed every layer as before) and
    per-(layer, expert) spans for the routed expert weights.  Top-k
    routing touches only a fraction of the experts, so the serving
    engine reads just the activated experts' spans (core.residency keeps
    the popular ones device-resident) instead of the full E-expert block.
    An expert span is not paged: it is one row of (d_model, d_ff) blocks
    (``ExpertManifest``) that the expert's FFN reads where it lies.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class LeafEntry:
    """Where one leaf lies in a span.  In a layer span (``PageManifest``)
    ``offset`` counts elements of the flat span; in an expert span
    (``ExpertManifest``) it counts (d_model, d_ff) blocks."""
    path: Tuple[str, ...]
    shape: Tuple[int, ...]       # per-layer shape (stack dim removed)
    dtype: str
    offset: int                  # where the leaf starts (see above)
    perm: Tuple[int, ...] = ()   # axis order the span stores it in (() = as is)

    def restore(self, flat, lead: Tuple[int, ...] = ()):
        """(*lead, n) slice of a span holding this leaf in its storage
        order (flat, or as blocks) -> (*lead, *shape)."""
        if not self.perm:
            return flat.reshape(lead + self.shape)
        x = flat.reshape(lead + tuple(self.shape[i] for i in self.perm))
        k = len(lead)
        inv = [int(i) for i in np.argsort(self.perm)]
        return jnp.transpose(x, tuple(range(k)) + tuple(k + i for i in inv))


def _storage_perm(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """Axis order a leaf is stored in inside its span: axes shorter than a
    sublane tile (8) first, the rest in order.  Rebuilding the leaf is then
    a reshape into tile-sized trailing dims plus a transpose; a reshape
    that lands a size-2 axis in a tiled position (the (D, 2, F) gate/up
    layout) made the TPU compiler stall for minutes on a Mixtral-width
    expert span."""
    order = sorted(range(len(shape)), key=lambda i: shape[i] >= 8)
    return tuple(order) if order != list(range(len(shape))) else ()


@dataclass
class PageManifest:
    page_elems: int
    layer_elems: int             # padded flat elements per layer
    pages_per_layer: int
    num_layers: int
    leaves: List[LeafEntry]
    dtype: str

    def layer_pages(self, layer: int) -> np.ndarray:
        start = layer * self.pages_per_layer
        return np.arange(start, start + self.pages_per_layer)


def _flatten_with_paths(tree, prefix=()):
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out += _flatten_with_paths(tree[k], prefix + (k,))
        return out
    return [(prefix, tree)]


def _span_pages(elems: int, page_elems: int, dtype) -> int:
    """Pages per span: enough for ``elems``, rounded up to a whole TPU
    sublane tile of rows (8 × 32-bit words: 8 f32, 16 bf16, 32 int8
    pages) so the store keeps its row-major device layout and a span is
    a slice of the most major axis only — the one cut a host→device copy
    may make (a span count off the tile makes the TPU compiler re-lay the
    store out with the span axis tiled, and the host slice then fails)."""
    tile = 8 * max(1, 4 // np.dtype(dtype).itemsize)
    return -(-math.ceil(elems / page_elems) // tile) * tile


def _pack_pages(leaves, *, perms, lead: int, elems: int, page_elems: int,
                dtype) -> np.ndarray:
    """Flatten every leaf behind its first ``lead`` dims (its per-layer
    axes in ``perms`` storage order), concatenate, zero-pad to ``elems``
    and cut into pages: (*lead_dims, pages, page_elems).  Built in host
    memory with numpy — the packed store is the host tier anyway, so
    packing never holds a second copy of the weights on device (and a
    device relayout of a gigabyte-sized (D, 2, F) leaf is a program the
    TPU compiler takes minutes over)."""
    shape = tuple(leaves[0].shape[:lead])
    out = np.zeros(shape + (elems,), dtype)
    off = 0
    for leaf, perm in zip(leaves, perms):
        x = np.asarray(leaf)
        if perm:
            x = x.transpose(tuple(range(lead)) + tuple(lead + i for i in perm))
        n = int(np.prod(x.shape[lead:], dtype=np.int64))
        out[..., off:off + n] = x.reshape(shape + (n,))
        off += n
    return out.reshape(shape + (elems // page_elems, page_elems))


def pack_layer_stack(stacked: Dict, page_elems: int = 1 << 20
                     ) -> Tuple[jax.Array, PageManifest]:
    """stacked: pytree whose every leaf has a leading `layers` dim L.
    Returns (pages (P, page_elems), manifest)."""
    leaves = _flatten_with_paths(stacked)
    L = leaves[0][1].shape[0]
    dtype = leaves[0][1].dtype
    entries: List[LeafEntry] = []
    offset = 0
    for path, leaf in leaves:
        assert leaf.shape[0] == L, f"stack dim mismatch at {path}"
        per_layer = int(np.prod(leaf.shape[1:])) if leaf.ndim > 1 else 1
        entries.append(LeafEntry(path, tuple(leaf.shape[1:]), str(leaf.dtype),
                                 offset, _storage_perm(leaf.shape[1:])))
        offset += per_layer
    pages_per_layer = _span_pages(offset, page_elems, dtype)
    layer_elems = pages_per_layer * page_elems

    pages = _pack_pages([leaf for _, leaf in leaves],
                        perms=tuple(e.perm for e in entries), lead=1,
                        elems=layer_elems, page_elems=page_elems,
                        dtype=np.dtype(dtype)
                        ).reshape(L * pages_per_layer, page_elems)
    manifest = PageManifest(page_elems, layer_elems, pages_per_layer, L,
                            entries, str(dtype))
    return pages, manifest


def fetch_layer(pages: jax.Array, manifest: PageManifest, layer) -> Dict:
    """Gather one layer's pages and rebuild its parameter pytree.
    `layer` may be a traced index (used inside lax.scan/fori loops)."""
    start = layer * manifest.pages_per_layer
    span = jax.lax.dynamic_slice_in_dim(pages, start,
                                        manifest.pages_per_layer, axis=0)
    flat = span.reshape(-1)
    out: Dict = {}
    for e in manifest.leaves:
        n = int(np.prod(e.shape)) if e.shape else 1
        leaf = jax.lax.dynamic_slice_in_dim(flat, e.offset, n, axis=0)
        leaf = e.restore(leaf) if e.shape else leaf[0]
        node = out
        for p in e.path[:-1]:
            node = node.setdefault(p, {})
        node[e.path[-1]] = leaf
    return out


def fetch_pages(pages: jax.Array, page_ids) -> jax.Array:
    return pages[jnp.asarray(page_ids)]


# ---------------------------------------------------------------------------
# Split manifests: shared span + per-(layer, expert) spans
# ---------------------------------------------------------------------------

# Routed-expert leaves inside a "moe" subtree (shared experts stay in the
# shared span — they run for every token, so streaming them per layer is
# already optimal).  The int8 dequant scales (wi_scale/wo_scale) also stay
# in the shared span: they are 4 bytes per expert, and the expert store
# is packed at the expert-weight dtype, which would truncate float32
# scales.  moe_paged reads them per activated expert from the shared
# params instead.
EXPERT_LEAF_NAMES = ("wi", "wo")

# Axis order each routed-expert leaf is stored in: d_ff minor, d_model
# second-minor.  The gate/up leaf (D, 2, F) becomes two (D, F) blocks,
# the down projection (F, D) one transposed (D, F) block, so every block
# of a span shares one tiled layout and each is a slice of the span's
# most major axis: the FFN's dots read the blocks in place.
EXPERT_STORAGE_PERM = {"wi": (1, 0, 2), "wo": (1, 0)}


def _is_expert_leaf(path: Tuple[str, ...]) -> bool:
    return ("moe" in path and "shared" not in path
            and path[-1] in EXPERT_LEAF_NAMES)


def _tree_from_leaves(leaves):
    out: Dict = {}
    for path, leaf in leaves:
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return out


@dataclass
class ExpertManifest:
    """Per-(layer, expert) spans for one stacked layer group.  The span
    unit is ONE expert's weights in ONE layer — the granularity the
    residency cache pins/evicts and the router-gated fetch reads — stored
    as ``span_shape`` = (blocks, d_model, d_ff): the gate and up halves
    of ``wi``, then ``wo`` transposed (``EXPERT_STORAGE_PERM``).  A span
    is one row of the host store (one host→device copy carries the whole
    expert) and one slot of the device pool; ``leaves`` give each leaf's
    first block."""
    num_layers: int
    num_experts: int
    block_shape: Tuple[int, int]   # (d_model, d_ff)
    blocks: int                    # blocks per span
    leaves: List[LeafEntry]        # paths relative to the moe subtree
    dtype: str

    @property
    def span_shape(self) -> Tuple[int, int, int]:
        return (self.blocks,) + tuple(self.block_shape)

    @property
    def span_bytes(self) -> int:
        """Bytes one expert span moves (what a host→device copy costs)."""
        return int(np.prod(self.span_shape)) * np.dtype(self.dtype).itemsize

    def leaf_size(self, e: LeafEntry) -> int:
        """Blocks leaf ``e`` takes in a span."""
        return int(np.prod(e.shape)) // int(np.prod(self.block_shape))

    def leaf_blocks(self, block) -> Dict[str, Tuple]:
        """One span's leaves in their storage layout, by leaf name, each
        the tuple of its blocks: {"wi": (gate, up), "wo": (wo
        transposed,)}.  ``block(i)`` reads the span's i-th block where it
        lies, so the caller decides where the blocks come from and no
        leaf is cut out of the span as a whole."""
        return {e.path[-1]: tuple(block(e.offset + j)
                                  for j in range(self.leaf_size(e)))
                for e in self.leaves}


@dataclass
class SplitManifest:
    shared: PageManifest
    experts: Optional[ExpertManifest]


def expert_manifest(expert_leaves) -> ExpertManifest:
    """The manifest ``pack_expert_stack`` packs by, from the leaves'
    shapes and dtypes alone (arrays or ``jax.ShapeDtypeStruct``s).  Leaf
    paths are stored relative to the ``moe`` subtree so a span unflattens
    straight into the MoE param dict."""
    L, NE = expert_leaves[0][1].shape[:2]
    dtype = expert_leaves[0][1].dtype
    entries: List[LeafEntry] = []
    block_shape, offset = None, 0
    for path, leaf in expert_leaves:
        assert leaf.shape[:2] == (L, NE), f"expert stack mismatch at {path}"
        rel = path[path.index("moe") + 1:]
        shape = tuple(leaf.shape[2:])
        perm = EXPERT_STORAGE_PERM[path[-1]]
        stored = tuple(shape[i] for i in perm)
        block_shape = block_shape or stored[-2:]
        assert stored[-2:] == block_shape, f"{path} stores as {stored}"
        entries.append(LeafEntry(rel, shape, str(leaf.dtype), offset, perm))
        offset += int(np.prod(stored[:-2], dtype=np.int64))
    return ExpertManifest(L, NE, block_shape, offset, entries, str(dtype))


def _copy_leaf(dst: np.ndarray, src: np.ndarray, tile: int = 256):
    """dst[...] = src.  A source whose minor axis is strided (a leaf read
    transposed) is copied a tile of the two minor axes at a time: copied
    whole, a Mixtral-width (F, D) leaf runs several times slower than
    tiles that stay in cache."""
    if src.strides[-1] == src.itemsize:
        dst[...] = src
        return
    rows, cols = dst.shape[-2:]
    for i in range(0, rows, tile):
        for j in range(0, cols, tile):
            dst[..., i:i + tile, j:j + tile] = src[..., i:i + tile, j:j + tile]


def pack_expert_stack(expert_leaves) -> Tuple[np.ndarray, ExpertManifest]:
    """expert_leaves: [(path, arr (L, E, ...))].  Returns
    (store (L, E, *span_shape), manifest), built in host memory with
    numpy like ``_pack_pages``."""
    em = expert_manifest(expert_leaves)
    out = np.empty((em.num_layers, em.num_experts) + em.span_shape,
                   np.dtype(em.dtype))
    for (_, leaf), e in zip(expert_leaves, em.leaves):
        x = np.asarray(leaf).transpose((0, 1) + tuple(2 + i for i in e.perm))
        k = int(np.prod(x.shape[2:-2], dtype=np.int64))
        _copy_leaf(out[:, :, e.offset:e.offset + k],
                    x.reshape(x.shape[:2] + (k,) + x.shape[-2:]))
    return out, em


def unflatten_expert_span(span: jax.Array, em: ExpertManifest) -> Dict:
    """Rebuild expert params from spans with arbitrary leading batch dims:
    span (..., *span_shape) -> {leaf: (..., *leaf_shape)}, the leaves
    transposed back from their storage order.  The serving path reads
    spans in place and never calls this; only the stacked-subset compute
    (``moe._grouped_subset``, the Pallas ``moe_ffn`` kernel), which wants
    the (A, D, 2, F) / (A, F, D) model layout, does."""
    lead = span.shape[:-3]
    out: Dict = {}
    for e in em.leaves:
        k = em.leaf_size(e)
        leaf = e.restore(span[..., e.offset:e.offset + k, :, :], lead)
        node = out
        for p in e.path[:-1]:
            node = node.setdefault(p, {})
        node[e.path[-1]] = leaf
    return out


def pack_layer_stack_split(stacked: Dict, page_elems: int = 1 << 20
                           ) -> Tuple[jax.Array, Optional[jax.Array],
                                      SplitManifest]:
    """Split one stacked layer group into a shared span (everything that
    streams every layer: attention, norms, router, shared experts) and
    per-(layer, expert) spans for the routed expert weights.

    Returns (shared_pages (L*ppl, page_elems),
             expert store (L, E, *ExpertManifest.span_shape) or None,
             SplitManifest)."""
    leaves = _flatten_with_paths(stacked)
    expert_leaves = [(p, l) for p, l in leaves if _is_expert_leaf(p)]
    shared_leaves = [(p, l) for p, l in leaves if not _is_expert_leaf(p)]
    shared_pages, shared_manifest = pack_layer_stack(
        _tree_from_leaves(shared_leaves), page_elems)
    if not expert_leaves:
        return shared_pages, None, SplitManifest(shared_manifest, None)
    expert_pages, em = pack_expert_stack(expert_leaves)
    return shared_pages, expert_pages, SplitManifest(shared_manifest, em)


@dataclass
class PagedWeights:
    """Engine-facing bundle for split (expert-granular) paging: per-group
    shared spans shaped for the layer scan, plus the per-(layer, expert)
    span stores and manifests for every MoE group.  Groups without routed
    experts appear only in ``pages``/``manifests`` (identical to the
    whole-layer path)."""
    pages: Dict[str, jax.Array]              # key -> (L, ppl, page_elems)
    manifests: Dict[str, PageManifest]
    expert_pages: Dict[str, jax.Array]       # key -> (L, E, *span_shape)
    expert_manifests: Dict[str, ExpertManifest]

    def shared_layer_bytes(self, key: str) -> int:
        m = self.manifests[key]
        return (m.pages_per_layer * m.page_elems
                * np.dtype(m.dtype).itemsize)


def pack_block_groups_split(blocks: Dict, page_elems: int = 1 << 20
                            ) -> PagedWeights:
    """Split-pack every period-position group of a model's stacked block
    params (the expert-granular analogue of ``pack_block_groups``).

    The packed pools are the engine's *host-side* weight store: they are
    placed in pinned host memory when the backend exposes the space
    (core.offload), so the transfer_plan/window_plan slices the serving
    scan consumes — and the router-gated expert-span gathers — lower to
    async pinned-DMA copies instead of pageable-rate transfers."""
    from repro.core import offload
    pages, manifests, epages, emanifests = {}, {}, {}, {}
    for key, group in blocks.items():
        shared, experts, sm = pack_layer_stack_split(group, page_elems)
        L = sm.shared.num_layers
        pages[key] = offload.pinned_put(
            shared.reshape(L, sm.shared.pages_per_layer,
                           sm.shared.page_elems))
        manifests[key] = sm.shared
        if experts is not None:
            epages[key] = offload.pinned_put(experts)
            emanifests[key] = sm.experts
    return PagedWeights(pages, manifests, epages, emanifests)


def page_stores(paged) -> Dict:
    """The arrays of a packed layout (``PagedWeights`` or the whole-layer
    ``(pages, manifests)`` pair): what a jitted step takes as an argument.
    A store closed over by the step instead would be baked into the
    program as a constant, off its pinned_host placement."""
    if isinstance(paged, PagedWeights):
        return {"pages": paged.pages, "expert_pages": paged.expert_pages}
    return paged[0]


def bind_page_stores(paged, stores):
    """``paged`` with its arrays replaced by ``stores`` (the step's traced
    argument, as laid out by ``page_stores``); the manifests stay."""
    if isinstance(paged, PagedWeights):
        return dataclasses.replace(paged, pages=stores["pages"],
                                   expert_pages=stores["expert_pages"])
    return stores, paged[1]


def unflatten_span(span: jax.Array, manifest: PageManifest) -> Dict:
    """Rebuild one layer's parameter pytree from its page span
    (pages_per_layer, page_elems) — static offsets, reshape-only (used
    inside lax.scan where the span arrives as a scan slice)."""
    flat = span.reshape(-1)
    out: Dict = {}
    for e in manifest.leaves:
        n = int(np.prod(e.shape)) if e.shape else 1
        leaf = flat[e.offset:e.offset + n]
        leaf = e.restore(leaf) if e.shape else leaf[0]
        node = out
        for p in e.path[:-1]:
            node = node.setdefault(p, {})
        node[e.path[-1]] = leaf
    return out


def pack_block_groups(blocks: Dict, page_elems: int = 1 << 20):
    """Pack every period-position group ('p0', 'p1', ...) of a model's
    stacked block params into page pools.  Returns (pages_dict, manifests):
    pages_dict[key] has shape (L, pages_per_layer, page_elems) — sliceable
    by the layer scan — and manifests[key] rebuilds the layer pytree."""
    from repro.core import offload
    pages_dict, manifests = {}, {}
    for key, group in blocks.items():
        pages, manifest = pack_layer_stack(group, page_elems)
        L = manifest.num_layers
        # host-side page store: pinned placement when available, so the
        # in-scan page consumption streams at pinned-DMA rate
        pages_dict[key] = offload.pinned_put(
            pages.reshape(L, manifest.pages_per_layer, manifest.page_elems))
        manifests[key] = manifest
    return pages_dict, manifests


# ---------------------------------------------------------------------------
# Transfer scheduling (which page moves during which micro-batch)
# ---------------------------------------------------------------------------

def transfer_plan(pages_per_layer: int, n_ubs: int) -> List[List[int]]:
    """Split a layer's pages into n_ubs groups; group j is transferred
    while micro-batch j computes (CGOPipe interleaving: the small, urgent
    hidden-state transfer for ub j+1 slots between groups)."""
    groups: List[List[int]] = [[] for _ in range(n_ubs)]
    for p in range(pages_per_layer):
        groups[p * n_ubs // pages_per_layer].append(p)
    return groups


def window_plan(n_items: int, n_ubs: int,
                positions: Sequence[int]) -> List[int]:
    """Module-batched drain schedule: the union of the transfer_plan
    groups for every rotation position in one accumulation window —
    prefetch admitted during a window may drain through all of the
    window's interleave slots, not just one group's.  `positions` are
    rotation indices (taken mod n_ubs); returns sorted item ids."""
    plan = transfer_plan(n_items, n_ubs)
    return sorted({i for p in positions for i in plan[p % n_ubs]})


def predicted_drain_order(pairs: Sequence[Tuple[int, int]],
                          scores: Sequence[float]) -> List[int]:
    """Earliest-deadline-first enqueue order for gate-predicted expert
    spans: a span predicted for layer l is only useful if it lands
    before the scan's layer-l step consumes it, so shallow layers
    enqueue first (ties broken toward higher predicted probability).
    The engine feeds the ordered entries into the same pending queue the
    router-ahead prefetch drains through ``transfer_plan`` slices — the
    slices interleave the H2D work between the rotation's compute steps,
    and deadline order maximizes the spans that complete before their
    consuming layer.  Returns indices into ``pairs``."""
    return sorted(range(len(pairs)),
                  key=lambda i: (pairs[i][0], -scores[i], pairs[i][1]))


@dataclass
class DoubleBuffer:
    """The 2×W_L weight buffer of Appendix A.1 (logical model; the JAX
    engine realizes it as two donated page buffers)."""
    n_slots: int = 2
    resident: List[int] = field(default_factory=lambda: [-1, -1])

    def slot_for(self, layer: int) -> int:
        return layer % self.n_slots

    def load(self, layer: int) -> int:
        s = self.slot_for(layer)
        self.resident[s] = layer
        return s

    def is_resident(self, layer: int) -> bool:
        return self.resident[self.slot_for(layer)] == layer
