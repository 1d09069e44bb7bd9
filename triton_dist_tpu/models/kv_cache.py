"""KV cache (reference ``python/triton_dist/models/kv_cache.py:29``).

The reference keeps a preallocated per-layer (B, Hkv, S_max, D) cache with an
offset bumped per decode step (CUDA-graph-safe). The TPU analog is identical
in spirit: fixed-shape arrays + an int32 ``lengths`` vector, functionally
updated (donated through jit so XLA updates in place).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class KVCache:
    """Host-side handle: stacked per-layer caches (L, B, Hkv_local, S, D)."""

    k: jax.Array
    v: jax.Array
    lengths: jax.Array  # (B,) int32

    @property
    def max_len(self) -> int:
        return self.k.shape[3]

    def inc_offset(self, n: int = 1, active: jax.Array | None = None) -> "KVCache":
        """Reference ``kv_cache.inc_offset`` (``engine.py:170``).

        With ``active`` — a (B,) bool/int mask — only active slots advance
        (``lengths + n·active``): a finished or padded slot must not grow
        past its real content, or the next tenant of the slot inherits a
        phantom prefix (the serving layer's slot reuse depends on this)."""
        if active is None:
            return dataclasses.replace(self, lengths=self.lengths + n)
        step = jnp.asarray(active).astype(self.lengths.dtype) * n
        return dataclasses.replace(self, lengths=self.lengths + step)


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v", "lengths"], meta_fields=[]
)


NULL_BLOCK = 0  # reserved: never allocated, masked/garbage writes land here


@dataclasses.dataclass(frozen=True)
class CacheRow:
    """One kind of cache row a model keeps a token: its name, how many
    layers keep it, and its (heads, width). A model declares a pair of them
    (``cache_rows()``): ``DenseLLM`` its K and V rows, alike; a latent
    model one latent row for all heads on every layer and an index key on
    the layers that own an indexer. The pool manager holds one pool a kind
    under ONE block table, and a block's price is the sum over the kinds."""

    kind: str
    layers: int
    heads: int
    width: int


def kv_rows(num_layers: int, num_kv_heads: int, head_dim: int) -> tuple:
    """K rows and V rows, alike, on every layer: what a model with per-head
    keys and values declares."""
    return (CacheRow("k", num_layers, num_kv_heads, head_dim),
            CacheRow("v", num_layers, num_kv_heads, head_dim))


class BlockAllocator:
    """Host-side free-list + refcount bookkeeping for a paged KV pool.

    The device never sees this object — it only sees the int32 block
    tables the serving layer builds from the chains handed out here.
    Block 0 is the NULL block: it is never allocated, so table rows can
    point masked or out-of-range writes at it without corrupting a
    tenant.

    Refcounts make prefix sharing safe: a block chain owned by the radix
    index and referenced by N running slots has refcount N+1; ``free``
    only returns a block to the free list when the count hits zero, and
    ``ensure_exclusive`` is the copy-on-write primitive (returns a fresh
    block when the caller does not hold the only reference).
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved null)")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))  # pop() yields 1,2,…
        self._ref = {}  # block -> refcount (absent = free)

    # -- queries ----------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    @property
    def num_shared(self) -> int:
        """Blocks held by more than one reference."""
        return sum(1 for c in self._ref.values() if c > 1)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    # -- lifecycle --------------------------------------------------------
    def alloc(self, n: int) -> list[int] | None:
        """Allocate ``n`` blocks at refcount 1, or None (all-or-nothing)."""
        if n < 0 or n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._ref[b] = 1
        return blocks

    def incref(self, blocks: list[int]) -> None:
        for b in blocks:
            if b == NULL_BLOCK or b not in self._ref:
                raise ValueError(f"incref of unallocated block {b}")
            self._ref[b] += 1

    def free(self, blocks: list[int]) -> None:
        """Drop one reference per block; recycle those that hit zero."""
        for b in blocks:
            if b == NULL_BLOCK:
                continue
            c = self._ref.get(b, 0)
            if c <= 0:
                raise ValueError(f"double free of block {b}")
            if c == 1:
                del self._ref[b]
                self._free.append(b)
            else:
                self._ref[b] = c - 1

    def ensure_exclusive(self, block: int) -> tuple[int, bool]:
        """Copy-on-write: return ``(block, False)`` when the caller holds
        the only reference, else drop the shared ref and hand back a fresh
        block as ``(new_block, True)`` — the caller must copy the pool
        contents before writing. Raises when the pool is dry (the caller's
        eviction policy runs *before* divergent writes, so this is a
        can't-happen guard, not a control path)."""
        if self._ref.get(block, 0) <= 1:
            return block, False
        fresh = self.alloc(1)
        if fresh is None:
            raise RuntimeError("KV pool exhausted during copy-on-write")
        self.free([block])
        return fresh[0], True


@dataclasses.dataclass
class PagedKVCache:
    """Paged pool handle: per-layer KV blocks + per-slot block tables.

    ``k``/``v`` are (L, num_blocks, Hkv_local, block_size, D) — a global
    pool shared by every slot; ``tables`` is (B, max_blocks) int32 mapping
    each slot's logical block index to a physical pool block (rows of
    NULL_BLOCK when unmapped); ``lengths`` is the same (B,) valid-length
    vector the contiguous cache carries. Fixed shapes throughout: batch
    composition, chain layout, and prefix sharing all change *data* in the
    tables, never array shapes — nothing recompiles (the vLLM block table,
    Kwon et al. SOSP'23, under the jit discipline).

    With ``quant`` set ("int8"/"fp8", ``models/quant.py``) the payload pools
    hold the wire dtype and ``k_scale``/``v_scale`` are the parallel scale
    pools — (L, num_blocks, Hkv_local, block_size, 1) f32, one scale per
    stored ROW. Per-row scales make the quantize-once invariant structural:
    a row is quantized exactly once, at append, by whichever scatter wrote
    it; sharing, CoW copies, and gathers only ever move the (payload, scale)
    pair — they never re-derive a scale, so a shared prefix block stays
    byte-identical across donor and borrower."""

    k: jax.Array
    v: jax.Array
    tables: jax.Array  # (B, max_blocks) int32
    lengths: jax.Array  # (B,) int32
    block_size: int
    k_scale: jax.Array | None = None  # (L, blocks, Hkv, bs, 1) f32 when quant
    v_scale: jax.Array | None = None
    quant: str | None = None  # None | "int8" | "fp8"
    #: What the two pools hold (``CacheRow.kind``): K and V rows, or the
    #: row kinds a model declared.
    kinds: tuple = ("k", "v")
    #: What the slots keep whatever their length (a model's
    #: ``slot_state(num_slots)``: recurrent state, a window's ring), the
    #: slot's axis first on every array; ``()`` for a model that keeps none.
    #: Not under the block table: a block shared with another slot shares
    #: nothing of this.
    state: object = ()

    @staticmethod
    def create(rows, num_slots, *, block_size, num_blocks, max_len,
               dtype=jnp.bfloat16, sharding=None, quant=None):
        """``rows`` is a pair of :class:`CacheRow` (a model's
        ``cache_rows()``; :func:`kv_rows` for K and V rows alike): each pool
        gets the shape its kind of row asks for, (layers, blocks, heads, bs,
        width)."""
        if quant is not None:
            from triton_dist_tpu.models.quant import wire_dtype

            dtype = wire_dtype(quant)
        shapes = [(r.layers, num_blocks, r.heads, block_size, r.width) for r in rows]
        alike = shapes[0] == shapes[1]
        if quant is not None and not alike:
            raise NotImplementedError("a quantized pool holds K and V rows of one shape")

        def filled(fill, shape, dt):
            if sharding is None:
                return fill(shape, dt)
            return jax.jit(lambda: fill(shape, dt), out_shardings=sharding)()

        k = filled(jnp.zeros, shapes[0], dtype)
        v = jnp.copy(k) if alike else filled(jnp.zeros, shapes[1], dtype)
        k_scale = v_scale = None
        if quant is not None:
            # Scale pools start at 1.0 — quantize_rows' scale for an
            # all-zero row — so NULL-block reads dequantize to exact zeros
            # and an untouched row round-trips bitwise.
            k_scale = filled(jnp.ones, shapes[0][:-1] + (1,), jnp.float32)
            v_scale = jnp.copy(k_scale)
        max_blocks = -(-max_len // block_size)
        return PagedKVCache(
            k=k,
            v=v,
            tables=jnp.zeros((num_slots, max_blocks), jnp.int32),
            lengths=jnp.zeros((num_slots,), jnp.int32),
            block_size=block_size,
            k_scale=k_scale,
            v_scale=v_scale,
            quant=quant,
            kinds=(rows[0].kind, rows[1].kind),
        )

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def max_blocks(self) -> int:
        return self.tables.shape[1]

    @property
    def max_len(self) -> int:
        return self.max_blocks * self.block_size

    @property
    def bytes_per_block(self) -> int:
        """Real HBM bytes one pool block costs across k+v payloads AND the
        scale pools — the ledger's admission unit (logical block count alone
        under-charges quantized pools by the scale overhead and over-charges
        them by the dtype shrink)."""
        per = sum(self.bytes_per_block_by_kind.values())
        if self.k_scale is not None:
            nl, _, hkv, bs, _ = self.k.shape
            per += 2 * nl * hkv * bs * self.k_scale.dtype.itemsize
        return per

    @property
    def bytes_per_block_by_kind(self) -> dict:
        """Payload bytes of one block in each pool, by the kind of row it
        holds (the two pools may differ in layers, heads and width)."""
        out = {}
        for kind, pool in zip(self.kinds, (self.k, self.v)):
            nl, _, hkv, bs, hd = pool.shape
            out[kind] = nl * hkv * bs * hd * pool.dtype.itemsize
        return out

    @property
    def slot_state_bytes(self) -> int:
        """Bytes of the slots' state, all slots together."""
        return sum(x.nbytes for x in jax.tree.leaves(self.state))


jax.tree_util.register_dataclass(
    PagedKVCache,
    data_fields=["k", "v", "tables", "lengths", "k_scale", "v_scale", "state"],
    meta_fields=["block_size", "quant", "kinds"],
)


def draft_block_range(length: int, width: int, block_size: int) -> tuple[int, int]:
    """Chain positions ``[lo, hi)`` a speculative draft window may touch.

    A k-wide verify chunk writes draft KV rows at ``[length, length +
    width)`` of a slot's logical sequence (``width = chunk * k`` bounds the
    whole chunk; per-round clipping to ``remaining`` keeps actual writes
    inside the reserved chain). The serving loop runs
    ``BlockAllocator.ensure_exclusive`` over exactly these chain positions
    before dispatch so rejected drafts can be rolled back by a pure length
    rewind — no shared (prefix-donor) block is ever dirtied."""
    lo = length // block_size
    hi = -(-(length + width) // block_size)
    return lo, hi
