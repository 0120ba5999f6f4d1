"""SELCC-coherent disaggregated KV-page pool for multi-replica serving.

The paper's technique as a first-class serving feature (DESIGN.md Sec. 2):

* GCL = one KV page: [page_size, Hkv, hd] keys + values, one per layer
  stack; the 64-bit latch word per page carries the directory
  (writer byte | 56-bit reader bitmap) exactly as in Fig. 3;
* replicas CACHE pages they read (shared prefixes / system prompts) and
  keep the shared latch lazily — re-reads are local until a writer
  (decode appending into the page, or eviction) invalidates;
* the coherence plane is the bulk-synchronous round (core/rounds, over
  the shared core/coherence.py spec): reads = FAA+fetch (the combined
  one-RTT op — kernels/gcl_fetch) registering the replica's REAL
  directory lane, appends = S->X upgrade (or fresh CAS) + in-place
  update + version bump + downgrade back to S.

The pool state is a dict of arrays (shardable over the mesh: pages are
striped so each device homes P/devices pages).  The replica cache is a
set-associative map local_slot -> (global_page, version); a cached page
is VALID iff its version matches the directory version — the version
check at round boundaries is the deterministic form of the invalidation
message (DESIGN.md "what changed").

Rounds-backed serving (:meth:`SELCCKVPool.open_rounds_plane`): the pool
can serve its KV bytes straight from the rounds engine's GCL payload
plane instead of the host-side shadow page copies above.  Pages become
lines, replicas become nodes, and each page's k+v tensors are bitcast
into the line's int32 payload lanes (``mem_data`` / per-replica
``cache_data``).  ``pool.read`` then drives real coherence-plane read
ops through ``rounds.run_rounds`` (or ``run_rounds_sharded`` on a
mesh) and returns bytes whose freshness the protocol guarantees;
``pool.append`` is a coherent read-modify-write (S grant -> token
splice -> S->X upgrade write); ``pool.attend`` decodes the plane's
memory image for ``pool_decode_attention``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core import coherence as co
from ..core.addressing import GAddr
from .address import LineAllocator
from ..kernels.gcl_fetch.ops import fetch as gcl_fetch_op
from ..kernels.latch_ops.ops import OP_CAS, apply_batch
from ..kernels.paged_attention.ops import decode_paged


@dataclass(frozen=True)
class KVPoolConfig:
    n_pages: int = 1024
    page_size: int = 16              # tokens per GCL
    n_kv_heads: int = 8
    head_dim: int = 128
    n_layers: int = 1                # pools are usually per layer-stack
    n_replicas: int = 4
    cache_slots: int = 256           # local cache capacity per replica
    dtype: str = "bfloat16"


def _pool_dtype(cfg: KVPoolConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def make_pool(cfg: KVPoolConfig, mesh=None, axis: str = "shards"):
    dt = _pool_dtype(cfg)
    shape = (cfg.n_pages, cfg.page_size, cfg.n_kv_heads, cfg.head_dim)
    pool = {
        "k_pages": jnp.zeros(shape, dt),
        "v_pages": jnp.zeros(shape, dt),
        "words": jnp.zeros((cfg.n_pages, 2), jnp.int32),   # latch+directory
        "page_version": jnp.zeros((cfg.n_pages,), jnp.int32),
        "page_fill": jnp.zeros((cfg.n_pages,), jnp.int32), # tokens written
        "alloc_top": jnp.zeros((), jnp.int32),
        # readers evicted by append PeerWr broadcasts (coherence stat:
        # the serving analogue of the DES inv_sent counter)
        "append_evictions": jnp.zeros((), jnp.int32),
    }
    if mesh is None:
        return pool
    # mesh-backed pool: every page-indexed leaf is sharded over the page
    # axis (each device homes n_pages / n_shards pages); the jitted
    # append/read paths stay unchanged — XLA partitions the scatters and
    # gathers, the GSPMD analogue of the rounds plane's explicit
    # all_to_all routing.  NamedSharding places pages in contiguous
    # BLOCKS (device d holds pages [d*P/S, (d+1)*P/S)), whereas the
    # rounds plane stripes by page % S — logical page indices are
    # identical on both planes, physical placement is not (GSPMD cannot
    # express mod placement without permuting the logical order the
    # page tables index by)
    n_shards = mesh.shape[axis]
    if cfg.n_pages % n_shards:
        raise ValueError(f"n_pages={cfg.n_pages} not divisible by the "
                         f"mesh's {n_shards} shards")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..core.rounds.sharded import auto_mesh
    mesh = auto_mesh(mesh)

    def put(name, arr):
        if arr.ndim == 0:                       # counters: replicated
            spec = P()
        else:                                   # page axis is dim 0
            spec = P(*((axis,) + (None,) * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(mesh, spec))
    return {k: put(k, v) for k, v in pool.items()}


def make_replica_cache(cfg: KVPoolConfig):
    dt = _pool_dtype(cfg)            # local copies match the pool dtype
    shape = (cfg.n_replicas, cfg.cache_slots, cfg.page_size,
             cfg.n_kv_heads, cfg.head_dim)
    return {
        # local copies of pages + the (page, version) tags
        "k_local": jnp.zeros(shape, dt),
        "v_local": jnp.zeros(shape, dt),
        "tag_page": jnp.full((cfg.n_replicas, cfg.cache_slots), -1,
                             jnp.int32),
        "tag_version": jnp.zeros((cfg.n_replicas, cfg.cache_slots),
                                 jnp.int32),
        "clock": jnp.zeros((cfg.n_replicas,), jnp.int32),
    }


def _slot_of(page, cache_slots):
    return page % cache_slots        # direct-mapped (paper uses hashed LRU)


# ------------------------------------- pages <-> GCL payload lanes (int32)

def page_lanes(cfg: KVPoolConfig) -> int:
    """int32 payload lanes per line for one (k, v) page pair — the
    ``payload_width`` of the pool's rounds-plane coherence state."""
    elems = cfg.page_size * cfg.n_kv_heads * cfg.head_dim
    if _pool_dtype(cfg) == jnp.bfloat16:
        if elems % 2:
            raise ValueError(
                f"bf16 page of {elems} elements cannot pack into int32 "
                f"lanes (need an even element count)")
        return elems                 # k: elems//2 lanes + v: elems//2
    return 2 * elems                 # fp32: one lane per element


def encode_kv(k, v, cfg: KVPoolConfig):
    """Bitcast k/v page tensors [..., page_size, Hkv, hd] into the
    line's int32 payload lanes [..., W] (k lanes then v lanes)."""
    dt = _pool_dtype(cfg)

    def enc(x):
        flat = jnp.asarray(x).astype(dt).reshape(x.shape[:-3] + (-1,))
        if dt == jnp.bfloat16:       # 2 bf16 elements per int32 lane
            flat = flat.reshape(flat.shape[:-1] + (flat.shape[-1] // 2, 2))
        return jax.lax.bitcast_convert_type(flat, jnp.int32)
    return jnp.concatenate([enc(k), enc(v)], axis=-1)


def decode_kv(data, cfg: KVPoolConfig):
    """Inverse of :func:`encode_kv`: payload lanes [..., W] -> (k, v)
    page tensors [..., page_size, Hkv, hd] in the pool dtype."""
    dt = _pool_dtype(cfg)
    data = jnp.asarray(data, jnp.int32)
    half = data.shape[-1] // 2
    page_shape = (cfg.page_size, cfg.n_kv_heads, cfg.head_dim)

    def dec(lanes):
        x = jax.lax.bitcast_convert_type(lanes, dt)
        return x.reshape(lanes.shape[:-1] + page_shape)
    return dec(data[..., :half]), dec(data[..., half:])


@functools.lru_cache(maxsize=None)
def _append_splice(cfg: KVPoolConfig):
    """The rounds-plane append's token splice as a ``run_rmw`` lane
    transform: decode the freshly-read page bytes, land every token of
    the batch on its page (later slots winning — the engine serializes
    a coalesced write group to its LAST slot's payload, so EVERY slot
    of a duplicate-page group must carry the group total), re-encode.
    Cached per config so repeated appends of one shape share one jit
    trace (``rounds.TRACE_COUNTS`` proves it).  A ``line = -1`` row is
    padding and keeps its (zero) bytes."""
    def modify(data, line, offsets, k_new, v_new):
        k_pg, v_pg = decode_kv(data, cfg)          # [B, ps, Hkv, hd]
        b = line.shape[0]
        tok = jnp.arange(b)
        match = jnp.logical_and(line[:, None] == line[None, :],
                                (line >= 0)[:, None])     # [tok, row]
        oh = offsets[:, None] == jnp.arange(cfg.page_size)[None, :]
        win = jnp.max(jnp.where(
            jnp.logical_and(match[:, :, None], oh[:, None, :]),
            tok[:, None, None], -1), axis=0)              # [B, ps]
        keep = (win >= 0)[..., None, None]
        sel = jnp.maximum(win, 0)
        k_pg = jnp.where(keep,
                         jnp.asarray(k_new).astype(k_pg.dtype)[sel],
                         k_pg)
        v_pg = jnp.where(keep,
                         jnp.asarray(v_new).astype(v_pg.dtype)[sel],
                         v_pg)
        return encode_kv(k_pg, v_pg, cfg)
    return modify


# ---------------------------------------------------------------- appends

@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def append_tokens(pool, replica, pages, offsets, k_new, v_new, *,
                  cfg: KVPoolConfig, backend: str = "ref"):
    """Decode write path: the replica owning the tail pages writes one
    token per sequence.  pages/offsets [B] (page -1 = skip); k_new/v_new
    [B, Hkv, hd].

    Exclusive access follows the protocol's write path through the
    shared spec (core/coherence.py):

    1. S->X UPGRADE — CAS(my reader bit -> my writer field) through the
       latch kernel: succeeds iff this replica is the sole registered
       holder (Algorithm 2);
    2. a failed CAS's returned old word IS the embedded directory: its
       other reader bits are the PeerWr broadcast targets (counted into
       ``append_evictions`` — single-writer-per-sequence means the
       contenders are always readers; a fresh acquire is the same case
       with no readers to evict);
    3. after the in-place write + version bump (the version IS the lazy
       invalidation — evicted readers' tags mismatch on their next
       read), the writer DOWNGRADES M -> S.  The whole append is one
       bulk-synchronous step, so the transient M-held word is never
       externally observable: the boundary writes the POST-downgrade
       word directly — the writer's sole reader bit, exactly the word
       the DES `_downgrade` leaves behind."""
    valid = pages >= 0
    idx = jnp.maximum(pages, 0)
    n_pages = cfg.n_pages
    bit_hi, bit_lo = co.bit_lanes(replica)
    wf = co.writer_field_hi(replica)
    words = pool["words"]
    line = jnp.where(valid, pages, -1).astype(jnp.int32)
    zeros = jnp.zeros_like(line)
    cas = jnp.full_like(line, OP_CAS)
    # 1. upgrade: CAS(my bit -> writer field)
    words, old_hi, old_lo, ok_up = apply_batch(
        words, {"line": line, "op": cas,
                "arg_hi": zeros + wf, "arg_lo": zeros,
                "cmp_hi": zeros + bit_hi, "cmp_lo": zeros + bit_lo},
        backend=backend)
    # 2. PeerWr boundary for failed upgrades: the CAS's returned old
    # word carries the OTHER readers to evict (the step-3 scatter below
    # writes the post-eviction, post-downgrade word)
    forced = jnp.logical_and(valid, ok_up == 0)
    others_lo = (old_lo & ~bit_lo).astype(jnp.uint32)
    others_hi = ((old_hi & ~bit_hi) & ((1 << co.WRITER_SHIFT_HI) - 1)) \
        .astype(jnp.uint32)
    evicted = jnp.sum(jnp.where(
        forced,
        jax.lax.population_count(others_lo).astype(jnp.int32)
        + jax.lax.population_count(others_hi).astype(jnp.int32), 0))
    # in-place write + version bump (write-through: pool IS the memory)
    kp = pool["k_pages"].at[jnp.where(valid, idx, n_pages), offsets].set(
        k_new.astype(pool["k_pages"].dtype), mode="drop")
    vp = pool["v_pages"].at[jnp.where(valid, idx, n_pages), offsets].set(
        v_new.astype(pool["v_pages"].dtype), mode="drop")
    ver = pool["page_version"].at[jnp.where(valid, idx, n_pages)].add(
        1, mode="drop")
    fill = pool["page_fill"].at[jnp.where(valid, idx, n_pages)].max(
        offsets + 1, mode="drop")
    # 3. downgrade M -> S: writer keeps a registered coherent copy
    words = words.at[jnp.where(valid, idx, n_pages)].set(
        jnp.stack([zeros + bit_hi, zeros + bit_lo], axis=1), mode="drop")
    return dict(pool, k_pages=kp, v_pages=vp, words=words,
                page_version=ver, page_fill=fill,
                append_evictions=pool["append_evictions"] + evicted)


# ---------------------------------------------------------------- reads

@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def read_through_cache(pool, cache, replica, pages, *, cfg: KVPoolConfig,
                       backend: str = "ref"):
    """Replica `replica` needs `pages` [R] (−1 = none).  Hits come from
    the local cache; misses do the combined latch+fetch (gcl_fetch) and
    install the page + reader bit.  Returns (k [R,page,Hkv,hd], v, cache',
    pool', hit_mask)."""
    slots = _slot_of(jnp.maximum(pages, 0), cfg.cache_slots)
    tag_p = cache["tag_page"][replica, slots]
    tag_v = cache["tag_version"][replica, slots]
    cur_v = pool["page_version"][jnp.maximum(pages, 0)]
    valid = pages >= 0
    hit = jnp.logical_and(valid,
                          jnp.logical_and(tag_p == pages, tag_v == cur_v))
    miss = jnp.logical_and(valid, ~hit)

    # --- combined latch + payload fetch for misses (1 "round trip") -------
    flat_k = pool["k_pages"].reshape(cfg.n_pages, -1)
    flat_v = pool["v_pages"].reshape(cfg.n_pages, -1)
    req_page = jnp.where(miss, pages, -1).astype(jnp.int32)
    # this replica's OWN directory lanes from the shared spec (pre-spec,
    # every replica aliased bit 1<<1 and the embedded directory
    # under-counted readers)
    rep_hi, rep_lo = co.bit_lanes(replica)
    bit_lo = jnp.where(miss, rep_lo, 0).astype(jnp.int32)
    bit_hi = jnp.where(miss, rep_hi, 0).astype(jnp.int32)
    k_fetch, _, _, granted_k, words = gcl_fetch_op(
        flat_k, pool["words"], req_page, bit_hi, bit_lo, backend=backend)
    v_fetch, _, _, _, _ = gcl_fetch_op(
        flat_v, pool["words"], req_page, bit_hi, bit_lo, backend=backend)
    page_shape = (cfg.page_size, cfg.n_kv_heads, cfg.head_dim)
    k_fetch = k_fetch.reshape((-1,) + page_shape)
    v_fetch = v_fetch.reshape((-1,) + page_shape)

    # --- install misses into the local cache ------------------------------
    kl = cache["k_local"].at[replica, slots].set(
        jnp.where(miss[:, None, None, None], k_fetch,
                  cache["k_local"][replica, slots]), mode="drop")
    vl = cache["v_local"].at[replica, slots].set(
        jnp.where(miss[:, None, None, None], v_fetch,
                  cache["v_local"][replica, slots]), mode="drop")
    tp = cache["tag_page"].at[replica, slots].set(
        jnp.where(miss, pages, tag_p), mode="drop")
    tv = cache["tag_version"].at[replica, slots].set(
        jnp.where(miss, cur_v, tag_v), mode="drop")
    new_cache = dict(cache, k_local=kl, v_local=vl, tag_page=tp,
                     tag_version=tv)
    new_pool = dict(pool, words=words)

    k_out = jnp.where(hit[:, None, None, None],
                      cache["k_local"][replica, slots], k_fetch)
    v_out = jnp.where(hit[:, None, None, None],
                      cache["v_local"][replica, slots], v_fetch)
    return k_out, v_out, new_cache, new_pool, hit


# ----------------------------------------------------- attention over pool

@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def pool_decode_attention(pool, q, page_tbl, lens, *, cfg: KVPoolConfig,
                          backend: str = "ref"):
    """Decode attention straight over the shared pool (paged_attention
    kernel): q [B,Hq,hd], page_tbl [B,max_pages], lens [B]."""
    return decode_paged(q, pool["k_pages"], pool["v_pages"], page_tbl,
                        lens, backend=backend)


@functools.partial(jax.jit, static_argnames=("cfg", "n_shards", "backend"))
def pool_decode_attention_rounds(rstate, q, page_tbl, lens, *,
                                 cfg: KVPoolConfig, n_shards: int = 1,
                                 backend: str = "ref"):
    """Decode attention over the ROUNDS-PLANE memory image: the page
    bytes come out of the coherence state's ``mem_data`` payload lanes
    (unstriped back to page-major on a sharded plane), not the host-side
    shadow ``k_pages``/``v_pages``.  Under write-through appends the
    memory image is always protocol-fresh; under write-back a dirty
    appender's bytes reach it on the next downgrade/invalidation/evict,
    exactly like the DES."""
    md = rstate["mem_data"]
    if n_shards > 1:
        from ..core.rounds.state import unstripe_lines
        md = unstripe_lines(md, n_shards)
    k_pages, v_pages = decode_kv(md, cfg)
    return decode_paged(q, k_pages, v_pages, page_tbl, lens,
                        backend=backend)


class SELCCKVPool:
    """Convenience façade tying pool + replica caches together for the
    examples and tests (allocation is host-side bump allocation; the
    data/coherence plane is the jitted functions above)."""

    def __init__(self, cfg: KVPoolConfig, mesh=None, axis: str = "shards"):
        co.check_node_capacity(cfg.n_replicas)   # replicas = directory lanes
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.pool = make_pool(cfg, mesh=mesh, axis=axis)
        self.cache = make_replica_cache(cfg)
        self.rounds_plane = None     # set by open_rounds_plane()
        # page allocation shares dsm.LineAllocator's contract: free-list
        # reuse, raise on exhaustion, reject double-free/never-allocated
        self._alloc = LineAllocator(cfg.n_pages)

    @property
    def rounds_state(self):
        """The coherence plane's state dict (None until
        ``open_rounds_plane``); owned by ``self.rounds_plane``."""
        return (None if self.rounds_plane is None
                else self.rounds_plane.state)

    @rounds_state.setter
    def rounds_state(self, value):
        if value is None:
            self.rounds_plane = None
        else:
            self.rounds_plane.state = value

    def as_rounds_state(self, *, write_back: bool = False, mesh=None,
                        axis: str | None = None):
        """A rounds-plane coherence state for THIS pool's pages: pages
        are the lines, replicas are the nodes.  With a mesh (the pool's
        own by default) the state is the mesh-sharded plane
        (``home = page % n_shards`` — ``dsm.address.home_of``), driven
        by ``rounds.run_rounds_sharded`` or a
        ``DevicePlane.open(state, mesh)`` facade
        with the SAME logical page indices the pool's data
        plane uses.  Note the two planes agree on indices, not physical
        placement: the data arrays are GSPMD block-sharded (see
        :func:`make_pool`) while the coherence plane stripes by
        ``page % n_shards``."""
        from ..core import rounds
        mesh = mesh if mesh is not None else self.mesh
        if mesh is not None:
            return rounds.make_sharded_state(
                self.cfg.n_replicas, self.cfg.n_pages, mesh,
                axis or self.axis, write_back=write_back)
        return rounds.make_state(self.cfg.n_replicas, self.cfg.n_pages,
                                 write_back=write_back)

    # ----------------------------------------- rounds-backed serving plane
    def open_rounds_plane(self, *, write_back: bool = False,
                          recorder=None):
        """Switch this pool's read/append/attend paths onto the rounds
        engine's GCL payload plane: a coherence state whose lines are
        the pool's pages and whose ``mem_data`` payload lanes hold the
        REAL page bytes (seeded from the current ``k_pages``/
        ``v_pages`` by bitcast).  On a mesh-backed pool the plane is
        the mesh-sharded engine (``home = page % n_shards``) and every
        read/append crosses it through the two per-round all_to_alls.
        ``recorder`` optionally attaches an ``obs.FlightRecorder`` to
        the plane (one span per fused append/read dispatch).
        Returns the state (also kept as ``self.rounds_state``)."""
        from ..core import rounds
        if self.rounds_state is not None:
            # re-seeding from the shadow pages would silently discard
            # every append made through the plane (rounds-mode appends
            # never touch k_pages/v_pages)
            raise RuntimeError(
                "rounds plane already open; build a fresh SELCCKVPool "
                "to re-open with different settings")
        width = page_lanes(self.cfg)
        state = rounds.make_state(self.cfg.n_replicas, self.cfg.n_pages,
                                  write_back=write_back,
                                  payload_width=width)
        state["mem_data"] = encode_kv(jnp.asarray(self.pool["k_pages"]),
                                      jnp.asarray(self.pool["v_pages"]),
                                      self.cfg)
        if self.mesh is not None:
            state = rounds.shard_state(state, self.mesh, self.axis)
        self.rounds_plane = rounds.DevicePlane.open(
            state, self.mesh, axis=self.axis,
            n_nodes=self.cfg.n_replicas, recorder=recorder)
        return state

    def _plane_ops(self, node, line, isw, wdata):
        """Drive one op batch through the pool's coherence plane (flat
        or mesh-sharded) and return (versions, read payloads)."""
        res = self.rounds_plane.ops(node, line, isw, wdata)
        return res.version, res.data

    def _plane_held(self, replica: int, pages) -> np.ndarray:
        """Rounds-mode hit mask: the replica already holds the page in
        S or M (a lazy-latch local re-read — the plane's analogue of
        the legacy tag/version match)."""
        cs = np.asarray(self.rounds_state["cache_state"])
        pos = np.maximum(pages, 0)
        if self.mesh is not None:
            s = self.mesh.shape[self.axis]
            n_lines = cs.shape[1]                 # stripe layout
            pos = (pos % s) * (n_lines // s) + pos // s
        return np.logical_and(pages >= 0, cs[replica, pos] != 0)

    @property
    def free_pages(self) -> int:
        """Pages currently allocatable (never-used + freed)."""
        return self._alloc.free_lines

    @property
    def pages_in_use(self) -> int:
        return self.cfg.n_pages - self._alloc.free_lines

    def allocate(self, n: int) -> np.ndarray:
        """Allocate ``n`` pages — freed pages are reused first, then the
        bump pointer grows (``dsm.LineAllocator``).  Raises instead of
        wrapping past ``n_pages`` — the pre-guard modulo silently handed
        out pages that were still live."""
        return self._alloc.alloc(int(n))

    def free(self, pages) -> None:
        """Return pages to the pool's free list, to be reused by
        :meth:`allocate` (slot eviction churn in a serving loop would
        otherwise exhaust the grow-only pool).  Raises ``ValueError`` on
        a double-free or a never-allocated page, exactly like
        ``dsm.LineAllocator`` — recycling a page that is still latched
        corrupts the coherence directory silently.

        Freeing does NOT scrub the page's bytes or its directory entry:
        a recycled page keeps its stale payload until the next writer
        lands, and stale reader registrations are evicted through the
        normal S->X upgrade path — the protocol, not the allocator,
        keeps recycled pages coherent."""
        self._alloc.free(pages)

    def gaddr_of(self, page: int, n_homes: int = 1) -> GAddr:
        """Structured address of a flat page index — the SAME vocabulary
        the DES facade speaks (``SELCCLayer.line_to_gaddr``), so serving
        pages and protocol GCLs are interchangeable identifiers."""
        page = int(page)
        if not 0 <= page < self.cfg.n_pages:
            raise ValueError(
                f"page {page} outside this pool's 0..{self.cfg.n_pages - 1}")
        return GAddr.from_flat(page, n_homes)

    def page_of(self, gaddr, n_homes: int = 1) -> int:
        """Flat page index of a :class:`GAddr`.  Raises ``ValueError``
        for an address from a FOREIGN pool geometry (home id outside
        ``n_homes`` or a page outside this pool) instead of silently
        aliasing it onto a live page."""
        g = GAddr(*gaddr)
        if not 0 <= g.node_id < n_homes:
            raise ValueError(
                f"{g!r} is not from this pool's geometry: home "
                f"{g.node_id} outside 0..{n_homes - 1}")
        page = g.flat(n_homes)
        if not 0 <= page < self.cfg.n_pages:
            raise ValueError(
                f"{g!r} maps to page {page}, outside this pool's "
                f"0..{self.cfg.n_pages - 1}")
        return page

    def append(self, pages, offsets, k_new, v_new, replica=0):
        """Append one token per row.  ``replica`` may be a scalar or an
        [B] array on the rounds plane (the serving engine batches slots
        owned by different replicas into one fused step — rows of
        different replicas must target different pages, the ``run_rmw``
        per-call atomicity contract).  Returns the coherence rounds the
        fused step spun (0 on the legacy plane)."""
        if self.rounds_state is None:
            if np.ndim(replica) != 0:
                raise TypeError("per-row replica vectors need the "
                                "rounds plane (open_rounds_plane())")
            self.pool = append_tokens(self.pool, jnp.int32(replica),
                                      jnp.asarray(pages),
                                      jnp.asarray(offsets), k_new, v_new,
                                      cfg=self.cfg)
            return 0
        # Rounds-plane append: ONE fused coherent read-modify-write
        # (rounds.run_rmw) — the S-grant read, the token splice
        # (_append_splice, on device between the phases), and the S->X
        # upgrade write all inside a single jitted rounds call.
        # Pre-fuse this was a host-side two-phase: a read rounds call,
        # a numpy splice, and a write rounds call — two dispatches and
        # a full host round trip per appended batch.
        pages = np.asarray(pages, np.int32)
        offsets = np.asarray(offsets, np.int32)
        node = np.broadcast_to(np.asarray(replica, np.int32),
                               pages.shape).astype(np.int32)
        res = self.rounds_plane.rmw(
            node, pages, modify=_append_splice(self.cfg),
            operands=(offsets, np.asarray(k_new), np.asarray(v_new)))
        return res.rounds

    def read(self, replica: int, pages):
        if self.rounds_state is None:
            k, v, self.cache, self.pool, hit = read_through_cache(
                self.pool, self.cache, replica, jnp.asarray(pages),
                cfg=self.cfg)
            return k, v, np.asarray(hit)
        # Rounds-plane read: real coherence ops — the returned bytes
        # come out of the engine's cache_data/mem_data payload lanes
        # with protocol-guaranteed freshness (fetch-on-grant installs
        # the replica's copy; a writer's invalidation drops it).
        pages = np.asarray(pages, np.int32)
        hit = self._plane_held(replica, pages)
        node = np.full(pages.shape, replica, np.int32)
        width = page_lanes(self.cfg)
        _, data = self._plane_ops(node, pages, np.zeros_like(pages),
                                  np.zeros((pages.shape[0], width),
                                           np.int32))
        k, v = decode_kv(data, self.cfg)
        return k, v, hit

    def attend(self, q, page_tbl, lens):
        if self.rounds_state is not None:
            n_shards = (self.mesh.shape[self.axis]
                        if self.mesh is not None else 1)
            return pool_decode_attention_rounds(
                self.rounds_state, q, jnp.asarray(page_tbl),
                jnp.asarray(lens), cfg=self.cfg, n_shards=n_shards)
        return pool_decode_attention(self.pool, q, jnp.asarray(page_tbl),
                                     jnp.asarray(lens), cfg=self.cfg)
