"""The backend-neutral SELCC coherence spec — ONE protocol, two planes.

This module is the single source of truth for everything the paper's
Sec. 4 defines once but this repo used to implement three times:

* the Fig. 3 latch-word encoding (8-bit exclusive-holder byte + 56-bit
  reader bitmap in one 64-bit RDMA word), in BOTH representations —
  canonical Python ints for the discrete-event plane (host form) and
  2 x int32 lanes for the JAX/Pallas device plane (TPUs are 32-bit-lane
  machines);
* the MSI transition table (Fig. 2): what a holder in state q does when
  a peer's invalidation event arrives.  The DES handlers
  (core/protocol.py ``_handle``) and the bulk-synchronous round engine
  (core/rounds/engine.py boundary step) both *look transitions up here*
  instead of re-encoding them, so the two planes cannot drift.

Consumers: core/latchword.py (compat re-export of the host form),
core/protocol.py (DES), core/rounds/* (device engine), dsm/kvpool.py
(serving pool reader lanes + append upgrade path).

Every function is pure; the array helpers are jnp-traceable (no Python
branching on traced values) so they inline into jitted round bodies.
Capacity errors are raised eagerly at *static* entry points
(:func:`check_node_capacity`) because a traced lane computation cannot
raise — pre-spec, node ids >= 56 silently aliased onto node 55's reader
bit (``jnp.clip(node - 32, 0, 23)``), under-counting readers.
"""

from __future__ import annotations

# --------------------------------------------------------------------------
# Word geometry (paper Sec. 4.2, Figure 3)
# --------------------------------------------------------------------------

MAX_NODES = 56                     # the paper's compute-node limit
WRITER_SHIFT = 56                  # writer byte: bits 63..56 of the word
READER_MASK = (1 << WRITER_SHIFT) - 1
WORD_MASK = (1 << 64) - 1
FREE = 0                           # latch off: no writer, no readers

# lane split: hi = bits 63..32, lo = bits 31..0
LANE_READERS = 32                  # readers 0..31 live in lo
HI_READER_BITS = MAX_NODES - LANE_READERS      # readers 32..55: hi bits 0..23
WRITER_SHIFT_HI = 24               # writer byte: hi-lane bits 31..24

# --------------------------------------------------------------------------
# MSI states + the peer-event transition table (Fig. 2)
# --------------------------------------------------------------------------

I, S, M = 0, 1, 2                  # shared numeric encoding (device plane)
STATE_NAMES = ("I", "S", "M")

EV_PEER_RD, EV_PEER_WR, EV_PEER_UPGR = 0, 1, 2
PEER_EVENTS = {"PeerRd": EV_PEER_RD, "PeerWr": EV_PEER_WR,
               "PeerUpgr": EV_PEER_UPGR}

# MSI_ON_PEER[state][event] -> next state for a HOLDER receiving a peer's
# invalidation.  Readers don't conflict with readers (S stays S on
# PeerRd); a writer downgrades on PeerRd (M -> S, after write-back) and
# releases outright on PeerWr/PeerUpgr; shared copies release on any
# writer intent.  Row I is the identity (nothing to invalidate).
MSI_ON_PEER = (
    #  PeerRd  PeerWr  PeerUpgr
    (I, I, I),          # from I
    (S, I, I),          # from S
    (S, I, I),          # from M (PeerRd = downgrade, with write-back)
)


def on_peer(state: int, event: int) -> int:
    """Next MSI state for a holder in ``state`` hit by peer ``event``."""
    return MSI_ON_PEER[state][event]


def check_node_capacity(n_nodes: int) -> None:
    """Reject node counts the 64-bit word cannot encode.  Raised at the
    static entry points (make_state / pool construction / engine trace)
    because traced lane math cannot raise per-element."""
    if not 0 < n_nodes <= MAX_NODES:
        raise ValueError(
            f"n_nodes={n_nodes} not encodable in the Fig. 3 latch word "
            f"(writer byte + {MAX_NODES}-bit reader bitmap allows "
            f"1..{MAX_NODES} nodes)")


def _check_node(node_id: int) -> None:
    if not 0 <= node_id < MAX_NODES:
        raise ValueError(f"node_id {node_id} out of range [0, {MAX_NODES})")


# --------------------------------------------------------------------------
# Host form: canonical Python ints (DES plane + checkers)
# --------------------------------------------------------------------------

def writer_field(node_id: int) -> int:
    """The word value representing 'node_id holds the exclusive latch'."""
    _check_node(node_id)
    return (node_id + 1) << WRITER_SHIFT


def reader_bit(node_id: int) -> int:
    _check_node(node_id)
    return 1 << node_id


def pack(writer: int | None, readers) -> int:
    """Build a latch word. ``writer`` is a node id or None; ``readers`` an
    iterable of node ids."""
    w = 0 if writer is None else (writer + 1)
    word = w << WRITER_SHIFT
    for r in readers:
        word |= reader_bit(r)
    return word


def writer_of(word: int) -> int | None:
    """Node id of the exclusive holder, or None."""
    w = (word >> WRITER_SHIFT) & 0xFF
    return None if w == 0 else w - 1


def readers_of(word: int) -> list[int]:
    bits = word & READER_MASK
    out = []
    i = 0
    while bits:
        if bits & 1:
            out.append(i)
        bits >>= 1
        i += 1
    return out


def has_readers(word: int) -> bool:
    return bool(word & READER_MASK)


def holders_of(word: int) -> list[int]:
    """Every node id that holds the latch in any mode (invalidation targets)."""
    w = writer_of(word)
    out = [] if w is None else [w]
    out.extend(r for r in readers_of(word) if r != w)
    return out


def is_free(word: int) -> bool:
    return word == FREE


def faa(word: int, delta: int) -> int:
    """Fetch-and-add semantics on the 64-bit word (wraps at 2**64 like the
    NIC does).  Returns the *old* value; caller applies ``(old + delta) & MASK``."""
    return (word + delta) & WORD_MASK


def to_lanes(word: int) -> tuple[int, int]:
    return (word >> 32) & 0xFFFFFFFF, word & 0xFFFFFFFF


def from_lanes(hi: int, lo: int) -> int:
    return ((hi & 0xFFFFFFFF) << 32) | (lo & 0xFFFFFFFF)


# --------------------------------------------------------------------------
# Device form: jnp-traceable lane helpers (rounds engine + kvpool)
# --------------------------------------------------------------------------

def bit_lanes(node):
    """Reader-bit lanes for ``node`` (scalar or array, int32): readers
    0..31 -> lo bit, 32..55 -> hi bits 0..23.  Callers must have passed
    :func:`check_node_capacity` — lane math cannot raise."""
    import jax.numpy as jnp
    node = jnp.asarray(node)
    lo = jnp.where(node < LANE_READERS,
                   jnp.left_shift(1, jnp.minimum(node, LANE_READERS - 1)), 0)
    hi = jnp.where(node >= LANE_READERS,
                   jnp.left_shift(1, jnp.clip(node - LANE_READERS, 0,
                                              HI_READER_BITS - 1)), 0)
    return hi.astype(jnp.int32), lo.astype(jnp.int32)


def writer_field_hi(node):
    """Hi-lane value for 'node holds the exclusive latch' (lo lane is 0)."""
    import jax.numpy as jnp
    return jnp.left_shift(jnp.asarray(node) + 1,
                          WRITER_SHIFT_HI).astype(jnp.int32)


def writer_of_hi(hi):
    """Writer node id encoded in a hi lane; -1 = no exclusive holder."""
    import jax.numpy as jnp
    w = jnp.right_shift(jnp.asarray(hi), WRITER_SHIFT_HI) & 0xFF
    return w - 1


def directory_from_state(cache_state):
    """Rebuild the per-line latch words from MSI cache states [N, L]:
    writer byte from the (unique) M holder, reader bits from S holders.

    The round engine calls this at every round boundary on the [N, k]
    columns of the lines the round named, so the word and the
    cache-state array cannot drift — the construction IS the paper's
    'the latch word is the directory' invariant.  Summation is exact
    because each node contributes one distinct bit."""
    import jax.numpy as jnp
    n_nodes = cache_state.shape[0]
    nodes = jnp.arange(n_nodes, dtype=jnp.int32)
    bhi, blo = bit_lanes(nodes)                         # [N]
    is_s = cache_state == S
    lo = jnp.sum(jnp.where(is_s, blo[:, None], 0), axis=0)
    hi = jnp.sum(jnp.where(is_s, bhi[:, None], 0), axis=0)
    is_m = cache_state == M
    writer = jnp.argmax(is_m, axis=0).astype(jnp.int32)
    has_w = jnp.any(is_m, axis=0)
    hi = hi + jnp.where(has_w, writer_field_hi(writer), 0)
    return jnp.stack([hi.astype(jnp.int32), lo.astype(jnp.int32)], axis=1)
