"""Free-slot allocation for fixed-capacity tensor pools.

The paper's Java engine calls ``new RpcCloudlet()``; the tensor engine
instead assigns the r-th new cloudlet to the r-th free slot of the active
buffer with two prefix sums and two scatters — O(pool + spawns), no sort.
Overflow is *counted*, never silently ignored (backpressure/drop semantics
are the caller's choice).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from ..analysis.annotate import checked_mode, collide, disjoint


def segment_sum(data: jnp.ndarray, ids: jnp.ndarray, n: int,
                valid: jnp.ndarray | None = None) -> jnp.ndarray:
    """Scatter-add ``data`` into ``n`` segments, dropping -1/invalid ids —
    the drop-semantics workhorse of the tick phases (scheduler, network)."""
    if valid is None:
        valid = ids >= 0
    idx = jnp.where(valid, ids, n)
    with collide("segment_sum"):
        return jnp.zeros((n,), data.dtype).at[idx].add(
            jnp.where(valid, data, jnp.zeros_like(data)), mode="drop")


def rank_select(mask: jnp.ndarray, k: int, block: int = 128) -> jnp.ndarray:
    """[k] i32: the index of the r-th True lane of ``mask`` at rank r, and
    0 at ranks past the last — what ``zeros.at[rank].set(arange)`` over
    the masked lanes gives, without its scatter.

    A TPU scatter costs a step per update lane, which here would be every
    lane of ``mask``.  Instead the lanes are cut into blocks: a rank's
    block comes from the blocks' running counts ([k, n/block] compares),
    and its lane from that block's own running count, read by a one-hot
    product (small integers, exact in float32).
    """
    i32, f32 = jnp.int32, jnp.float32
    n = mask.shape[0]
    nb = -(-n // block)
    m = jnp.pad(mask.astype(i32), (0, nb * block - n)).reshape(nb, block)
    within = jnp.cumsum(m, axis=1)                       # [nb, block]
    cnt = within[:, -1]
    incl = jnp.cumsum(cnt)                               # [nb]
    r = jnp.arange(k, dtype=i32)
    before = incl[None, :] <= r[:, None]                 # [k, nb]
    blk = jnp.sum(before, axis=1, dtype=i32)
    local = r - jnp.sum(jnp.where(before, cnt[None, :], 0), axis=1,
                        dtype=i32)
    onehot = (jnp.arange(nb, dtype=i32)[None, :] == blk[:, None]).astype(f32)
    rows = jnp.dot(onehot, within.astype(f32),
                   precision=lax.Precision.HIGHEST).astype(i32)
    lane = jnp.sum(rows <= local[:, None], axis=1, dtype=i32)
    # the min is a no-op at live ranks; it keeps the index in [0, n) for
    # the index verifier
    return jnp.where(r < incl[-1], jnp.minimum(blk * block + lane, n - 1), 0)


class SlotAssignment(NamedTuple):
    dst: jnp.ndarray       # [K] i32 destination pool slot for rank r
    src: jnp.ndarray       # [K] i32 source descriptor index for rank r
    live: jnp.ndarray      # [K] bool rank is actually assigned
    n_assigned: jnp.ndarray  # scalar i32
    n_dropped: jnp.ndarray   # scalar i32 (valid descriptors with no slot)


def assign_free_slots(free_mask: jnp.ndarray, valid_mask: jnp.ndarray,
                      k_static: int | None = None) -> SlotAssignment:
    """Match the r-th valid descriptor to the r-th free pool slot.

    Parameters
    ----------
    free_mask : [C] bool — pool slots that may be written.
    valid_mask : [M] bool — descriptors that want a slot (flattened).
    k_static : static cap on assignments per call (default min(C, M)).
    """
    C = free_mask.shape[0]
    M = valid_mask.shape[0]
    K = min(C, M) if k_static is None else min(k_static, C, M)
    i32 = jnp.int32

    free_rank = jnp.cumsum(free_mask.astype(i32)) - 1      # [C]
    want_rank = jnp.cumsum(valid_mask.astype(i32)) - 1     # [M]
    n_free = free_rank[-1] + 1
    n_want = want_rank[-1] + 1
    n_assigned = jnp.minimum(jnp.minimum(n_free, n_want), K)

    # slot_of_rank[r] = index of the r-th free slot (ranks ≥ K dropped).
    slot_of_rank = jnp.zeros((K,), i32).at[
        jnp.where(free_mask & (free_rank < K), free_rank, K)
    ].set(jnp.arange(C, dtype=i32), mode="drop")
    # src_of_rank[r] = index of the r-th valid descriptor.
    src_of_rank = jnp.zeros((K,), i32).at[
        jnp.where(valid_mask & (want_rank < K), want_rank, K)
    ].set(jnp.arange(M, dtype=i32), mode="drop")

    ranks = jnp.arange(K, dtype=i32)
    live = ranks < n_assigned
    return SlotAssignment(dst=slot_of_rank, src=src_of_rank, live=live,
                          n_assigned=n_assigned,
                          n_dropped=n_want - n_assigned)


def scatter_pool(cl, asg: SlotAssignment, **cols):
    """Fused spawn writer: one wave of new cloudlets lands in exactly TWO
    scatters — every i32 field of the stacked [C, NI] pool in one, every
    f32 field of the [C, NF] pool in the other.  All three spawn sites —
    root cloudlets (``gen_spawn``), successors (``derive``) and retry
    respawns (``faults.disruption``, §7) — go through here, so the pool
    write cost per tick is independent of how many columns exist.

    ``cl`` is the :class:`core.types.Cloudlets` buffer; column order and
    WIDTH come from its mode-keyed ``PoolLayout``, so the storage layout
    lives only in ``core.types``.  Columns are passed BY NAME, each a
    rank-level [K] array or a scalar to broadcast.  Every column of the
    active layout must be supplied — a spawn initializes whole rows —
    while registered columns outside the layout are accepted and skipped,
    so spawn sites stay mode-agnostic (the dead values fold away under
    jit).  Unregistered names raise.  Descriptor-level [M] arrays must be
    pre-gathered by ``asg.src``.  Returns the updated ``Cloudlets``.
    """
    from .types import CL_F_FIELDS, CL_I_FIELDS
    layout = cl.layout
    vocab = set(CL_I_FIELDS) | set(CL_F_FIELDS)
    missing = [n for n in layout.columns if n not in cols]
    unknown = sorted(set(cols) - vocab)
    if missing or unknown:
        raise TypeError(
            f"scatter_pool needs every column of the active layout "
            f"{layout.columns}; missing {sorted(missing)}, "
            f"unknown {unknown}")
    ints, flts = cl.ints, cl.flts
    C = ints.shape[0]
    K = asg.dst.shape[0]
    dst = jnp.where(asg.live, asg.dst, C)  # sentinel C → dropped

    def stacked(names, dtype):
        return jnp.stack(
            [jnp.broadcast_to(jnp.asarray(cols[n], dtype), (K,))
             for n in names], axis=1)

    if checked_mode():
        # The disjointness declared below is exactly what free-slot
        # compaction guarantees; REPRO_CHECKED=1 re-verifies it at runtime.
        from jax.experimental import checkify
        hits = jnp.zeros((C,), jnp.int32).at[dst].add(1, mode="drop")
        checkify.check(jnp.all(hits <= 1),
                       "scatter_pool: duplicate destination slot")
        checkify.check(
            jnp.all(jnp.where(asg.live, (asg.dst >= 0) & (asg.dst < C),
                              True)),
            "scatter_pool: live destination out of range")

    # Disjointness argument: live lanes carry slot_of_rank values — indices
    # of DISTINCT free slots by construction of the prefix-sum compaction —
    # and dead lanes carry the sentinel C, which mode="drop" discards.  The
    # interval domain cannot see this (the rank→slot gather erases the
    # rank tag), hence the declaration + the checked-mode assert above.
    with disjoint("scatter_pool"):
        return cl.replace(
            ints=ints.at[dst].set(stacked(layout.i_fields, ints.dtype),
                                  mode="drop"),
            flts=flts.at[dst].set(stacked(layout.f_fields, flts.dtype),
                                  mode="drop"))


def segment_rank(keys: jnp.ndarray, mask: jnp.ndarray,
                 num_segments: int, block: int = 128) -> jnp.ndarray:
    """Rank of each masked element within its segment (FCFS by slot order).

    Sort-free prefix ranking, used on the capped space-shared dispatch path
    (paper §4.2 waiting-queue admission).  The pool is cut into blocks of
    ``block`` lanes: intra-block ranks come from a strictly-lower-triangular
    equality count (O(n·block) elementwise work, no sort), block offsets
    from a per-segment count matrix cumsummed over blocks.  Unmasked
    elements get rank = n (never admitted).

    The count matrix is [n/block, num_segments+1]; when that exceeds a
    memory budget (huge instance counts × huge pools) the sort-based
    ranking — O(n) memory — takes over.
    """
    n = keys.shape[0]
    n_blocks = -(-n // max(min(block, n), 1))
    if n_blocks * (num_segments + 1) > (1 << 24):   # > 64 MB of i32 counts
        return segment_rank_sorted(keys, mask, num_segments)
    i32 = jnp.int32
    big = jnp.asarray(num_segments, i32)
    k = jnp.where(mask, keys.astype(i32), big)
    L = min(block, n)
    pad = -n % L
    if pad:
        k = jnp.concatenate([k, jnp.full((pad,), big, i32)])
        mask_p = jnp.concatenate([mask, jnp.zeros((pad,), bool)])
    else:
        mask_p = mask
    B = k.shape[0] // L
    kb = k.reshape(B, L)
    mb = mask_p.reshape(B, L)
    # intra-block rank: earlier masked lanes of the same segment
    same = (kb[:, :, None] == kb[:, None, :]) & mb[:, None, :]
    earlier = jnp.tril(jnp.ones((L, L), bool), k=-1)[None]
    intra = jnp.sum(same & earlier, axis=2).astype(i32)            # [B, L]
    # exclusive per-segment totals of all preceding blocks
    with collide("segment_rank"):
        cnt = jnp.zeros((B, num_segments + 1), i32).at[
            jnp.arange(B, dtype=i32)[:, None], kb].add(mb.astype(i32))
    base = jnp.cumsum(cnt, axis=0) - cnt                           # [B, S+1]
    rank = (base[jnp.arange(B)[:, None], kb] + intra).reshape(-1)[:n]
    return jnp.where(mask, rank, n)


def segment_rank_sorted(keys: jnp.ndarray, mask: jnp.ndarray,
                        num_segments: int) -> jnp.ndarray:
    """O(n log n) sort-based ranking: the reference oracle for
    :func:`segment_rank` and its O(n)-memory fallback for segment counts
    too large for the blocked count matrix."""
    n = keys.shape[0]
    i32 = jnp.int32
    big = jnp.asarray(num_segments, i32)
    k = jnp.where(mask, keys.astype(i32), big)
    order = jnp.argsort(k, stable=True)  # stable → slot order within segment
    pos = jnp.zeros((n,), i32).at[order].set(jnp.arange(n, dtype=i32))
    # first position of each segment
    with collide("segment_rank_sorted"):
        first = jnp.full((num_segments + 1,), n, i32).at[k].min(pos)
    rank = pos - first[k]
    return jnp.where(mask, rank, n)
