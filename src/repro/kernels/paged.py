"""Block-table-aware page ops for the paged packed-KV cache.

A paged cache stores every sequence buffer as a pool of FIXED-SIZE pages
(`(reps, n_pages, page_size, *tail)`); a request owns an ordered list of
page ids (its block-table row) instead of a contiguous span.  Admission
and eviction are then pure metadata — pages change owner by index, and
the packed VP words inside them are NEVER copied or dequantized when
requests come and go.

These ops are the XLA side of the pool layout:

  * `gather_pages`    — block table -> contiguous per-request view
                        (what prefill chunks, the planes/float golden
                        caches, the mesh runner's decode and decode over
                        page layouts the paged kernel cannot tile
                        consume, masked by the per-request `lengths`)
  * `scatter_pages`   — write whole pages (prefill commits a prompt)
  * `scatter_positions` — write single positions (decode commits its
                        new tokens per request; chunked prefill commits
                        a chunk that may straddle pages)

On the jnp/ref backend these lower to one XLA gather / scatter over the
page axis.  Single-device decode over a packed-word cache whose layout
the kernel tiles (`kernels.ops.paged_decode_supported`) gathers
nothing: `kernels.ops.vp_paged_decode_attention` takes the whole pools
and the block table, and its Pallas kernel DMAs each request's live
pages by id through the scalar-prefetched table (its ref oracle gathers
one layer's pages with `gather_pages`).

Page 0 is reserved as the DUMMY page: free-list allocation never hands
it out, and masked writes (inactive batch rows) land there.  Nothing
ever reads it back — tests poison it to prove that.
"""
from __future__ import annotations


def gather_pages(pool, page_ids):
    """Pool view through a block table.

    pool (reps, n_pages, page_size, *tail), page_ids (B, P) int32 ->
    (reps, B, P * page_size, *tail): request b's pages concatenated in
    block-table order — a contiguous cache view whose positions
    [0, lengths[b]) are valid.
    """
    reps, _, ps = pool.shape[:3]
    B, P = page_ids.shape
    g = pool[:, page_ids]                      # (reps, B, P, ps, *tail)
    return g.reshape(reps, B, P * ps, *pool.shape[3:])


def scatter_pages(pool, page_ids, values):
    """Write whole pages (one request's prefill commit).

    page_ids (P,) int32, values (reps, P * page_size, *tail) -> pool'.
    """
    reps, _, ps = pool.shape[:3]
    P = page_ids.shape[0]
    v = values.reshape(reps, P, ps, *pool.shape[3:])
    return pool.at[:, page_ids].set(v)


def scatter_positions(pool, page_ids, offsets, values):
    """Write single in-page positions (decode / chunked-prefill commit).

    page_ids (N,) int32 (page per position — duplicates allowed only on
    the dummy page 0), offsets (N,) int32 in [0, page_size), values
    (reps, N, *tail) -> pool'.
    """
    return pool.at[:, page_ids, offsets].set(values)


def flip_bit(pool, page, offset, bit):
    """XOR one bit of the first stored element at (`page`, `offset`).

    The fault-injection primitive for the chaos harness: corrupts ONE
    packed VP word (or one float cache element, via a same-width integer
    bitcast) in place, exactly as an HBM upset would — no other word in
    the pool changes, so the chaos suite can assert the corruption never
    escapes the page's owning request.  Targets rep 0 and the first tail
    element; `bit` is masked into the dtype's width.
    """
    import jax
    import jax.numpy as jnp

    idx = (0, page, offset) + (0,) * (pool.ndim - 3)
    word = pool[idx]
    if jnp.issubdtype(pool.dtype, jnp.integer):
        # XOR in int32 (a 1<<7 mask does not FIT int8) and wrap back.
        nbits = jnp.iinfo(pool.dtype).bits
        mask = jnp.int32(1 << (bit % nbits))
        flipped = (word.astype(jnp.int32) ^ mask).astype(pool.dtype)
    else:
        itype = {2: jnp.uint16, 4: jnp.uint32,
                 8: jnp.uint64}[pool.dtype.itemsize]
        nbits = pool.dtype.itemsize * 8
        raw = jax.lax.bitcast_convert_type(word, itype)
        raw = raw ^ itype(1 << (bit % nbits))
        flipped = jax.lax.bitcast_convert_type(raw, pool.dtype)
    return pool.at[idx].set(flipped)
