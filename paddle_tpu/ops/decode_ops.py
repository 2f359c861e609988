"""Incremental-decoding ops: the KV-cache fast path for autoregressive
LMs (models/generation.py generate(), serving decode batching).

The reference generates with beam_search/sampling_id over FULL forward
passes — every new token recomputes all S positions, O(S^2) attention
per token. These ops implement the standard prefill/decode split from
the LLM-serving literature (Orca iteration-level scheduling; vLLM's
cache-centric serving): a prefill hands each layer's bucket-long keys
and values to the block-paged pool (``serving/kvpool``), new tokens
append through a row's block table (``paged_kv_cache_write``; every row
of the batch can sit at a DIFFERENT position — the decode batch shares
one executable), and causal masking is driven by the per-row position
counters instead of the query/key index triangle. Per-token cost drops
from an O(S^2) recompute to one O(S) cache append + read, which is
bandwidth-bound. ``kv_cache_write``, the append into a dense ``[B, H,
max_len, D]`` cache, is kept as an op; no serving program uses it.
"""
import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .common import named, x_of

_NEG_INF = -1e30   # additive mask value; -inf breaks softmax on all-masked rows


@register_op("kv_cache_write", grad=False, infer_shape=False)
def kv_cache_write(ctx, ins, attrs):
    """Append S new key/value vectors into a preallocated cache at each
    row's own position. Cache [B, H, L, D], KV [B, H, S, D], Pos [B]
    int32 -> Out [B, H, L, D] with Out[b, :, pos[b]:pos[b]+S, :] = KV[b].

    ``dynamic_update_slice`` clamps the start index to [0, L-S], so an
    (invalid) overflowing position writes at the end instead of OOB —
    callers enforce position < max_len host-side.
    """
    cache = x_of(ins, "Cache")
    kv = x_of(ins, "KV")
    pos = x_of(ins, "Pos")

    def row(c, u, p):
        z = jnp.int32(0)
        return jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (z, p.astype(jnp.int32), z))

    return {"Out": jax.vmap(row)(cache, kv, pos)}


@register_op("paged_kv_cache_write", grad=False, infer_shape=False)
def paged_kv_cache_write(ctx, ins, attrs):
    """Append S new k/v vectors into a BLOCK-PAGED pool at each row's
    own position. Cache is the shared pool in its STORED shape
    [N, H * bs // f, f * D] (kernels/paged_attention: logically
    [N, H, bs, D]), KV [B, H, S, D], Tables [B, nblk] int32 (per-row
    block table), Pos [B] int32 -> Out: pool with row b's vector i
    written at logical ``(Tables[b, (Pos[b]+i)//bs], :, (Pos[b]+i)%bs)``.
    The optional Limit input [B] int32 marks how many of the S vectors
    are REAL per row (chunked prefill's ragged tail): positions at/past
    the limit are routed to the reserved trash block 0 instead. With an
    int8 pool the op quantizes (kernels/paged_attention.quantize_kv) and
    the optional Scale input (stored [N, f, H * bs // f]) is updated too
    (second output OutScale).

    With the attribute ``ring`` the table is a window layer's ring:
    position ``p`` lives in column ``(p // bs) % nblk`` (one token a row
    only; the prefill scatter fills a ring from outside).

    One token a row with no Limit (the decode step) is the Pallas call
    ``paged_kv_append`` on a TPU: in place in the stored layout, no
    table among its operands. Everything else, and every backend but
    the TPU, takes the composite: one scatter over the logical view
    covers the batch. Slots own disjoint blocks and COW guarantees a
    written block has refcount 1, so the valid (block, offset) pairs
    are unique; rows whose table entry is the trash block (free serving
    slots / past-limit padding) write garbage nobody reads. Which one
    ran is counted as ``attention_impl_total{op="paged_kv_append"}``.
    """
    from ..kernels import _dispatch
    from ..kernels.paged_attention import (
        paged_kv_append, quantize_kv, scales_to_logical, scales_to_stored,
        to_logical, to_stored)

    pool = x_of(ins, "Cache")
    kv = x_of(ins, "KV")
    tables = x_of(ins, "Tables").astype(jnp.int32)
    pos = x_of(ins, "Pos").astype(jnp.int32)
    B, H, S, D = kv.shape
    bs = pool.shape[1] * pool.shape[2] // (H * D)
    limit = ins.get("Limit")
    quant = pool.dtype == jnp.int8
    scale = x_of(ins, "Scale") if quant else None

    ring = bool(attrs.get("ring", False))
    if ring and (S != 1 or limit):
        raise NotImplementedError(
            "paged_kv_cache_write: a ring table takes one token a row "
            "(chunked prefill and the verify span are not built for "
            "window layers)")
    if S == 1 and not limit:
        # the decode step: one (block, offset) a row
        col = (pos // bs) % tables.shape[1] if ring else pos // bs
        block_ids = tables[jnp.arange(B), col]              # [B]
        offs = pos % bs                                     # [B]
        vals = kv[:, :, 0, :]                               # [B, H, D]
        impl, reason = _dispatch.auto_impl(), "backend"
    else:
        # multi-token path: per-(row, token) absolute positions, invalid
        # (past-limit) entries routed to the trash block. Clip keeps the
        # table gather in-bounds for padded rows whose pos+S would run
        # past the row's table; those entries are invalid by
        # construction.
        steps = jnp.arange(S, dtype=jnp.int32)
        qpos = pos[:, None] + steps[None, :]                # [B, S]
        if limit:
            valid = steps[None, :] < limit[0].astype(jnp.int32)[:, None]
        else:
            valid = jnp.ones((B, S), dtype=bool)
        safe = jnp.clip(qpos, 0, tables.shape[1] * bs - 1)
        blk = jnp.take_along_axis(tables, safe // bs, axis=1)   # [B, S]
        block_ids = jnp.where(valid, blk, 0).reshape(-1)    # [B*S]
        offs = (safe % bs).reshape(-1)                      # [B*S]
        vals = kv.transpose(0, 2, 1, 3).reshape(B * S, H, D)
        impl, reason = "xla", "multi_token"
    new_scale = None
    if quant:
        vals, new_scale = quantize_kv(vals)

    with _dispatch.resolved("paged_kv_append", impl, reason):
        if impl == "xla":
            out = to_stored(to_logical(pool, H, D).at[
                block_ids, :, offs, :].set(vals.astype(pool.dtype)))
            if not quant:
                return {"Out": out}
            return {"Out": out, "OutScale": scales_to_stored(
                scales_to_logical(scale, H).at[block_ids, :, offs].set(
                    new_scale), D)}

        def kernel(pool, vals, block_ids, offs, scale, new_scale):
            return paged_kv_append(pool, vals.astype(pool.dtype), block_ids,
                                   offs, scale, new_scale,
                                   interpret=impl == "interpret")

        # heads over tp: the stored rows and the scales' columns are
        # head-major, a chip's shard a contiguous range of either
        outs = _dispatch.per_shard(
            kernel, None if ctx.abstract else ctx.mesh,
            (pool, vals, block_ids, offs, scale, new_scale),
            ((None, "heads", None), (None, "heads", None), (None,), (None,),
             (None, None, "heads"), (None, "heads")),
            ((None, "heads", None), (None, None, "heads")) if quant
            else (None, "heads", None))
    if quant:
        return {"Out": outs[0], "OutScale": outs[1]}
    return {"Out": outs}


@register_op("paged_attention", grad=False, infer_shape=False)
def paged_attention_op(ctx, ins, attrs):
    """Decode attention of one query per row over the block-paged pool:
    Q [B, H, 1, D], K/V pools in their stored shape [N, Hkv * bs // f,
    f * D] (+ KScale/VScale [N, f, Hkv * bs // f] for int8; attrs
    ["kv_heads"] is Hkv, 0 = H), Tables [B, nblk] int32, Pos [B] int32
    -> Out [B, H, 1, D].
    Dispatches to kernels/paged_attention (Pallas fused gather+attend on
    TPU; jnp.take reference elsewhere — attrs["impl"] overrides). The
    pools may have fewer heads than Q (grouped queries); attrs["window"]
    (0 = none) makes Tables a ring and keeps the last ``window`` keys.
    Under a mesh the kernel runs per shard, heads over tp."""
    from ..kernels.paged_attention import paged_attention as _kernel

    q = x_of(ins, "Q")
    k = x_of(ins, "K")
    v = x_of(ins, "V")
    tables = x_of(ins, "Tables")
    pos = x_of(ins, "Pos")
    with named(attrs.get("scope")):
        out = _kernel(q, k, v, tables, pos,
                      k_scale=x_of(ins, "KScale"),
                      v_scale=x_of(ins, "VScale"),
                      scale=float(attrs.get("scale", 0.0)) or None,
                      impl=attrs.get("impl") or None,
                      mesh=None if ctx.abstract else ctx.mesh,
                      window=int(attrs.get("window", 0)) or None,
                      kv_heads=int(attrs.get("kv_heads", 0)) or None)
    return {"Out": out}


@register_op("row_gather", grad=False, infer_shape=False)
def row_gather(ctx, ins, attrs):
    """Out[b] = X[b, Index[b]] — per-row gather along axis 1 (e.g. the
    last REAL token's hidden state of a right-padded prefill batch).
    X [B, S, ...], Index [B] int -> Out [B, ...]."""
    x = x_of(ins)
    idx = x_of(ins, "Index").astype(jnp.int32)
    idx = jnp.clip(idx, 0, x.shape[1] - 1)
    expand = idx.reshape(idx.shape + (1,) * (x.ndim - 1))
    return {"Out": jnp.take_along_axis(x, expand, axis=1)[:, 0]}


@register_op("sample_tokens", grad=False, needs_rng=True,
             infer_shape=False)
def sample_tokens(ctx, ins, attrs):
    """Next-token selection over logits [B, V] with PER-ROW sampling
    config, so greedy and stochastic requests share one decode batch
    (and one executable):

    - Temperature [B] float32: rows with t <= 0 take argmax (greedy);
      rows with t > 0 sample from softmax(logits / t).
    - TopK [B] int32 (optional input): rows with k > 0 restrict sampling
      to the k highest logits (ties at the threshold stay eligible);
      k <= 0 means the full vocabulary.

    Draws from the framework RNG stream: the op folds its build-time
    ``__rng_seed__`` into the executor's run key (``ctx.op_key``), which
    advances by ``split(key, 1)[0]`` per call — fixed seed => bitwise
    reproducible sequences, and the forward-vjp replay rules of
    dropout apply unchanged. Out [B] int32.
    """
    logits = x_of(ins).astype(jnp.float32)
    temp = x_of(ins, "Temperature").astype(jnp.float32)
    topk = ins.get("TopK")
    key = ctx.op_key(attrs)
    V = logits.shape[-1]

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
    if topk:
        k = jnp.clip(topk[0].astype(jnp.int32), 1, V)            # [B]
        sorted_desc = -jnp.sort(-logits, axis=-1)                # [B, V]
        thresh = jnp.take_along_axis(sorted_desc, (k - 1)[:, None],
                                     axis=1)                     # [B, 1]
        allowed = (topk[0].astype(jnp.int32) <= 0)[:, None] | \
            (logits >= thresh)
        scaled = jnp.where(allowed, scaled, _NEG_INF)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(
        jnp.int32)
    return {"Out": jnp.where(temp <= 0.0, greedy, sampled)}


@register_op("spec_accept", grad=False, needs_rng=True,
             infer_shape=False)
def spec_accept(ctx, ins, attrs):
    """Speculative-decoding acceptance (Leviathan 2022 / Chen 2023
    rejection sampling, specialized to a POINT-MASS draft distribution
    — the n-gram drafter proposes tokens, not distributions, so
    q = delta(d_i) and the accept probability min(1, p/q) reduces to
    p(d_i); the residual on rejection is p with d_i removed,
    renormalized). One call scores a whole verified span per row:

    - Logits [B, S, V] float32: the verify pass's span logits —
      position i is the model's next-token distribution AFTER the
      current token and drafts d_1..d_i.
    - Draft [B, K] int32 (K = S-1): the proposed tokens.
    - Temperature [B] float32 / optional TopK [B] int32: the exact
      per-row sampling config of ``sample_tokens`` — p is the same
      temperature-scaled, top-k-masked softmax, so a row that accepts
      nothing emits one token from exactly the distribution a plain
      decode step would have used.
    - NumDraft [B] int32: each row's real draft count (<= K); rows at
      0 degrade to a plain single-token step inside the same call.

    Greedy rows (t <= 0) accept d_i while it matches argmax and emit
    argmax tokens throughout — BITWISE what sequential greedy decode
    would produce. Stochastic rows accept d_i with probability
    p_i(d_i) (one uniform draw per position) and sample the
    correction/bonus from the residual (rejection) or from p_K
    (full acceptance) — the output distribution is exactly the
    non-speculative sampler's.

    Out [B, S] int32: position j holds the token emitted for sequence
    position pos+j+1, valid for j <= Accepted[b] (a+1 tokens per row);
    Accepted [B] int32: leading draft tokens accepted (0..NumDraft).
    """
    logits = x_of(ins).astype(jnp.float32)
    draft = x_of(ins, "Draft").astype(jnp.int32)
    temp = x_of(ins, "Temperature").astype(jnp.float32)
    topk = ins.get("TopK")
    num_draft = x_of(ins, "NumDraft").astype(jnp.int32)
    key = ctx.op_key(attrs)
    u_key, cat_key = jax.random.split(key)
    B, S, V = logits.shape
    K = S - 1

    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
    scaled = logits / jnp.maximum(temp, 1e-6)[:, None, None]
    if topk:
        k = jnp.clip(topk[0].astype(jnp.int32), 1, V)            # [B]
        sorted_desc = -jnp.sort(-logits, axis=-1)                # [B,S,V]
        thresh = jnp.take_along_axis(
            sorted_desc, (k - 1)[:, None, None], axis=-1)        # [B,S,1]
        allowed = (topk[0].astype(jnp.int32) <= 0)[:, None, None] | \
            (logits >= thresh)
        scaled = jnp.where(allowed, scaled, _NEG_INF)

    # per-position acceptance: greedy compares against argmax,
    # stochastic draws one uniform per position against p_i(d_i)
    p = jax.nn.softmax(scaled[:, :K, :], axis=-1)                # [B,K,V]
    p_draft = jnp.take_along_axis(p, draft[:, :, None],
                                  axis=-1)[:, :, 0]              # [B, K]
    u = jax.random.uniform(u_key, (B, K))
    is_greedy = temp <= 0.0                                      # [B]
    accept = jnp.where(is_greedy[:, None],
                       draft == greedy_tok[:, :K],
                       u < p_draft)
    steps = jnp.arange(K, dtype=jnp.int32)[None, :]
    accept = accept & (steps < num_draft[:, None])
    # leading run of accepts (a rejection stops everything after it)
    a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                axis=1).astype(jnp.int32)                        # [B]

    # correction/bonus from position a: on rejection (a < num_draft)
    # the rejected draft token is removed from the support (point-mass
    # residual); on full acceptance p_a = p_K is the bonus distribution
    row_scaled = jnp.take_along_axis(
        scaled, a[:, None, None], axis=1)[:, 0, :]               # [B, V]
    d_at_a = jnp.take_along_axis(
        draft, jnp.clip(a, 0, max(K - 1, 0))[:, None],
        axis=1)[:, 0] if K > 0 else jnp.zeros((B,), jnp.int32)
    rejected = a < num_draft
    excl = (jnp.arange(V, dtype=jnp.int32)[None, :]
            == d_at_a[:, None]) & rejected[:, None]
    corr_sample = jax.random.categorical(
        cat_key, jnp.where(excl, _NEG_INF, row_scaled),
        axis=-1).astype(jnp.int32)
    corr_greedy = jnp.take_along_axis(greedy_tok, a[:, None],
                                      axis=1)[:, 0]
    corr = jnp.where(is_greedy, corr_greedy, corr_sample)        # [B]

    # emitted tokens: accepted drafts then the correction (greedy rows
    # emit argmax everywhere — identical to the accepted drafts on the
    # accepted prefix); past-correction slots repeat it, ignored
    # host-side
    padded_draft = jnp.concatenate(
        [draft, jnp.zeros((B, 1), jnp.int32)], axis=1)           # [B, S]
    emit_steps = jnp.arange(S, dtype=jnp.int32)[None, :]
    out = jnp.where(emit_steps < a[:, None], padded_draft,
                    corr[:, None])
    return {"Out": out, "Accepted": a}
