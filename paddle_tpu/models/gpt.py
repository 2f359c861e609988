"""GPT-style causal language model — the long-context decoder family
(pre-LN transformer decoder + weight-tied LM head + next-token loss).

The reference era's generative model is ERNIE-GEN-class BERT variants;
a causal-attention decoder at long sequence lengths is exactly the
workload its V100 fused attention could not run (O(S^2) scores in HBM)
— here the Pallas flash kernel's causal path (kernels/
flash_attention.py, dead-block skipping over the upper triangle) makes
seq 2048+ trainable on one chip. Static-graph builder in the style of
models/bert.py; shares its TP/SP sharding annotations style.
"""
import numpy as np

from .. import layers
from ..framework import initializer as I
from ..layers import math as M
from ..layers import tensor as T
from ..param_attr import ParamAttr


class GPTConfig:
    def __init__(self, vocab_size=32000, hidden_size=768, num_layers=12,
                 num_heads=12, ffn_size=3072, max_position=2048,
                 dropout=0.1, initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_size = ffn_size
        self.max_position = max_position
        self.dropout = dropout
        self.initializer_range = initializer_range

    @classmethod
    def base(cls):
        return cls()

    def serving(self):
        return GPTServing(self)

    @classmethod
    def tiny(cls):
        # 1 layer: the test suite compiles this config hundreds of
        # times and XLA compile time scales with depth; nothing the
        # tiny tests assert needs a second identical decoder layer
        return cls(vocab_size=128, hidden_size=32, num_layers=1,
                   num_heads=2, ffn_size=64, max_position=64,
                   dropout=0.0)


def _param(cfg, name):
    return ParamAttr(name=name,
                     initializer=I.Normal(0.0, cfg.initializer_range))


def _fc(cfg, x, size, name, act=None):
    return layers.fc(x, size, num_flatten_dims=2, act=act,
                     param_attr=_param(cfg, f"{name}.w_0"),
                     bias_attr=ParamAttr(name=f"{name}.b_0",
                                         initializer=I.Constant(0.0)))


def _ln(cfg, x, name, begin_axis=2):
    return layers.layer_norm(
        x, begin_norm_axis=begin_axis,
        param_attr=ParamAttr(name=f"{name}_scale",
                             initializer=I.Constant(1.0)),
        bias_attr=ParamAttr(name=f"{name}_bias",
                            initializer=I.Constant(0.0)))


def decoder_layer(cfg, x, idx, is_test, kv_cache=None, pos=None):
    """Pre-LN block: x + attn(LN(x)); x + ffn(LN(x)).

    Three attention modes, one set of parameter names (so trained
    params drive every path):

    - ``kv_cache=None`` (training / full-sequence eval): causal attention
      through the flash kernel (upper triangle never computed).
    - ``kv_cache={"mode": "prefill"}``: the same causal attention over
      the length BUCKET, and the fresh float32 ``k`` and ``v``
      ``[B, H, L, D]`` it attended over handed back as they are (the
      pool's scatter cuts them into blocks). Returns ``(x, k, v)``.
    - ``mode: "paged"`` with ``tables`` [B, nblk] int32: the
      block-paged incremental step — k/v caches are a SHARED pool
      ``[num_blocks, H, block_size, D]`` routed through per-row block
      tables (serving/kvpool.py owns the allocator), appended via
      ``paged_kv_cache_write`` and read by the fused
      ``paged_attention`` kernel. Quantized (int8) pools carry
      ``k_scale``/``v_scale`` arrays; an optional ``limit`` [B] int32
      marks how many of the S tokens are real per row (chunked
      prefill's ragged tail — past-limit k/v route to the trash
      block). Returns ``(x, new_pk, new_pv[, new_ks, new_vs])``.
    """
    h = cfg.hidden_size
    n_head, d_head = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    pre = f"decoder_layer_{idx}"

    a = _ln(cfg, x, f"{pre}_pre_att_ln")
    qkv = _fc(cfg, a, 3 * h, f"{pre}_qkv")
    q = T.slice(qkv, axes=[2], starts=[0], ends=[h])
    k = T.slice(qkv, axes=[2], starts=[h], ends=[2 * h])
    v = T.slice(qkv, axes=[2], starts=[2 * h], ends=[3 * h])
    q = T.transpose(T.reshape(q, [0, 0, n_head, d_head]), [0, 2, 1, 3])
    k = T.transpose(T.reshape(k, [0, 0, n_head, d_head]), [0, 2, 1, 3])
    v = T.transpose(T.reshape(v, [0, 0, n_head, d_head]), [0, 2, 1, 3])
    new_k = new_v = None
    new_ks = new_vs = None
    paged = kv_cache is not None and kv_cache.get("mode") == "paged"
    if kv_cache is None:
        ctx = layers.nn.flash_attention(q, k, v, causal=True)
    elif paged:
        tables = kv_cache["tables"]
        limit = kv_cache.get("limit")
        k_sc, v_sc = kv_cache.get("k_scale"), kv_cache.get("v_scale")
        if k_sc is not None:
            new_k, new_ks = layers.nn.paged_kv_cache_write(
                kv_cache["k"], k, tables, pos, scale=k_sc, limit=limit)
            new_v, new_vs = layers.nn.paged_kv_cache_write(
                kv_cache["v"], v, tables, pos, scale=v_sc, limit=limit)
        else:
            new_k = layers.nn.paged_kv_cache_write(
                kv_cache["k"], k, tables, pos, limit=limit)
            new_v = layers.nn.paged_kv_cache_write(
                kv_cache["v"], v, tables, pos, limit=limit)
        ctx = layers.nn.paged_attention(q, new_k, new_v, tables, pos,
                                        k_scale=new_ks, v_scale=new_vs)
    else:
        new_k, new_v = k, v
        ctx = layers.nn.flash_attention(q, k, v, causal=True)
    ctx = T.reshape(T.transpose(ctx, [0, 2, 1, 3]), [0, 0, h])
    attn_out = _fc(cfg, ctx, h, f"{pre}_att_out")
    attn_out = layers.dropout(attn_out, cfg.dropout, is_test=is_test,
                              dropout_implementation="upscale_in_train")
    x = M.elementwise_add(x, attn_out)

    f = _ln(cfg, x, f"{pre}_pre_ffn_ln")
    ffn = _fc(cfg, f, cfg.ffn_size, f"{pre}_ffn_0", act="gelu")
    ffn = _fc(cfg, ffn, h, f"{pre}_ffn_1")
    ffn = layers.dropout(ffn, cfg.dropout, is_test=is_test,
                         dropout_implementation="upscale_in_train")
    out = M.elementwise_add(x, ffn)
    if kv_cache is None:
        return out
    if paged and new_ks is not None:
        return out, new_k, new_v, new_ks, new_vs
    return out, new_k, new_v


def gpt_pretrain(cfg, batch_size, seq_len, is_test=False):
    """Feeds -> next-token LM loss. tokens [B, S] predict tokens[:, 1:]
    (the final position is trained against the padded label)."""
    tokens = T.data("tokens", [batch_size, seq_len], dtype="int32")
    labels = T.data("labels", [batch_size, seq_len], dtype="int32")
    loss_mask = T.data("loss_mask", [batch_size, seq_len],
                       dtype="float32")
    pos_ids = T.data("pos_ids", [batch_size, seq_len], dtype="int32")

    emb = layers.embedding(tokens, size=[cfg.vocab_size, cfg.hidden_size],
                           param_attr=_param(cfg, "word_embedding"))
    pos = layers.embedding(pos_ids, size=[cfg.max_position,
                                          cfg.hidden_size],
                           param_attr=_param(cfg, "pos_embedding"))
    x = M.elementwise_add(emb, pos)
    x = layers.dropout(x, cfg.dropout, is_test=is_test,
                       dropout_implementation="upscale_in_train")
    checkpoints = []
    for i in range(cfg.num_layers):
        x = decoder_layer(cfg, x, i, is_test)
        checkpoints.append(x)
    x = _ln(cfg, x, "final_ln")

    # weight-tied LM head over every position
    word_emb = x.block.program.global_block().var("word_embedding")
    flat = T.reshape(x, [-1, cfg.hidden_size])               # [B*S, H]
    logits = layers.matmul(flat, word_emb, transpose_y=True)  # [B*S, V]
    ce = layers.softmax_with_cross_entropy(
        logits, T.reshape(labels, [-1, 1]))
    w = T.reshape(loss_mask, [-1, 1])
    loss = M.elementwise_div(
        M.reduce_sum(M.elementwise_mul(ce, w)),
        M.elementwise_add(M.reduce_sum(w),
                          T.fill_constant([1], "float32", 1e-9)))
    return {"feeds": [tokens, labels, loss_mask, pos_ids],
            "loss": loss, "checkpoints": checkpoints}


# ---- inference graphs: full-forward logits, prefill, paged decode ----
# (the generation driver over these lives in models/generation.py)

def _tied_next_logits(cfg, x, last_pos):
    """final-LN hidden [B, S, H] -> next-token logits [B, V] at each
    row's own last REAL position (right-padded batches)."""
    x = _ln(cfg, x, "final_ln")
    h = layers.nn.row_gather(x, last_pos)                    # [B, H]
    word_emb = x.block.program.global_block().var("word_embedding")
    return layers.matmul(h, word_emb, transpose_y=True)      # [B, V]


def _tied_span_logits(cfg, x):
    """final-LN hidden [B, S, H] -> next-token logits [B, S, V] at
    EVERY position (the verify step scores all K+1 speculative
    positions in one pass; jnp.matmul broadcasts the 3-D hidden
    against the tied 2-D head)."""
    x = _ln(cfg, x, "final_ln")
    word_emb = x.block.program.global_block().var("word_embedding")
    return layers.matmul(x, word_emb, transpose_y=True)      # [B, S, V]


def gpt_logits(cfg, batch_size=-1, seq_len=-1):
    """Full-sequence forward -> next-token logits (no KV cache): the
    naive-generation baseline and the prefill-parity reference. Feeds:
    tokens [B, S] int32, pos_ids [B, S] int32, last_pos [B] int32 (index
    of each row's last real token)."""
    tokens = T.data("tokens", [batch_size, seq_len], dtype="int32")
    pos_ids = T.data("pos_ids", [batch_size, seq_len], dtype="int32")
    last_pos = T.data("last_pos", [batch_size], dtype="int32")
    emb = layers.embedding(tokens, size=[cfg.vocab_size, cfg.hidden_size],
                           param_attr=_param(cfg, "word_embedding"))
    pos = layers.embedding(pos_ids, size=[cfg.max_position,
                                          cfg.hidden_size],
                           param_attr=_param(cfg, "pos_embedding"))
    x = M.elementwise_add(emb, pos)
    for i in range(cfg.num_layers):
        x = decoder_layer(cfg, x, i, True)
    logits = _tied_next_logits(cfg, x, last_pos)
    return {"feed_names": ["tokens", "pos_ids", "last_pos"],
            "logits": logits}


def gpt_prefill(cfg, kv_dtype="fp32", batch_size=-1, seq_len=-1):
    """Prompt ingestion: one causal forward over the (length-bucketed)
    prompt that also returns every layer's keys and values ``[B, H, S,
    D]`` at the bucket's length ``S``, cast to the dtype a ``kv_dtype``
    pool stores (the cast happens once, where the values are made); the
    pool scatters them into blocks (``KVBlockPool.scatter_prefill``).
    Padded rows hand back garbage beyond their true length: it lands in
    the trash block, or in slots the decode step's per-row position
    mask never attends and later appends overwrite. Fetch ``logits``
    [B, V] (each row's last real position) plus ``cache_k``/``cache_v``."""
    cache_dt = {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype]
    tokens = T.data("tokens", [batch_size, seq_len], dtype="int32")
    pos_ids = T.data("pos_ids", [batch_size, seq_len], dtype="int32")
    last_pos = T.data("last_pos", [batch_size], dtype="int32")
    emb = layers.embedding(tokens, size=[cfg.vocab_size, cfg.hidden_size],
                           param_attr=_param(cfg, "word_embedding"))
    pos = layers.embedding(pos_ids, size=[cfg.max_position,
                                          cfg.hidden_size],
                           param_attr=_param(cfg, "pos_embedding"))
    x = M.elementwise_add(emb, pos)
    cache_k, cache_v = [], []
    for i in range(cfg.num_layers):
        x, k, v = decoder_layer(cfg, x, i, True,
                                kv_cache={"mode": "prefill"})
        cache_k.append(T.cast(k, cache_dt))
        cache_v.append(T.cast(v, cache_dt))
    logits = _tied_next_logits(cfg, x, last_pos)
    return {"feed_names": ["tokens", "pos_ids", "last_pos"],
            "logits": logits, "cache_k": cache_k, "cache_v": cache_v}


def gpt_decode_step_paged(cfg, kv_dtype="fp32", batch_size=-1):
    """ONE block-paged incremental decode step: embed the current token
    at each row's own position, append its k/v into every layer's
    SHARED block pool ``[num_blocks, H, block_size, D]``
    (``serving/kvpool``) through a per-row block table
    (``paged_kv_cache_write``), attend over the row's blocks with the
    fused ``paged_attention`` kernel, emit next-token logits. Rows at
    different positions share this one executable — per-token cost is a
    cache append + read instead of an O(S^2) full recompute. All pool
    dims are dynamic, so one program covers every pool
    size; ``kv_dtype`` picks the cache element type (``int8`` adds the
    per-(block, head, slot) float32 scale pools to the feed/fetch set).

    Feeds: token [B] int32, pos [B] int32, block_tables [B, nblk] int32,
    cache_pk_<i>/cache_pv_<i> pools (+ cache_pks_<i>/cache_pvs_<i> for
    int8). Fetches: logits, then the updated pools in
    ``serving.kvpool.pool_feed_names`` order (``cache_names``)."""
    quantized = kv_dtype == "int8"
    cache_dt = {"fp32": "float32", "bf16": "bfloat16",
                "int8": "int8"}[kv_dtype]
    token = T.data("token", [batch_size], dtype="int32")
    pos = T.data("pos", [batch_size], dtype="int32")
    tables = T.data("block_tables", [batch_size, -1], dtype="int32")
    n_head, d_head = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    emb = layers.embedding(token, size=[cfg.vocab_size, cfg.hidden_size],
                           param_attr=_param(cfg, "word_embedding"))
    pemb = layers.embedding(pos, size=[cfg.max_position, cfg.hidden_size],
                            param_attr=_param(cfg, "pos_embedding"))
    x = M.elementwise_add(emb, pemb)                     # [B, H]
    x = T.reshape(x, [-1, 1, cfg.hidden_size])           # [B, 1, H]
    feed_names = ["token", "pos", "block_tables"]
    pk_out, pv_out, ks_out, vs_out = [], [], [], []
    for i in range(cfg.num_layers):
        pk = T.data(f"cache_pk_{i}", [-1, -1, -1],
                    dtype=cache_dt)
        pv = T.data(f"cache_pv_{i}", [-1, -1, -1],
                    dtype=cache_dt)
        feed_names += [f"cache_pk_{i}", f"cache_pv_{i}"]
        kv_cache = {"k": pk, "v": pv, "mode": "paged", "tables": tables}
        if quantized:
            pks = T.data(f"cache_pks_{i}", [-1, -1, -1],
                         dtype="float32")
            pvs = T.data(f"cache_pvs_{i}", [-1, -1, -1],
                         dtype="float32")
            feed_names += [f"cache_pks_{i}", f"cache_pvs_{i}"]
            kv_cache["k_scale"], kv_cache["v_scale"] = pks, pvs
            x, npk, npv, nks, nvs = decoder_layer(
                cfg, x, i, True, kv_cache=kv_cache, pos=pos)
            ks_out.append(nks)
            vs_out.append(nvs)
        else:
            x, npk, npv = decoder_layer(
                cfg, x, i, True, kv_cache=kv_cache, pos=pos)
        pk_out.append(npk)
        pv_out.append(npv)
    zero = T.fill_constant_batch_size_like(token, [-1], "int32", 0)
    logits = _tied_next_logits(cfg, x, zero)             # S=1: gather at 0
    from ..serving.kvpool import pool_feed_names
    cache_names = pool_feed_names(cfg.num_layers, quantized)
    by_name = {}
    for i in range(cfg.num_layers):
        by_name[f"cache_pk_{i}"] = pk_out[i]
        by_name[f"cache_pv_{i}"] = pv_out[i]
        if quantized:
            by_name[f"cache_pks_{i}"] = ks_out[i]
            by_name[f"cache_pvs_{i}"] = vs_out[i]
    return {"feed_names": feed_names, "logits": logits,
            "cache_names": cache_names,
            "cache_vars": [by_name[n] for n in cache_names]}


def gpt_prefill_chunk_paged(cfg, kv_dtype="fp32", batch_size=-1,
                            chunk_len=-1):
    """ONE chunk of an incremental PAGED prefill (Orca/Sarathi
    continuous scheduling): ingest up to C prompt tokens per row
    directly into the shared block pool, attending each fresh query
    over everything the row has already written (earlier chunks +
    earlier tokens of this chunk). Repeated over a prompt's chunks this
    is the paged analogue of :func:`gpt_prefill`; sized to a decode
    step it interleaves with the decode bank so a long prompt never
    stalls token cadence.

    Feeds: tokens [B, C] int32 (zero-padded past each row's limit),
    pos_ids [B, C] int32 (absolute positions, clipped for padding),
    start_pos [B] int32 (absolute position of each row's FIRST chunk
    token), limit [B] int32 (real tokens in this chunk; past-limit k/v
    route to the trash block), last_idx [B] int32 (chunk index of the
    last real token — logits are only meaningful on a prompt's final
    chunk), block_tables [B, nblk] int32, then the pools. Fetches:
    logits [B, V], then the updated pools in
    ``serving.kvpool.pool_feed_names`` order (``cache_names``)."""
    quantized = kv_dtype == "int8"
    cache_dt = {"fp32": "float32", "bf16": "bfloat16",
                "int8": "int8"}[kv_dtype]
    tokens = T.data("tokens", [batch_size, chunk_len], dtype="int32")
    pos_ids = T.data("pos_ids", [batch_size, chunk_len], dtype="int32")
    start_pos = T.data("start_pos", [batch_size], dtype="int32")
    limit = T.data("limit", [batch_size], dtype="int32")
    last_idx = T.data("last_idx", [batch_size], dtype="int32")
    tables = T.data("block_tables", [batch_size, -1], dtype="int32")
    n_head, d_head = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    emb = layers.embedding(tokens, size=[cfg.vocab_size, cfg.hidden_size],
                           param_attr=_param(cfg, "word_embedding"))
    pemb = layers.embedding(pos_ids, size=[cfg.max_position,
                                           cfg.hidden_size],
                            param_attr=_param(cfg, "pos_embedding"))
    x = M.elementwise_add(emb, pemb)
    feed_names = ["tokens", "pos_ids", "start_pos", "limit", "last_idx",
                  "block_tables"]
    pk_out, pv_out, ks_out, vs_out = [], [], [], []
    for i in range(cfg.num_layers):
        pk = T.data(f"cache_pk_{i}", [-1, -1, -1],
                    dtype=cache_dt)
        pv = T.data(f"cache_pv_{i}", [-1, -1, -1],
                    dtype=cache_dt)
        feed_names += [f"cache_pk_{i}", f"cache_pv_{i}"]
        kv_cache = {"k": pk, "v": pv, "mode": "paged", "tables": tables,
                    "limit": limit}
        if quantized:
            pks = T.data(f"cache_pks_{i}", [-1, -1, -1],
                         dtype="float32")
            pvs = T.data(f"cache_pvs_{i}", [-1, -1, -1],
                         dtype="float32")
            feed_names += [f"cache_pks_{i}", f"cache_pvs_{i}"]
            kv_cache["k_scale"], kv_cache["v_scale"] = pks, pvs
            x, npk, npv, nks, nvs = decoder_layer(
                cfg, x, i, True, kv_cache=kv_cache, pos=start_pos)
            ks_out.append(nks)
            vs_out.append(nvs)
        else:
            x, npk, npv = decoder_layer(
                cfg, x, i, True, kv_cache=kv_cache, pos=start_pos)
        pk_out.append(npk)
        pv_out.append(npv)
    logits = _tied_next_logits(cfg, x, last_idx)
    from ..serving.kvpool import pool_feed_names
    cache_names = pool_feed_names(cfg.num_layers, quantized)
    by_name = {}
    for i in range(cfg.num_layers):
        by_name[f"cache_pk_{i}"] = pk_out[i]
        by_name[f"cache_pv_{i}"] = pv_out[i]
        if quantized:
            by_name[f"cache_pks_{i}"] = ks_out[i]
            by_name[f"cache_pvs_{i}"] = vs_out[i]
    return {"feed_names": feed_names, "logits": logits,
            "cache_names": cache_names,
            "cache_vars": [by_name[n] for n in cache_names]}


def gpt_verify_step_paged(cfg, kv_dtype="fp32", batch_size=-1,
                          span_len=-1):
    """ONE speculative VERIFY step over the shared block pool: score
    S = K+1 positions per row (the current token plus K draft tokens)
    in a single pass, so ``logits[:, i]`` is exactly what a sequential
    decode step would emit after accepting the first i fed tokens.
    Built exactly like a chunked-prefill pass
    (:func:`gpt_prefill_chunk_paged` — same block-table gather, same
    per-row position masks, same trash-block routing for
    past-``limit`` padding) except that logits come back for EVERY
    position, not just the row's last real one. Rejected positions
    leave garbage k/v beyond the accepted point; the caller re-writes
    them on the next step before any mask admits them. ``limit``
    [B] carries each row's real span (k_b drafts + 1), so rows may
    speculate at different depths inside one executable; a row's
    padding positions write to the trash block and its logits there
    are ignored host-side.

    Feeds: tokens [B, S] int32, pos_ids [B, S] int32, start_pos [B]
    int32, limit [B] int32, block_tables [B, nblk] int32, then the
    pools. Fetches: logits [B, S, V], then the updated pools in
    ``serving.kvpool.pool_feed_names`` order (``cache_names``)."""
    quantized = kv_dtype == "int8"
    cache_dt = {"fp32": "float32", "bf16": "bfloat16",
                "int8": "int8"}[kv_dtype]
    tokens = T.data("tokens", [batch_size, span_len], dtype="int32")
    pos_ids = T.data("pos_ids", [batch_size, span_len], dtype="int32")
    start_pos = T.data("start_pos", [batch_size], dtype="int32")
    limit = T.data("limit", [batch_size], dtype="int32")
    tables = T.data("block_tables", [batch_size, -1], dtype="int32")
    n_head, d_head = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    emb = layers.embedding(tokens, size=[cfg.vocab_size, cfg.hidden_size],
                           param_attr=_param(cfg, "word_embedding"))
    pemb = layers.embedding(pos_ids, size=[cfg.max_position,
                                           cfg.hidden_size],
                            param_attr=_param(cfg, "pos_embedding"))
    x = M.elementwise_add(emb, pemb)
    feed_names = ["tokens", "pos_ids", "start_pos", "limit",
                  "block_tables"]
    pk_out, pv_out, ks_out, vs_out = [], [], [], []
    for i in range(cfg.num_layers):
        pk = T.data(f"cache_pk_{i}", [-1, -1, -1],
                    dtype=cache_dt)
        pv = T.data(f"cache_pv_{i}", [-1, -1, -1],
                    dtype=cache_dt)
        feed_names += [f"cache_pk_{i}", f"cache_pv_{i}"]
        kv_cache = {"k": pk, "v": pv, "mode": "paged", "tables": tables,
                    "limit": limit}
        if quantized:
            pks = T.data(f"cache_pks_{i}", [-1, -1, -1],
                         dtype="float32")
            pvs = T.data(f"cache_pvs_{i}", [-1, -1, -1],
                         dtype="float32")
            feed_names += [f"cache_pks_{i}", f"cache_pvs_{i}"]
            kv_cache["k_scale"], kv_cache["v_scale"] = pks, pvs
            x, npk, npv, nks, nvs = decoder_layer(
                cfg, x, i, True, kv_cache=kv_cache, pos=start_pos)
            ks_out.append(nks)
            vs_out.append(nvs)
        else:
            x, npk, npv = decoder_layer(
                cfg, x, i, True, kv_cache=kv_cache, pos=start_pos)
        pk_out.append(npk)
        pv_out.append(npv)
    logits = _tied_span_logits(cfg, x)                   # [B, S, V]
    from ..serving.kvpool import pool_feed_names
    cache_names = pool_feed_names(cfg.num_layers, quantized)
    by_name = {}
    for i in range(cfg.num_layers):
        by_name[f"cache_pk_{i}"] = pk_out[i]
        by_name[f"cache_pv_{i}"] = pv_out[i]
        if quantized:
            by_name[f"cache_pks_{i}"] = ks_out[i]
            by_name[f"cache_pvs_{i}"] = vs_out[i]
    return {"feed_names": feed_names, "logits": logits,
            "cache_names": cache_names,
            "cache_vars": [by_name[n] for n in cache_names]}


# ---- what the serving path asks an architecture for --------------------

class GPTServing:
    """GPT-2's serving programs, the layout of its keys and values in
    the pool and the bytes a prefill hands back: the one place
    ``GPTGenerator``, ``GenerationEngine`` and ``KVBlockPool`` take them
    from (``models/mellum.MellumServing`` is the other architecture's)."""

    name = "gpt"
    kv_dtypes = ("fp32", "bf16", "int8")
    supports_tp = True

    def __init__(self, cfg):
        self.cfg = cfg

    def eager_builders(self, max_len):
        return {"logits": lambda: gpt_logits(self.cfg)}

    def build(self, kind, max_len):
        """The program of a lazily built ``kind`` (the prefill, the
        paged decode step, chunked prefill and the verify steps exist
        per KV-cache dtype and most processes never touch them)."""
        kv_dtype = kind.rsplit("_", 1)[-1]
        if kind.startswith("verify_paged_"):
            return gpt_verify_step_paged(self.cfg, kv_dtype=kv_dtype)
        if kind.startswith("decode_paged_"):
            return gpt_decode_step_paged(self.cfg, kv_dtype=kv_dtype)
        if kind.startswith("prefill_chunk_"):
            return gpt_prefill_chunk_paged(self.cfg, kv_dtype=kv_dtype)
        if kind.startswith("prefill_"):
            return gpt_prefill(self.cfg, kv_dtype=kv_dtype)
        raise KeyError(f"unknown generation program kind {kind!r}")

    def prefill_kind(self, kv_dtype):
        """An int8 pool quantizes in its scatter, from the rows the
        float32 pool's program hands back."""
        return f"prefill_{'fp32' if kv_dtype == 'int8' else kv_dtype}"

    def apply_tp_sharding(self, main):
        apply_tp_sharding(main, self.cfg)

    @property
    def kv_heads(self):
        return self.cfg.num_heads

    @property
    def head_dim(self):
        return self.cfg.hidden_size // self.cfg.num_heads

    def kv_groups(self):
        return [{"name": "full", "window": None,
                 "layers": list(range(self.cfg.num_layers))}]

    def prefill_bytes(self, rows, seq, max_len, kv_elem_bytes):
        """Device bytes one prefill of ``rows`` x ``seq`` holds at its
        peak beyond the weights: every layer's ``[rows, H, seq, D]``
        keys and values in the dtype it hands them back in (the pool's;
        float32 for an int8 pool), once as the prefill's result and
        once more in the scatter that cuts them into blocks, and the
        logits."""
        cfg = self.cfg
        row_elem = 4 if kv_elem_bytes == 1 else int(kv_elem_bytes)
        return int(rows) * (2 * 2 * cfg.num_layers * cfg.hidden_size
                            * int(seq) * row_elem + cfg.vocab_size * 4)


# ---- tensor-parallel sharding annotation (Megatron-style over "tp") ----

def apply_tp_sharding(program, cfg):
    """Same scheme as bert.apply_tp_sharding: QKV and FFN-in split on
    the output dim, attention-out and FFN-out on the input dim — one
    psum per matmul pair per block under GSPMD; the tied LM head rides
    the row-sharded word embedding. Call BEFORE optimizer.minimize():
    accumulators copy the parameter's dist_attr at creation time, so
    annotating afterwards leaves optimizer state replicated."""
    from ..parallel.mesh import set_param_dist_attr as _set
    for i in range(cfg.num_layers):
        pre = f"decoder_layer_{i}"
        _set(program, f"{pre}_qkv.w_0", (None, "tp"))
        _set(program, f"{pre}_qkv.b_0", ("tp",))
        _set(program, f"{pre}_att_out.w_0", ("tp", None))
        _set(program, f"{pre}_ffn_0.w_0", (None, "tp"))
        _set(program, f"{pre}_ffn_0.b_0", ("tp",))
        _set(program, f"{pre}_ffn_1.w_0", ("tp", None))
    _set(program, "word_embedding", ("tp", None))


def random_batch(cfg, batch_size, seq_len, rng=None):
    rng = rng or np.random.default_rng()
    toks = rng.integers(0, cfg.vocab_size,
                        (batch_size, seq_len + 1)).astype(np.int32)
    return {
        "tokens": toks[:, :-1].copy(),
        "labels": toks[:, 1:].copy(),
        "loss_mask": np.ones((batch_size, seq_len), np.float32),
        "pos_ids": np.broadcast_to(
            np.arange(seq_len, dtype=np.int32),
            (batch_size, seq_len)).copy(),
    }
