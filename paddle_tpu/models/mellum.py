"""Mellum-2-class decoder: RMSNorm, rotary positions, grouped-query
attention in window and full layers, and a routed SwiGLU expert layer in
every block (JetBrains ``Mellum2-12B-A2.5B-Instruct``'s ``config.json``;
the config class keeps the source's key names).

One block, for hidden states ``x`` at positions ``p``::

    a = rmsnorm(x); q, k, v = a Wq, a Wk, a Wv         # no bias
    q, k = rope(q, p), rope(k, p)                       # the layer type's table
    x = x + attention(q, k, v) Wo                       # window or full, causal
    f = rmsnorm(x); x = x + routed_experts(f)           # top-k of E, dropless

then a final RMSNorm and an untied head. The matrices (embedding, the
four projections, the experts, the head) are held in ``cfg.dtype``
(bfloat16 when served); their products take operands in that dtype and
accumulate in float32. The residual stream, RMSNorm's statistics, the
rotary tables and the router (logits, softmax, top-k) are float32.

Serving programs (``MellumServing``, what ``GPTGenerator`` asks a
config for): full-sequence logits, prefill (returns every layer's keys
and values at the prompt bucket's length, in the KV pool's dtype) and the
paged decode step over a two-group pool: full layers keep every block of
a row, window layers a ring of ``window_blocks`` blocks. The paths this
block is not built for refuse it with
:class:`models.generation.UnsupportedPathError`.
"""
import math

import numpy as np

from .. import layers
from ..framework import initializer as I
from ..layers import math as M
from ..layers import tensor as T
from ..param_attr import ParamAttr
from .generation import UnsupportedPathError

SLIDING, FULL = "sliding_attention", "full_attention"


class MellumConfig:
    """The keys of the source's ``config.json`` that shape the model,
    under their own names, plus ``dtype`` (what the matrices are held
    in) and ``initializer_range``."""

    def __init__(self, vocab_size=98304, hidden_size=2304,
                 num_hidden_layers=28, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, layer_types=None,
                 sliding_window=1024, num_experts=64,
                 num_experts_per_tok=8, moe_intermediate_size=896,
                 norm_topk_prob=True, rope_parameters=None,
                 rms_norm_eps=1e-6, tie_word_embeddings=False,
                 max_position_embeddings=131072, initializer_range=0.02,
                 dtype="bfloat16"):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        if layer_types is None:
            layer_types = [FULL if i % 4 == 3 else SLIDING
                           for i in range(self.num_hidden_layers)]
        self.layer_types = list(layer_types)
        self.sliding_window = int(sliding_window)
        self.num_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rope_parameters = rope_parameters or {
            FULL: {"rope_type": "default", "rope_theta": 500000},
            SLIDING: {"rope_type": "default", "rope_theta": 500000}}
        self.rms_norm_eps = float(rms_norm_eps)
        self.tie_word_embeddings = bool(tie_word_embeddings)
        self.max_position_embeddings = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        self.dtype = dtype
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types must name every layer")
        if set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"unknown layer type in {self.layer_types}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into the KV heads")
        if self.tie_word_embeddings:
            raise ValueError("the head is untied in this family")

    # what GPTGenerator, the engine and the pool read of any config
    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def num_heads(self):
        return self.num_attention_heads

    @property
    def max_position(self):
        return self.max_position_embeddings

    @classmethod
    def tiny(cls, **over):
        """Both layer kinds, 8 experts top-2, 2 KV heads under 4 query
        heads, a window shorter than a test prompt."""
        kw = dict(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                  layer_types=[SLIDING, FULL], sliding_window=8,
                  num_experts=8, num_experts_per_tok=2,
                  moe_intermediate_size=16, max_position_embeddings=64,
                  rope_parameters={
                      FULL: {"rope_type": "yarn", "rope_theta": 10000,
                             "factor": 4,
                             "original_max_position_embeddings": 16,
                             "beta_fast": 32, "beta_slow": 1,
                             "attention_factor": 1.1386294361119891},
                      SLIDING: {"rope_type": "default",
                                "rope_theta": 10000}},
                  dtype="float32")
        kw.update(over)
        return cls(**kw)

    def serving(self):
        return MellumServing(self)


def rope_inv_freq(cfg, layer_type):
    """``(inv_freq [head_dim / 2], attention_factor)`` of a layer type's
    rotary table: plain ``theta ** (-2i / d)``, or YaRN's blend of those
    frequencies and their ``factor``-fold interpolation between the
    dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
    original context (Peng et al. 2023, as ``transformers`` computes it)."""
    par = cfg.rope_parameters[layer_type]
    d = cfg.head_dim
    base = float(par["rope_theta"])
    pos_freqs = base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if par.get("rope_type", "default") == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if par["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {par['rope_type']!r}")
    factor = float(par["factor"])
    orig = float(par["original_max_position_embeddings"])

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(par["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(par["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    inv_freq = (1.0 / (factor * pos_freqs)) * (1.0 - extrapolation) \
        + (1.0 / pos_freqs) * extrapolation
    attention_factor = par.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(attention_factor)


def _param(cfg, name):
    return ParamAttr(name=name,
                     initializer=I.Normal(0.0, cfg.initializer_range))


def _norm(cfg, x, name):
    return layers.nn.rms_norm(
        x, epsilon=cfg.rms_norm_eps,
        param_attr=ParamAttr(name=f"{name}_scale",
                             initializer=I.Constant(1.0)))


def _proj(cfg, x, size, name):
    return layers.nn.dense_acc32(x, size, dtype=cfg.dtype,
                                 param_attr=_param(cfg, f"{name}.w_0"))


def _embed(cfg, tokens):
    emb = layers.embedding(
        tokens, size=[cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
        param_attr=_param(cfg, "embed_tokens"))
    return T.cast(emb, "float32")


def decoder_layer(cfg, x, idx, pos_ids, kv=None, valid=None):
    """One block over ``x`` [B, S, hidden] at ``pos_ids`` [B, S].

    ``kv=None``: attention over the fed sequence through the flash
    forward (grouped queries, the window where the layer has one);
    returns ``(x, k, v, counts)`` with the rotated keys and the values
    ``[B, Hkv, S, D]`` the prefill hands to the pool. ``kv={"k", "v",
    "tables", "pos"}``: the paged decode step (S = 1) — this token's key
    and value are appended through the layer group's block table (a
    ring in a window layer) and the query reads the pool; returns
    ``(x, new_k_pool, new_v_pool, counts)``. ``counts`` [E] int32 are
    the expert layer's assignments; ``valid`` marks real tokens."""
    pre = f"layer_{idx}"
    kind = cfg.layer_types[idx]
    window = cfg.sliding_window if kind == SLIDING else None
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    inv_freq, factor = rope_inv_freq(cfg, kind)

    def heads(t, n):
        return T.transpose(T.reshape(t, [0, 0, n, d]), [0, 2, 1, 3])

    a = _norm(cfg, x, f"{pre}_input_norm")
    q = heads(_proj(cfg, a, hq * d, f"{pre}_q_proj"), hq)
    k = heads(_proj(cfg, a, hkv * d, f"{pre}_k_proj"), hkv)
    v = heads(_proj(cfg, a, hkv * d, f"{pre}_v_proj"), hkv)
    q = layers.nn.rotary_embedding(q, pos_ids, inv_freq, factor)
    k = layers.nn.rotary_embedding(k, pos_ids, inv_freq, factor)
    scope = "attn/window" if window else "attn/full"
    if kv is None:
        # the products of the flash forward take the matrices' dtype
        qc, k, v = (T.cast(t, cfg.dtype) for t in (q, k, v))
        ctx = layers.nn.flash_attention(qc, k, v, causal=True,
                                        window=window, scope=scope)
        new_k, new_v = k, v
    else:
        new_k = layers.nn.paged_kv_cache_write(
            kv["k"], k, kv["tables"], kv["pos"], ring=bool(window))
        new_v = layers.nn.paged_kv_cache_write(
            kv["v"], v, kv["tables"], kv["pos"], ring=bool(window))
        ctx = layers.nn.paged_attention(q, new_k, new_v, kv["tables"],
                                        kv["pos"], window=window,
                                        scope=scope, kv_heads=hkv)
    ctx = T.reshape(T.transpose(T.cast(ctx, "float32"), [0, 2, 1, 3]),
                    [0, 0, hq * d])
    x = M.elementwise_add(x, _proj(cfg, ctx, cfg.hidden_size,
                                   f"{pre}_o_proj"))

    f = _norm(cfg, x, f"{pre}_post_attn_norm")
    moe, counts = layers.nn.routed_experts(
        f, cfg.num_experts, cfg.num_experts_per_tok,
        cfg.moe_intermediate_size, norm_topk_prob=cfg.norm_topk_prob,
        dtype=cfg.dtype, valid=valid,
        param_attr={"router": _param(cfg, f"{pre}_router.w_0"),
                    "gate": _param(cfg, f"{pre}_experts_gate.w_0"),
                    "up": _param(cfg, f"{pre}_experts_up.w_0"),
                    "down": _param(cfg, f"{pre}_experts_down.w_0")})
    return M.elementwise_add(x, moe), new_k, new_v, counts


def _next_logits(cfg, x, last_pos):
    """Final RMSNorm and the untied head at each row's own last real
    position: [B, S, hidden] -> [B, vocab]."""
    h = layers.nn.row_gather(_norm(cfg, x, "final_norm"), last_pos)
    return _proj(cfg, h, cfg.vocab_size, "lm_head")


def _prompt_feeds(batch_size, seq_len):
    tokens = T.data("tokens", [batch_size, seq_len], dtype="int32")
    pos_ids = T.data("pos_ids", [batch_size, seq_len], dtype="int32")
    last_pos = T.data("last_pos", [batch_size], dtype="int32")
    # right padding routes to no expert
    valid = M.less_equal(pos_ids, layers.nn.unsqueeze(last_pos, [1]))
    return tokens, pos_ids, last_pos, valid


def mellum_logits(cfg, batch_size=-1, seq_len=-1):
    """Full-sequence forward -> next-token logits, no cache: what
    ``Executor`` runs, and the prefill's parity reference. Feeds as
    ``gpt_logits``: tokens, pos_ids [B, S] int32, last_pos [B] int32."""
    tokens, pos_ids, last_pos, valid = _prompt_feeds(batch_size, seq_len)
    x = _embed(cfg, tokens)
    counts = []
    for i in range(cfg.num_hidden_layers):
        x, _, _, c = decoder_layer(cfg, x, i, pos_ids, valid=valid)
        counts.append(c)
    return {"feed_names": ["tokens", "pos_ids", "last_pos"],
            "logits": _next_logits(cfg, x, last_pos),
            "aux": {"moe_counts": T.stack(counts, axis=0)}}


def mellum_prefill(cfg, kv_dtype="bf16", batch_size=-1, seq_len=-1):
    """Prompt ingestion: the forward of :func:`mellum_logits` that also
    returns every layer's keys and values ``[B, Hkv, S, D]`` at the
    bucket's length, cast to the pool's dtype (the pool scatters them
    into blocks: all of them in a full layer, the window's last in a
    window layer), and the ``[layers, experts]`` assignment counts."""
    cache_dt = {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype]
    tokens, pos_ids, last_pos, valid = _prompt_feeds(batch_size, seq_len)
    x = _embed(cfg, tokens)
    cache_k, cache_v, counts = [], [], []
    for i in range(cfg.num_hidden_layers):
        x, k, v, c = decoder_layer(cfg, x, i, pos_ids, valid=valid)
        cache_k.append(T.cast(k, cache_dt))
        cache_v.append(T.cast(v, cache_dt))
        counts.append(c)
    return {"feed_names": ["tokens", "pos_ids", "last_pos"],
            "logits": _next_logits(cfg, x, last_pos),
            "cache_k": cache_k, "cache_v": cache_v,
            "aux": {"moe_counts": T.stack(counts, axis=0)}}


def mellum_decode_step_paged(cfg, kv_dtype="bf16", batch_size=-1):
    """ONE paged decode step over the two-group pool. Feeds: token, pos
    [B] int32, ``block_tables`` [B, nblk] (the full layers' table) and
    ``block_tables_window`` [B, ring] (the window layers' ring), then
    the pools ``cache_pk_<i>`` / ``cache_pv_<i>`` (logically [N_group,
    Hkv, bs, D]; stored and fed as [N_group, Hkv * bs, D]: D = 128
    fills the lanes).
    A row whose full table starts at the trash block is a free slot and
    routes to no expert. Fetches: logits, the updated pools in
    ``serving.kvpool.pool_feed_names`` order, then the counts."""
    from ..serving.kvpool import pool_feed_names
    cache_dt = {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype]
    token = T.data("token", [batch_size], dtype="int32")
    pos = T.data("pos", [batch_size], dtype="int32")
    tables = {FULL: T.data("block_tables", [batch_size, -1], dtype="int32"),
              SLIDING: T.data("block_tables_window", [batch_size, -1],
                              dtype="int32")}
    feed_names = ["token", "pos", "block_tables", "block_tables_window"]
    live = M.not_equal(
        T.slice(tables[FULL], axes=[1], starts=[0], ends=[1]),
        T.fill_constant([1], "int32", 0))                    # [B, 1]
    x = T.reshape(_embed(cfg, token), [-1, 1, cfg.hidden_size])
    pos_ids = T.reshape(pos, [-1, 1])
    hkv, d = cfg.num_key_value_heads, cfg.head_dim
    by_name, counts = {}, []
    for i in range(cfg.num_hidden_layers):
        pk = T.data(f"cache_pk_{i}", [-1, -1, d], dtype=cache_dt)
        pv = T.data(f"cache_pv_{i}", [-1, -1, d], dtype=cache_dt)
        feed_names += [f"cache_pk_{i}", f"cache_pv_{i}"]
        x, npk, npv, c = decoder_layer(
            cfg, x, i, pos_ids, valid=live,
            kv={"k": pk, "v": pv, "pos": pos,
                "tables": tables[cfg.layer_types[i]]})
        by_name[f"cache_pk_{i}"], by_name[f"cache_pv_{i}"] = npk, npv
        counts.append(c)
    zero = T.fill_constant_batch_size_like(token, [-1], "int32", 0)
    cache_names = pool_feed_names(cfg.num_hidden_layers, False)
    return {"feed_names": feed_names,
            "logits": _next_logits(cfg, x, zero),
            "cache_names": cache_names,
            "cache_vars": [by_name[n] for n in cache_names],
            "aux": {"moe_counts": T.stack(counts, axis=0)}}


class MellumServing:
    """What the serving path asks an architecture for: its program
    builders, the layout of its keys and values in the pool, and the
    bytes a prefill hands back (``GPTServing`` is GPT-2's)."""

    name = "mellum"
    supports_tp = False

    def __init__(self, cfg):
        self.cfg = cfg

    def eager_builders(self, max_len):
        return {"logits": lambda: mellum_logits(self.cfg)}

    def build(self, kind, max_len):
        """The program of a lazily built ``kind``; the paths this block
        has no program for raise :class:`UnsupportedPathError`."""
        kv_dtype = kind.rsplit("_", 1)[-1]
        if kind.startswith("prefill_") and not kind.startswith(
                "prefill_chunk_"):
            return mellum_prefill(self.cfg, kv_dtype=kv_dtype)
        if kind.startswith("decode_paged_"):
            return mellum_decode_step_paged(self.cfg, kv_dtype=kv_dtype)
        for prefix, path in (("prefill_chunk", "chunked prefill"),
                             ("verify", "speculative verify")):
            if kind.startswith(prefix):
                raise UnsupportedPathError(self.name, path)
        raise KeyError(f"unknown generation program kind {kind!r}")

    def prefill_kind(self, kv_dtype):
        return f"prefill_{kv_dtype}"

    # -- the pool's geometry
    kv_dtypes = ("fp32", "bf16")

    @property
    def kv_heads(self):
        return self.cfg.num_key_value_heads

    @property
    def head_dim(self):
        return self.cfg.head_dim

    def kv_groups(self):
        """``[{"name", "layers", "window"}]``: the full layers, then the
        window layers."""
        types = self.cfg.layer_types
        groups = [{"name": "full", "window": None,
                   "layers": [i for i, t in enumerate(types) if t == FULL]},
                  {"name": "window", "window": self.cfg.sliding_window,
                   "layers": [i for i, t in enumerate(types)
                              if t == SLIDING]}]
        return [g for g in groups if g["layers"]]

    def prefill_bytes(self, rows, seq, max_len, kv_elem_bytes):
        """Device bytes one prefill of ``rows`` x ``seq`` holds at its
        peak beyond the weights: the keys and values it returns, the
        logits, and the expert layer's sorted rows in and out (the
        largest temporaries: ``k`` copies of every token)."""
        cfg = self.cfg
        tokens = int(rows) * int(seq)
        kv = 2 * cfg.num_hidden_layers * cfg.num_key_value_heads \
            * cfg.head_dim * tokens * kv_elem_bytes
        moe = tokens * cfg.num_experts_per_tok * cfg.hidden_size * (2 + 4 + 4)
        return kv + int(rows) * cfg.vocab_size * 4 + moe


def random_prompt(cfg, length, rng=None):
    rng = rng or np.random.default_rng()
    return rng.integers(1, cfg.vocab_size, int(length)).astype(np.int32)
