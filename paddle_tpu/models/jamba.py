"""Jamba-class hybrid decoder (AI21 ``AI21-Jamba2-3B``'s ``config.json``;
the config class keeps the source's key names): Mamba-1 state-space
layers with an attention layer every ``attn_layer_period``, a SwiGLU MLP
after every mixer, RMSNorm before each sub-layer, no positional encoding
of any kind (the state-space layers carry position) and a head tied to
the embedding.

For token ids ``t``, with ``h = E[t]``::

    for i in range(num_hidden_layers):
        a = rmsnorm_in_i(h)
        if i % attn_layer_period == attn_layer_offset:      # attention
            q, k, v = a [Wq_i | Wk_i | Wv_i]                # no bias or rotary
            K_i, V_i <- append(k, v)
            m = softmax_causal(q K_i^T / sqrt(d)) V_i Wo_i
        else:                                               # Mamba-1
            x, z = split(a Win_i)                           # inner width each
            x = silu(conv_i(x))                             # causal, depthwise
            d, B, C = split(x Wx_i)                         # dt_rank, N, N
            d, B, C = rmsnorm_dt_i(d), rmsnorm_B_i(B), rmsnorm_C_i(C)
            D_t = softplus(d Wdt_i + bdt_i)
            S_t = exp(D_t (x) A_i) * S_{t-1} + (D_t * x_t) (x) B_t
            m = ((S_t C_t + Dskip_i * x_t) * silu(z)) Wout_i
        h = h + m
        f = rmsnorm_ff_i(h)
        h = h + (silu(f Wg_i) * (f Wu_i)) Wd_i
    logits = rmsnorm_final(h)[last] E^T

What a served row keeps between steps is of two kinds: an attention layer
keeps keys and values a position, in blocks under a block table, as every
other block here does. A Mamba layer keeps, whatever the context's length,
the state ``S`` ``[d_state, inner]`` and the convolution's last
``d_conv - 1`` inputs ``[(d_conv - 1) * inner]``: arrays a layer indexed
by SLOT in the pool's state group (``serving/kvpool.py``), float32.

The matrices (embedding, q/k/v/o, the mixer's four projections, the MLP)
are held in ``cfg.dtype`` (bfloat16 when served) and their products
accumulate in float32; the residual stream, RMSNorm's statistics, the
convolution, ``D_t``, the decay, the recurrence and the stored state are
float32. The prefill hands back each row's state and convolution tail
after its last REAL token (``last_pos``), not at the bucket's end.
"""
from .. import layers
from ..framework import initializer as I
from ..layers import math as M
from ..layers import tensor as T
from ..param_attr import ParamAttr
from .generation import UnsupportedPathError
# the same building blocks as the other RMSNorm decoders: a named
# normal(0, initializer_range) matrix, an RMSNorm with a unit gain, a
# product in cfg.dtype accumulated in float32, the embedding look-up
from .mellum import _embed, _norm, _param, _proj

ATTENTION, MAMBA = "attention", "mamba"


class JambaConfig:
    """The keys of the source's ``config.json`` under their own names,
    plus ``dtype`` (what the matrices are held in) and
    ``initializer_range``."""

    def __init__(self, vocab_size=65536, hidden_size=2560,
                 intermediate_size=8192, num_hidden_layers=28,
                 num_attention_heads=20, num_key_value_heads=1,
                 attn_layer_period=14, attn_layer_offset=7,
                 expert_layer_period=2, expert_layer_offset=1,
                 num_experts=1, num_experts_per_tok=1, mamba_expand=2,
                 mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=160,
                 mamba_conv_bias=True, mamba_proj_bias=False,
                 use_mamba_kernels=True, hidden_act="silu",
                 rms_norm_eps=1e-6, sliding_window=None,
                 tie_word_embeddings=True, num_logits_to_keep=1,
                 max_position_embeddings=262144, model_type="jamba",
                 initializer_range=0.02, dtype="bfloat16"):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.attn_layer_period = int(attn_layer_period)
        self.attn_layer_offset = int(attn_layer_offset)
        self.expert_layer_period = int(expert_layer_period)
        self.expert_layer_offset = int(expert_layer_offset)
        self.num_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.mamba_expand = int(mamba_expand)
        self.mamba_d_state = int(mamba_d_state)
        self.mamba_d_conv = int(mamba_d_conv)
        self.mamba_dt_rank = int(mamba_dt_rank)
        self.mamba_conv_bias = bool(mamba_conv_bias)
        self.mamba_proj_bias = bool(mamba_proj_bias)
        self.use_mamba_kernels = bool(use_mamba_kernels)
        self.hidden_act = hidden_act
        self.rms_norm_eps = float(rms_norm_eps)
        self.sliding_window = sliding_window
        self.tie_word_embeddings = bool(tie_word_embeddings)
        self.num_logits_to_keep = int(num_logits_to_keep)
        self.max_position_embeddings = int(max_position_embeddings)
        self.model_type = model_type
        self.initializer_range = float(initializer_range)
        self.dtype = dtype
        if self.hidden_act != "silu":
            raise ValueError("the MLP is silu(gate) * up in this family")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError("heads must divide the hidden size, and "
                             "query heads into the KV heads")
        if self.num_experts != 1:
            # expert_layer_period / _offset then select real layers
            raise ValueError("num_experts > 1: this block's feed-forward "
                             "is one SwiGLU in every layer")
        if not self.mamba_conv_bias or self.mamba_proj_bias:
            raise ValueError("the convolution has a bias and the mixer's "
                             "projections none in this family")
        if not self.tie_word_embeddings:
            raise ValueError("the head is tied to the embedding here")
        if self.sliding_window or self.num_logits_to_keep != 1:
            raise ValueError("no window, and logits at the last position")

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

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def layers_block_type(self):
        """``attention`` where ``i % attn_layer_period ==
        attn_layer_offset``, ``mamba`` elsewhere (the family's code; the
        config has no list)."""
        return [ATTENTION if i % self.attn_layer_period
                == self.attn_layer_offset else MAMBA
                for i in range(self.num_hidden_layers)]

    def layers_of(self, kind):
        return [i for i, t in enumerate(self.layers_block_type)
                if t == kind]

    def serving(self):
        return JambaServing(self)


def _mlp(cfg, x, pre):
    f = _norm(cfg, x, f"{pre}_ff_norm")
    gate = layers.nn.swish(_proj(cfg, f, cfg.intermediate_size,
                                 f"{pre}_gate_proj"))
    up = _proj(cfg, f, cfg.intermediate_size, f"{pre}_up_proj")
    return M.elementwise_add(x, _proj(cfg, M.elementwise_mul(gate, up),
                                      cfg.hidden_size, f"{pre}_down_proj"))


def attention_layer(cfg, x, idx, kv=None):
    """Attention block ``idx`` over ``x`` [B, S, hidden]: grouped
    queries, no rotary. ``kv=None``: causal attention over the fed
    sequence through the flash forward; returns ``(x, k, v)`` with the
    keys and values ``[B, Hkv, S, D]`` the layer's cache takes.
    ``kv={"k", "v", "tables", "pos"}``: the paged decode step (S = 1);
    returns ``(x, new_k_pool, new_v_pool)``."""
    pre = f"layer_{idx}"
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)

    def heads(t, n):
        return T.transpose(T.reshape(t, [0, 0, n, d]), [0, 2, 1, 3])

    a = _norm(cfg, x, f"{pre}_in_norm")
    qkv = _proj(cfg, a, (hq + 2 * hkv) * d, f"{pre}_qkv_proj")
    q, k, v = T.split(qkv, [hq * d, hkv * d, hkv * d], dim=-1)
    q, k, v = heads(q, hq), heads(k, hkv), heads(v, hkv)
    if kv is None:
        # the products of the flash forward take the matrices' dtype
        qc, k, v = (T.cast(t, cfg.dtype) for t in (q, k, v))
        ctx = layers.nn.flash_attention(qc, k, v, causal=True,
                                        scope="attn/full")
        new_k, new_v = k, v
    else:
        new_k = layers.nn.paged_kv_cache_write(
            kv["k"], k, kv["tables"], kv["pos"])
        new_v = layers.nn.paged_kv_cache_write(
            kv["v"], v, kv["tables"], kv["pos"])
        ctx = layers.nn.paged_attention(q, new_k, new_v, kv["tables"],
                                        kv["pos"], scope="attn/full",
                                        kv_heads=hkv)
    ctx = T.reshape(T.transpose(T.cast(ctx, "float32"), [0, 2, 1, 3]),
                    [0, 0, hq * d])
    x = M.elementwise_add(x, _proj(cfg, ctx, cfg.hidden_size,
                                   f"{pre}_o_proj"))
    return _mlp(cfg, x, pre), new_k, new_v


def mamba_layer(cfg, x, idx, length=None, state=None):
    """Mamba block ``idx`` over ``x`` [B, S, hidden]. ``state=None``: a
    row starts from nothing and ``length`` [B] says how many of its
    tokens are real; ``state={"conv", "ssm"}``: a served row's
    convolution tail ``[B, (d_conv - 1) * inner]`` and state ``[B,
    d_state, inner]``, one token a row. Returns ``(x, conv_tail, ssm)``
    as they stand after each row's last real token."""
    pre = f"layer_{idx}"
    inner, n, rank = cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    state = state or {}

    def named(name, init):
        return ParamAttr(name=f"{pre}_{name}", initializer=init)

    a = _norm(cfg, x, f"{pre}_in_norm")
    xi, z = T.split(_proj(cfg, a, 2 * inner, f"{pre}_in_proj"),
                    [inner, inner], dim=-1)
    xc, tail = layers.nn.causal_conv1d(
        xi, cfg.mamba_d_conv, tail=state.get("conv"), length=length,
        param_attr=_param(cfg, f"{pre}_conv.w_0"),
        bias_attr=named("conv.b_0", I.Constant(0.0)))
    dt, b, c = T.split(_proj(cfg, xc, rank + 2 * n, f"{pre}_x_proj"),
                       [rank, n, n], dim=-1)
    delta = _proj(cfg, _norm(cfg, dt, f"{pre}_dt_norm"), inner,
                  f"{pre}_dt_proj")
    y, ssm = layers.nn.selective_scan(
        xc, delta, z, _norm(cfg, b, f"{pre}_b_norm"),
        _norm(cfg, c, f"{pre}_c_norm"), state=state.get("ssm"),
        length=length,
        param_attr={"a_log": named("a_log", I.Constant(0.0)),
                    "d": named("d", I.Constant(1.0)),
                    "dt_bias": named("dt_bias", I.Constant(0.0))})
    x = M.elementwise_add(x, _proj(cfg, y, cfg.hidden_size,
                                   f"{pre}_out_proj"))
    return _mlp(cfg, x, pre), tail, ssm


def _next_logits(cfg, x, last_pos):
    """Final RMSNorm and the tied head at each row's own last real
    position: [B, S, hidden] -> [B, vocab]."""
    h = layers.nn.row_gather(_norm(cfg, x, "final_norm"), last_pos)
    table = x.block.program.global_block().var("embed_tokens")
    return layers.nn.dense_acc32_nt(h, table)


def _prompt_feeds(batch_size, seq_len):
    tokens = T.data("tokens", [batch_size, seq_len], dtype="int32")
    # fed by every caller and read by nothing: no layer takes a position
    T.data("pos_ids", [batch_size, seq_len], dtype="int32")
    last_pos = T.data("last_pos", [batch_size], dtype="int32")
    length = M.elementwise_add(last_pos, T.fill_constant([1], "int32", 1))
    return tokens, last_pos, length


def _forward(cfg, batch_size, seq_len):
    """The stack over a fed sequence: ``(logits, keys, values, tails,
    states)``, a list an attention layer and a list a Mamba layer."""
    tokens, last_pos, length = _prompt_feeds(batch_size, seq_len)
    x = _embed(cfg, tokens)
    keys, values, tails, states = [], [], [], []
    for i, kind in enumerate(cfg.layers_block_type):
        if kind == ATTENTION:
            x, k, v = attention_layer(cfg, x, i)
            keys.append(k)
            values.append(v)
        else:
            x, tail, ssm = mamba_layer(cfg, x, i, length=length)
            tails.append(tail)
            states.append(ssm)
    return _next_logits(cfg, x, last_pos), keys, values, tails, states


def jamba_logits(cfg, batch_size=-1, seq_len=-1):
    """Full-sequence forward -> next-token logits, no cache: what
    ``Executor`` runs, and the prefill's parity reference. Feeds as
    ``gpt_logits``: tokens, pos_ids [B, S] int32, last_pos [B] int32."""
    logits = _forward(cfg, batch_size, seq_len)[0]
    return {"feed_names": ["tokens", "pos_ids", "last_pos"],
            "logits": logits}


def jamba_prefill(cfg, kv_dtype="bf16", batch_size=-1, seq_len=-1):
    """Prompt ingestion: the forward of :func:`jamba_logits` that also
    returns each attention layer's keys and values ``[B, Hkv, S, D]`` at
    the bucket's length in the pool's dtype (``cache_k`` / ``cache_v``,
    a cache layer an attention layer) and each Mamba layer's convolution
    tail and state after the row's last real token (``cache_state``,
    under the pool's own names)."""
    from ..serving.kvpool import state_array_specs
    cache_dt = {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype]
    logits, keys, values, tails, states = _forward(cfg, batch_size, seq_len)
    names = list(state_array_specs(cfg.serving().kv_groups()))
    return {"feed_names": ["tokens", "pos_ids", "last_pos"],
            "logits": logits,
            "cache_k": [T.cast(k, cache_dt) for k in keys],
            "cache_v": [T.cast(v, cache_dt) for v in values],
            "cache_state": dict(zip(names, tails + states))}


def jamba_decode_step_paged(cfg, kv_dtype="bf16", batch_size=-1):
    """ONE decode step over the whole slot bank. Feeds: token, pos [B]
    int32, ``block_tables`` [B, nblk] (the attention layers' table), the
    pools ``cache_pk_<a>`` / ``cache_pv_<a>`` of attention layer ``a``
    (stored ``[N, Hkv * bs, D]``) and, a Mamba layer ``m``, the slot
    bank's ``cache_sc_<m>`` ``[B, (d_conv - 1) * inner]`` and
    ``cache_ss_<m>`` ``[B, d_state, inner]``: row ``b`` of the step IS
    slot ``b``. Fetches: logits, then every array updated, in
    ``KVBlockPool.feed_names()`` order."""
    from ..serving.kvpool import pool_feed_names, state_array_specs
    cache_dt = {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype]
    token = T.data("token", [batch_size], dtype="int32")
    pos = T.data("pos", [batch_size], dtype="int32")
    tables = T.data("block_tables", [batch_size, -1], dtype="int32")
    inner, n, d = cfg.mamba_inner, cfg.mamba_d_state, cfg.head_dim
    x = T.reshape(_embed(cfg, token), [-1, 1, cfg.hidden_size])
    new, a, m = {}, 0, 0
    for i, kind in enumerate(cfg.layers_block_type):
        if kind == ATTENTION:
            pk = T.data(f"cache_pk_{a}", [-1, -1, d], dtype=cache_dt)
            pv = T.data(f"cache_pv_{a}", [-1, -1, d], dtype=cache_dt)
            x, new[f"cache_pk_{a}"], new[f"cache_pv_{a}"] = attention_layer(
                cfg, x, i, kv={"k": pk, "v": pv, "tables": tables,
                               "pos": pos})
            a += 1
        else:
            conv = T.data(f"cache_sc_{m}",
                          [batch_size, (cfg.mamba_d_conv - 1) * inner],
                          dtype="float32")
            ssm = T.data(f"cache_ss_{m}", [batch_size, n, inner],
                         dtype="float32")
            x, new[f"cache_sc_{m}"], new[f"cache_ss_{m}"] = mamba_layer(
                cfg, x, i, state={"conv": conv, "ssm": ssm})
            m += 1
    cache_names = pool_feed_names(a, False) \
        + list(state_array_specs(cfg.serving().kv_groups()))
    zero = T.fill_constant_batch_size_like(token, [-1], "int32", 0)
    return {"feed_names": ["token", "pos", "block_tables"] + cache_names,
            "logits": _next_logits(cfg, x, zero),
            "cache_names": cache_names,
            "cache_vars": [new[name] for name in cache_names]}


class JambaServing:
    """What the serving path asks an architecture for (``GPTServing``,
    ``MellumServing`` and ``OuroServing`` are the other three): its
    program builders, the layout of what a row keeps in the pool, and
    the bytes a prefill holds. The pool gets two groups: the attention
    layers' keys and values in blocks, and the Mamba layers' per-slot
    state."""

    name = "jamba"
    supports_tp = False
    kv_dtypes = ("fp32", "bf16")
    # a shared prefix or a moved row would need a snapshot of the state
    # at the cut, which nothing takes yet: KVBlockPool turns the prefix
    # cache off and refuses export / import for a pool with a state group

    def __init__(self, cfg):
        self.cfg = cfg

    def eager_builders(self, max_len):
        return {"logits": lambda: jamba_logits(self.cfg)}

    def build(self, kind, max_len):
        """The program of a lazily built ``kind``; the paths this block
        has no program for raise :class:`UnsupportedPathError`."""
        kv_dtype = kind.rsplit("_", 1)[-1]
        if kind.startswith("prefill_") and not kind.startswith(
                "prefill_chunk_"):
            return jamba_prefill(self.cfg, kv_dtype=kv_dtype)
        if kind.startswith("decode_paged_"):
            return jamba_decode_step_paged(self.cfg, kv_dtype=kv_dtype)
        for prefix, path in (("prefill_chunk", "chunked prefill"),
                             ("verify", "speculative verify")):
            if kind.startswith(prefix):
                raise UnsupportedPathError(self.name, path)
        raise KeyError(f"unknown generation program kind {kind!r}")

    def prefill_kind(self, kv_dtype):
        return f"prefill_{kv_dtype}"

    # -- the pool's geometry
    @property
    def kv_heads(self):
        return self.cfg.num_key_value_heads

    @property
    def head_dim(self):
        return self.cfg.head_dim

    def kv_groups(self):
        """The attention layers' cache layers (0, 1, ... in layer order)
        as the ``full`` group, and the Mamba layers' state group: for
        each of its layers the arrays a SLOT keeps, ``tag -> (shape,
        dtype)``, fed as ``cache_s<tag>_<m>``."""
        cfg = self.cfg
        inner = cfg.mamba_inner
        return [
            {"name": "full", "window": None,
             "layers": list(range(len(cfg.layers_of(ATTENTION))))},
            {"name": "state", "state": True,
             "layers": list(range(len(cfg.layers_of(MAMBA)))),
             "arrays": {
                 "c": (((cfg.mamba_d_conv - 1) * inner,), "float32"),
                 "s": ((cfg.mamba_d_state, inner), "float32")}}]

    def prefill_bytes(self, rows, seq, max_len, kv_elem_bytes):
        """Device bytes one prefill of ``rows`` x ``seq`` holds at its
        peak beyond the weights: what it returns (keys and values of the
        attention layers, every Mamba layer's tail and state, the
        logits) and the widest rows alive at once, which are a Mamba
        mixer's (the inner projection and its two halves, the
        convolved x, the step, the scan's result, B and C repeated along
        the lanes: float32 all) or the MLP's (gate, up and their product
        in float32, the product again in the matrices' dtype)."""
        cfg = self.cfg
        tokens, inner = int(rows) * int(seq), cfg.mamba_inner
        kv = 2 * len(cfg.layers_of(ATTENTION)) * cfg.num_key_value_heads \
            * cfg.head_dim * tokens * kv_elem_bytes
        state = len(cfg.layers_of(MAMBA)) * int(rows) * 4 * inner \
            * (cfg.mamba_d_conv - 1 + cfg.mamba_d_state)
        mixer = tokens * 4 * (7 * inner + 2 * cfg.mamba_d_state * 128)
        mlp = tokens * cfg.intermediate_size * (4 + 4 + 4 + 2)
        return kv + state + int(rows) * cfg.vocab_size * 4 \
            + max(mixer, mlp)
