"""Xing-4.0-class decoder (XingChen-AGI ``Xing4.0-29B-A4B``'s
``config.json``; the config class keeps the source's key names): latent
attention (MLA) over a latent cache, a residual state of ``hc_mult``
streams mixed by manifold-constrained hyper-connections (mHC), two
leading dense SwiGLU layers, then expert layers of sigmoid-routed top-k
of ``n_routed_experts`` beside one shared expert, and an untied head.

For token ids ``t``, with the residual state ``x`` of ``n = hc_mult``
streams of width C (``ops/hc_ops.py`` has the mix)::

    x = [E[t]] * n                                      # the embedding copied
    for i in range(num_hidden_layers):
        for F in (attention_i, ffn_i):
            h, post, res = hc_pre(x)                    # mHC mix weights
            x = res x + post^T F(h)
    logits = rmsnorm(hc_head(x)) W_head

    attention(h):                                       # MLA
        a = rmsnorm(h); c_q = rmsnorm(a W_qa)
        [q_nope (dn) | q_pe (R)] a head = c_q W_qb
        [c_kv (C_kv) | k_pe (R)] = a W_kva; c_kv = rmsnorm(c_kv)
        q_pe, k_pe = yarn_rope(q_pe), yarn_rope(k_pe)   # k_pe shared by heads
        [k_nope (dn) | v (dv)] a head = c_kv W_kvb
        o = softmax_causal([q_nope | q_pe] . [k_nope | k_pe] * scale) v W_o
    ffn(h) = swiglu(rmsnorm(h))                         # layers < first_k_dense_replace
           = sum_top-k w_e E_e(f) + Shared(f)           # f = rmsnorm(h)

A position keeps, a layer, ``c_kv`` after its norm and ``k_pe`` after
its rotation: ``kv_lora_rank + qk_rope_head_dim`` values, nothing a head
(the pool's latent group). The prefill expands the latent into every
head's keys and values and runs the flash forward (keys ``dn + R``
wide, values ``dv``); the decode step absorbs ``W_kvb`` into the query
and the output and reads the latent pool through the latent decode
kernel (``ops/mla_ops.py``). ``scale = mscale^2 / sqrt(dn + R)``, with
YaRN's ``mscale = 0.1 * mscale_all_dim * ln(factor) + 1``.

The matrices (embedding, the attention's five projections, the dense
MLP, the experts and the shared expert, the head) are held in
``cfg.dtype`` (bfloat16 when served) and their products accumulate in
float32. The residual streams, the mHC mix (its ``phi``, ``alpha`` and
``bias``, the Sinkhorn), RMSNorm's statistics, the rotary tables and the
router (logits, sigmoid, selection bias, top-k) are float32; the latent
cache takes the pool's dtype.
"""
import types

from .. import layers
from ..framework import initializer as I
from ..layers import math as M
from ..layers import tensor as T
from ..param_attr import ParamAttr
from .generation import UnsupportedPathError
from .mellum import _embed, _norm, _param, _proj, rope_inv_freq


class XingConfig:
    """The keys of the source's ``config.json`` that shape the model,
    under their own names, plus ``dtype`` (what the matrices are held
    in) and ``initializer_range``. ``num_nextn_predict_layers`` (1
    published) defaults to 0: the multi-token prediction module is not
    built."""

    def __init__(self, vocab_size=131072, hidden_size=3584,
                 intermediate_size=9216, moe_intermediate_size=1024,
                 num_hidden_layers=40, num_nextn_predict_layers=0,
                 num_attention_heads=32, num_key_value_heads=32,
                 q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=64,
                 n_shared_experts=1, num_experts_per_tok=4,
                 first_k_dense_replace=2, moe_layer_freq=1,
                 norm_topk_prob=True, routed_scaling_factor=2.0,
                 scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
                 topk_group=1, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
                 mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0,
                 rms_norm_eps=1e-6, rope_theta=10000, rope_scaling=None,
                 max_position_embeddings=262144, hidden_act="silu",
                 attention_bias=False, tie_word_embeddings=False,
                 initializer_range=0.02, dtype="bfloat16"):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_nextn_predict_layers = int(num_nextn_predict_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.q_lora_rank = int(q_lora_rank)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.n_routed_experts = int(n_routed_experts)
        self.n_shared_experts = int(n_shared_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.moe_layer_freq = int(moe_layer_freq)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.scoring_func = scoring_func
        self.topk_method = topk_method
        self.n_group = int(n_group)
        self.topk_group = int(topk_group)
        self.hc_mult = int(hc_mult)
        self.hc_sinkhorn_iters = int(hc_sinkhorn_iters)
        self.hc_eps = float(hc_eps)
        self.mhc_h_res_clamp_min = float(mhc_h_res_clamp_min)
        self.mhc_h_res_clamp_max = float(mhc_h_res_clamp_max)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling or {
            "type": "yarn", "factor": 64, "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096, "beta_fast": 32,
            "beta_slow": 1})
        self.max_position_embeddings = int(max_position_embeddings)
        self.hidden_act = hidden_act
        self.attention_bias = bool(attention_bias)
        self.tie_word_embeddings = bool(tie_word_embeddings)
        self.initializer_range = float(initializer_range)
        self.dtype = dtype
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc" \
                or self.n_group != 1 or self.topk_group != 1:
            raise ValueError("this block routes by sigmoid scores and a "
                             "selection bias over one group (noaux_tc)")
        if self.hidden_act != "silu" or self.attention_bias \
                or self.tie_word_embeddings or self.moe_layer_freq != 1 \
                or self.n_shared_experts != 1:
            raise ValueError("silu experts, no attention bias, an untied "
                             "head, an expert layer in every layer past "
                             "the dense ones, one shared expert")
        if self.num_nextn_predict_layers:
            raise ValueError("the multi-token prediction module is not "
                             "built: num_nextn_predict_layers must be 0")
        if self.rope_scaling.get("type", "yarn") != "yarn":
            raise ValueError("the rotary table is YaRN's")

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

    def is_moe(self, layer):
        return layer >= self.first_k_dense_replace

    @classmethod
    def tiny(cls, **over):
        """One dense layer and two expert layers (8 experts, top 2), 4
        heads, a latent of one 128-lane row (what the decode kernel
        reads) and a rotary key of 8, 4 streams, a YaRN table whose
        original context is shorter than a test prompt."""
        kw = dict(vocab_size=128, hidden_size=32, intermediate_size=48,
                  moe_intermediate_size=16, num_hidden_layers=3,
                  num_attention_heads=4, num_key_value_heads=4,
                  q_lora_rank=24, kv_lora_rank=128, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=8,
                  num_experts_per_tok=2, first_k_dense_replace=1,
                  max_position_embeddings=64,
                  rope_scaling={"type": "yarn", "factor": 4, "mscale": 1,
                                "mscale_all_dim": 1,
                                "original_max_position_embeddings": 16,
                                "beta_fast": 32, "beta_slow": 1},
                  dtype="float32")
        kw.update(over)
        return cls(**kw)

    def rope(self):
        """``(inv_freq [R / 2], cos/sin factor, softmax scale)``: YaRN's
        table for the rotary key's R lanes, cos and sin scaled by
        ``mscale`` over ``mscale_all_dim`` (1 where the two are equal),
        and ``mscale_all_dim``'s ``mscale^2 / sqrt(dn + R)``."""
        import math
        par = self.rope_scaling
        ratio = _yarn_mscale(par["factor"], par.get("mscale", 1)) \
            / _yarn_mscale(par["factor"], par.get("mscale_all_dim", 1))
        inv_freq, _ = rope_inv_freq(types.SimpleNamespace(
            head_dim=self.qk_rope_head_dim,
            rope_parameters={"yarn": {
                "rope_type": "yarn", "rope_theta": self.rope_theta,
                "factor": par["factor"], "attention_factor": ratio,
                "original_max_position_embeddings":
                    par["original_max_position_embeddings"],
                "beta_fast": par["beta_fast"],
                "beta_slow": par["beta_slow"]}}), "yarn")
        m = _yarn_mscale(par["factor"], par.get("mscale_all_dim", 1))
        scale = m * m / math.sqrt(self.qk_nope_head_dim
                                  + self.qk_rope_head_dim)
        return inv_freq, ratio, scale

    def serving(self):
        return XingServing(self)


def _yarn_mscale(factor, mscale):
    import math
    return 0.1 * float(mscale) * math.log(float(factor)) + 1.0 \
        if float(factor) > 1 else 1.0


def _matrix(cfg, name, shape):
    return T.create_parameter(shape, cfg.dtype,
                              attr=_param(cfg, f"{name}.w_0"))


def _hc(cfg, x, name):
    """The mix a sublayer ``name`` reads: ``(h, post, res)``."""
    def attr(key, init):
        return ParamAttr(name=f"{name}_{key}", initializer=init)

    with_std = I.Normal(0.0, cfg.initializer_range)
    return layers.nn.hc_pre(
        x, cfg.hc_mult, epsilon=cfg.rms_norm_eps, hc_eps=cfg.hc_eps,
        iters=cfg.hc_sinkhorn_iters,
        clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
        param_attr={"phi": attr("phi", with_std),
                    "alpha": attr("alpha", I.Constant(0.1)),
                    "bias": attr("bias", I.Constant(0.0))})


def _swiglu(cfg, f, width, pre):
    gate = layers.nn.swish(_proj(cfg, f, width, f"{pre}_gate_proj"))
    up = _proj(cfg, f, width, f"{pre}_up_proj")
    return _proj(cfg, M.elementwise_mul(gate, up), cfg.hidden_size,
                 f"{pre}_down_proj")


def attention(cfg, h, idx, pos_ids, kv=None):
    """MLA sublayer ``idx`` over ``h`` [B, S, hidden]. ``kv=None``:
    causal attention over the fed sequence, expanded; returns ``(out,
    c_kv [B, 1, S, C_kv], k_pe [B, 1, S, R])``, what a position's cache
    keeps (float32). ``kv={"c", "r", "tables", "pos"}``: the paged
    decode step (S = 1) over the layer's latent pool arrays; this
    token's latent and rotary key are appended through the table first.
    Returns ``(out, new_c_pool, new_r_pool)``."""
    pre = f"layer_{idx}"
    H, dn, R = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                cfg.qk_rope_head_dim)
    C, dv = cfg.kv_lora_rank, cfg.v_head_dim
    inv_freq, factor, scale = cfg.rope()

    def heads(t, n, d):
        return T.transpose(T.reshape(t, [0, 0, n, d]), [0, 2, 1, 3])

    a = _norm(cfg, h, f"{pre}_input_norm")
    c_q = _norm(cfg, _proj(cfg, a, cfg.q_lora_rank, f"{pre}_q_a_proj"),
                f"{pre}_q_a_norm")
    q = heads(_proj(cfg, c_q, H * (dn + R), f"{pre}_q_b_proj"), H, dn + R)
    q_nope, q_pe = T.split(q, [dn, R], dim=-1)
    c_kv, k_pe = T.split(_proj(cfg, a, C + R, f"{pre}_kv_a_proj"), [C, R],
                         dim=-1)
    c_kv = _norm(cfg, c_kv, f"{pre}_kv_a_norm")
    q_pe = layers.nn.rotary_embedding(q_pe, pos_ids, inv_freq, factor)
    k_pe = layers.nn.rotary_embedding(heads(k_pe, 1, R), pos_ids, inv_freq,
                                      factor)
    w_kvb = _matrix(cfg, f"{pre}_kv_b_proj", [C, H * (dn + dv)])
    if kv is None:
        ctx = layers.nn.mla_prefill(q_nope, q_pe, c_kv, k_pe, w_kvb, dv,
                                    scale, scope="mla/prefill")
        new_c, new_r = layers.nn.unsqueeze(c_kv, [1]), k_pe
    else:
        new_c = layers.nn.paged_kv_cache_write(
            kv["c"], layers.nn.unsqueeze(c_kv, [1]), kv["tables"],
            kv["pos"])
        new_r = layers.nn.paged_kv_cache_write(kv["r"], k_pe, kv["tables"],
                                               kv["pos"])
        ctx = layers.nn.mla_decode(q_nope, q_pe, w_kvb, new_c, new_r,
                                   kv["tables"], kv["pos"], dv, scale,
                                   scope="mla/decode")
    out = _proj(cfg, ctx, cfg.hidden_size, f"{pre}_o_proj")
    return out, new_c, new_r


def ffn(cfg, h, idx, valid=None):
    """Feed-forward sublayer ``idx`` over ``h``: ``(out, counts)``, the
    expert layer's ``[E]`` assignments (None in a dense layer)."""
    pre = f"layer_{idx}"
    f = _norm(cfg, h, f"{pre}_post_attn_norm")
    if not cfg.is_moe(idx):
        return _swiglu(cfg, f, cfg.intermediate_size, pre), None
    routed, counts = layers.nn.routed_experts(
        f, cfg.n_routed_experts, cfg.num_experts_per_tok,
        cfg.moe_intermediate_size, norm_topk_prob=cfg.norm_topk_prob,
        dtype=cfg.dtype, valid=valid, scoring_func="sigmoid",
        routed_scaling_factor=cfg.routed_scaling_factor,
        param_attr={"router": _param(cfg, f"{pre}_router.w_0"),
                    "e_bias": ParamAttr(name=f"{pre}_router_e_bias",
                                        initializer=I.Constant(0.0)),
                    "gate": _param(cfg, f"{pre}_experts_gate.w_0"),
                    "up": _param(cfg, f"{pre}_experts_up.w_0"),
                    "down": _param(cfg, f"{pre}_experts_down.w_0")})
    shared = _swiglu(cfg, f, cfg.moe_intermediate_size, f"{pre}_shared")
    return M.elementwise_add(routed, shared), counts


def decoder_layer(cfg, x, idx, pos_ids, kv=None, valid=None):
    """One block over the flat streams ``x`` [B, S, n * hidden]: returns
    ``(x, cache_c, cache_r, counts)`` (see :func:`attention`)."""
    pre = f"layer_{idx}"
    h, post, res = _hc(cfg, x, f"{pre}_hc_attn")
    out, new_c, new_r = attention(cfg, h, idx, pos_ids, kv)
    x = layers.nn.hc_post(x, out, post, res)
    h, post, res = _hc(cfg, x, f"{pre}_hc_ffn")
    out, counts = ffn(cfg, h, idx, valid)
    return layers.nn.hc_post(x, out, post, res), new_c, new_r, counts


def _streams(cfg, tokens):
    """The embedding copied into the ``hc_mult`` streams, flat."""
    e = _embed(cfg, tokens)
    return T.concat([e] * cfg.hc_mult, axis=-1)


def _next_logits(cfg, x, last_pos):
    """Head mix, final RMSNorm and the untied head at each row's own
    last real position: [B, S, n * hidden] -> [B, vocab]."""
    h = layers.nn.hc_head(
        layers.nn.row_gather(x, last_pos), cfg.hc_mult,
        epsilon=cfg.rms_norm_eps,
        param_attr={"phi": ParamAttr(
            name="hc_head_phi",
            initializer=I.Normal(0.0, cfg.initializer_range)),
            "alpha": ParamAttr(name="hc_head_alpha",
                               initializer=I.Constant(0.1)),
            "bias": ParamAttr(name="hc_head_bias",
                              initializer=I.Constant(0.0))})
    return _proj(cfg, _norm(cfg, h, "final_norm"), cfg.vocab_size,
                 "lm_head")


def _prompt_feeds(batch_size, seq_len):
    tokens = T.data("tokens", [batch_size, seq_len], dtype="int32")
    pos_ids = T.data("pos_ids", [batch_size, seq_len], dtype="int32")
    last_pos = T.data("last_pos", [batch_size], dtype="int32")
    # right padding routes to no expert
    valid = M.less_equal(pos_ids, layers.nn.unsqueeze(last_pos, [1]))
    return tokens, pos_ids, last_pos, valid


def _forward(cfg, batch_size, seq_len):
    tokens, pos_ids, last_pos, valid = _prompt_feeds(batch_size, seq_len)
    x = _streams(cfg, tokens)
    caches, counts = [], []
    for i in range(cfg.num_hidden_layers):
        x, c, r, n = decoder_layer(cfg, x, i, pos_ids, valid=valid)
        caches.append((c, r))
        if n is not None:
            counts.append(n)
    return _next_logits(cfg, x, last_pos), caches, counts


def xing_logits(cfg, batch_size=-1, seq_len=-1):
    """Full-sequence forward -> next-token logits, no cache: what
    ``Executor`` runs, and the prefill's parity reference. Feeds as
    ``gpt_logits``: tokens, pos_ids [B, S] int32, last_pos [B] int32."""
    logits, _, counts = _forward(cfg, batch_size, seq_len)
    return {"feed_names": ["tokens", "pos_ids", "last_pos"],
            "logits": logits,
            "aux": {"moe_counts": T.stack(counts, axis=0)}}


def xing_prefill(cfg, kv_dtype="bf16", batch_size=-1, seq_len=-1):
    """Prompt ingestion: the forward of :func:`xing_logits` that also
    returns every layer's latent ``cache_c_<i>`` [B, 1, S, C_kv] and
    rotary key ``cache_r_<i>`` [B, 1, S, R] at the bucket's length, in
    the pool's dtype (``cache_state``, under the names the pool's
    scatter reads), and the ``[expert layers, experts]`` counts."""
    cache_dt = {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype]
    logits, caches, counts = _forward(cfg, batch_size, seq_len)
    state = {f"cache_c_{i}": T.cast(c, cache_dt)
             for i, (c, _) in enumerate(caches)}
    state.update({f"cache_r_{i}": T.cast(r, cache_dt)
                  for i, (_, r) in enumerate(caches)})
    return {"feed_names": ["tokens", "pos_ids", "last_pos"],
            "logits": logits, "cache_k": [], "cache_v": [],
            "cache_state": state,
            "aux": {"moe_counts": T.stack(counts, axis=0)}}


def xing_decode_step_paged(cfg, kv_dtype="bf16", batch_size=-1):
    """ONE paged decode step over the latent pool. Feeds: token, pos [B]
    int32, ``block_tables`` [B, nblk], then each layer's latents
    ``cache_pc_<i>`` [N, bs, C_kv] and rotary keys ``cache_pr_<i>`` (the
    pool's stored shape), in ``KVBlockPool.feed_names()`` order. A row
    whose table starts at the trash block is a free slot and routes to
    no expert. Fetches: logits, the updated pool arrays in that order,
    then the counts."""
    from ..serving.kvpool import latent_feed_names
    cache_dt = {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype]
    token = T.data("token", [batch_size], dtype="int32")
    pos = T.data("pos", [batch_size], dtype="int32")
    tables = T.data("block_tables", [batch_size, -1], dtype="int32")
    live = M.not_equal(T.slice(tables, axes=[1], starts=[0], ends=[1]),
                       T.fill_constant([1], "int32", 0))       # [B, 1]
    x = T.reshape(_streams(cfg, token),
                  [-1, 1, cfg.hc_mult * cfg.hidden_size])
    pos_ids = T.reshape(pos, [-1, 1])
    names = latent_feed_names(cfg.num_hidden_layers, ("c", "r"))
    new, counts = {}, []
    for i in range(cfg.num_hidden_layers):
        pc = T.data(f"cache_pc_{i}", [-1, -1, cfg.kv_lora_rank],
                    dtype=cache_dt)
        pr = T.data(f"cache_pr_{i}", [-1, -1, -1], dtype=cache_dt)
        x, new[f"cache_pc_{i}"], new[f"cache_pr_{i}"], n = decoder_layer(
            cfg, x, i, pos_ids, valid=live,
            kv={"c": pc, "r": pr, "tables": tables, "pos": pos})
        if n is not None:
            counts.append(n)
    zero = T.fill_constant_batch_size_like(token, [-1], "int32", 0)
    return {"feed_names": ["token", "pos", "block_tables"] + names,
            "logits": _next_logits(cfg, x, zero),
            "cache_names": names,
            "cache_vars": [new[n] for n in names],
            "aux": {"moe_counts": T.stack(counts, axis=0)}}


class XingServing:
    """What the serving path asks an architecture for (``GPTServing``,
    ``MellumServing``, ``OuroServing`` and ``JambaServing`` are the
    others): its program builders, the layout of what a row keeps in the
    pool (one latent group: a latent and a rotary key a position a
    layer), and the bytes a prefill holds. The paths this block is not
    built for refuse it with :class:`UnsupportedPathError`, by name."""

    name = "xing"
    supports_tp = False
    # no int8 pool: a latent is not quantized a head as keys and values
    kv_dtypes = ("fp32", "bf16")

    def __init__(self, cfg):
        self.cfg = cfg

    def eager_builders(self, max_len):
        return {"logits": lambda: xing_logits(self.cfg)}

    def build(self, kind, max_len):
        """The program of a lazily built ``kind``; the paths this block
        has no program for raise :class:`UnsupportedPathError`."""
        kv_dtype = kind.rsplit("_", 1)[-1]
        if kind.startswith("prefill_") and not kind.startswith(
                "prefill_chunk_"):
            return xing_prefill(self.cfg, kv_dtype=kv_dtype)
        if kind.startswith("decode_paged_"):
            return xing_decode_step_paged(self.cfg, kv_dtype=kv_dtype)
        for prefix, path in (("prefill_chunk", "chunked prefill"),
                             ("verify", "speculative verify"),
                             ("train", "training")):
            if kind.startswith(prefix):
                raise UnsupportedPathError(self.name, path)
        raise KeyError(f"unknown generation program kind {kind!r}")

    def training_program(self):
        """Refused: every op of this block is forward only."""
        raise UnsupportedPathError(self.name, "training")

    def prefill_kind(self, kv_dtype):
        return f"prefill_{kv_dtype}"

    # -- the pool's geometry
    kv_heads = 1

    @property
    def head_dim(self):
        """The width a position keeps a layer: latent and rotary key."""
        return self.cfg.kv_lora_rank + self.cfg.qk_rope_head_dim

    def kv_groups(self):
        """One latent group of every layer: ``c`` the latent, ``r`` the
        rotary key, each a width a position."""
        cfg = self.cfg
        return [{"name": "latent",
                 "layers": list(range(cfg.num_hidden_layers)),
                 "latent": {"c": cfg.kv_lora_rank,
                            "r": cfg.qk_rope_head_dim}}]

    def prefill_bytes(self, rows, seq, max_len, kv_elem_bytes):
        """Device bytes one prefill of ``rows`` x ``seq`` holds at its
        peak beyond the weights: what it returns (every layer's latent
        and rotary key, the logits) and, at once, three copies of the
        float32 streams (the state, its normalised copy, the state
        written back) beside the widest sublayer's rows: the expert
        layer's (``k`` copies of every token, in the matrices' dtype,
        out and combined in float32) or the expanded attention's (keys
        and values of every head in float32 and in the matrices'
        dtype) or the dense MLP's (gate, up and their product)."""
        cfg = self.cfg
        tokens = int(rows) * int(seq)
        hid, H = cfg.hidden_size, cfg.num_attention_heads
        cache = cfg.num_hidden_layers * tokens * self.head_dim \
            * kv_elem_bytes
        streams = 3 * tokens * cfg.hc_mult * hid * 4
        moe = tokens * cfg.num_experts_per_tok * hid * (2 + 4 + 4)
        attn = tokens * H * (
            (cfg.qk_nope_head_dim + cfg.v_head_dim) * (4 + 2)
            + 2 * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) * 2)
        mlp = tokens * cfg.intermediate_size * (4 + 4 + 4 + 2)
        return cache + streams + int(rows) * cfg.vocab_size * 4 \
            + max(moe, attn, mlp)
