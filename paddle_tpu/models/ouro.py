"""Ouro-class looped decoder (ByteDance ``Ouro-2.6B``'s ``config.json``;
the config class keeps the source's key names): one stack of
``num_hidden_layers`` blocks run ``total_ut_steps`` times over the SAME
weights, each (pass, layer) pair with a KV cache of its own.

For token ids ``t`` at positions ``p``, with ``h = E[t]``::

    for u in range(total_ut_steps):              # the same weights each pass
        for i in range(num_hidden_layers):
            a = rmsnorm_i1(h); q, k, v = a [Wq_i | Wk_i | Wv_i]    # no bias
            q, k = rope(q, p), rope(k, p)
            K[u, i], V[u, i] <- append(k, v)     # cache layer u * layers + i
            h = h + rmsnorm_i2(attention(q, K[u, i], V[u, i]) Wo_i)
            f = rmsnorm_i3(h)
            h = h + rmsnorm_i4((silu(f Wg_i) * (f Wu_i)) Wd_i)
        h = rmsnorm_final(h)                     # what pass u + 1 starts from
        g_u = sigmoid(h w_exit + b_exit)         # one number a token a pass
    logits = h W_head

The exit distribution ``p_u = g_u prod_{j<u}(1 - g_j)`` (the last pass
takes what is left) is computed in every program; at the config's
``early_exit_threshold`` 1.0 every token leaves at the last pass, and a
threshold below 1 (rows of one step leaving at different passes) is
refused by name.

The passes are ONE loop in the program (``layers.StaticRNN``: a
``lax.scan`` whose body holds the ``num_hidden_layers`` blocks once), so
an executable compiles one stack, not ``total_ut_steps`` of them. The
hidden state is the loop's memory and, in the paged decode step, so are
the pool's arrays: an array a weight layer holds the blocks of all its
passes, pass ``u``'s at block ids ``table + u * N`` (``N`` blocks a
pass), and is appended to in place inside the loop.

The matrices are held in ``cfg.dtype`` (bfloat16 when served) and their
products accumulate in float32; the residual stream, RMSNorm's
statistics, the rotary table and the exit gate are float32.
"""
import numpy as np

from .. import layers
from ..framework import initializer as I
from ..layers import math as M
from ..layers import tensor as T
from ..layers.control_flow import StaticRNN
from ..param_attr import ParamAttr
from .generation import UnsupportedPathError
# the same building blocks as the other RMSNorm / rotary decoder: a named
# normal(0, initializer_range) matrix, an RMSNorm with a unit gain, a
# product in cfg.dtype accumulated in float32, the embedding look-up
from .mellum import _embed, _norm, _param, _proj, random_prompt  # noqa: F401

# the pass at which the exit distribution's running sum reaches this is
# what a step's span reports (``exit_pass_mean``): a number for the
# timeline, it changes no output
EXIT_REPORT_AT = 0.5


class OuroConfig:
    """The keys of the source's ``config.json`` that shape the model,
    under their own names, plus ``dtype`` (what the matrices are held
    in) and ``initializer_range``."""

    def __init__(self, vocab_size=49152, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=16,
                 num_key_value_heads=16, head_dim=128,
                 intermediate_size=5632, hidden_act="silu",
                 rms_norm_eps=1e-6, rope_theta=1000000.0,
                 tie_word_embeddings=False, total_ut_steps=4,
                 early_exit_threshold=1.0, max_position_embeddings=65536,
                 initializer_range=0.02, dtype="bfloat16"):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.intermediate_size = int(intermediate_size)
        self.hidden_act = hidden_act
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.tie_word_embeddings = bool(tie_word_embeddings)
        self.total_ut_steps = int(total_ut_steps)
        self.early_exit_threshold = float(early_exit_threshold)
        self.max_position_embeddings = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        self.dtype = dtype
        if self.hidden_act != "silu":
            raise ValueError("the MLP is silu(gate) * up in this family")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into the KV heads")
        if self.tie_word_embeddings:
            raise ValueError("the head is untied in this family")
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps counts the passes: >= 1")

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
    def cache_layers(self):
        """A KV cache a (pass, layer) pair."""
        return self.total_ut_steps * self.num_hidden_layers

    @classmethod
    def tiny(cls, **over):
        kw = dict(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=4, head_dim=8,
                  intermediate_size=64, rope_theta=10000.0,
                  total_ut_steps=4, max_position_embeddings=64,
                  dtype="float32")
        kw.update(over)
        return cls(**kw)

    def serving(self):
        return OuroServing(self)


def rope_inv_freq(cfg):
    """Plain rotary frequencies ``theta ** (-2i / d)``, no scaling."""
    d = cfg.head_dim
    return (cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
            ).astype(np.float32)


def decoder_layer(cfg, x, idx, pos_ids, kv=None):
    """Block ``idx`` over ``x`` [B, S, hidden] at ``pos_ids`` [B, S]: a
    norm before and after each sub-layer.

    ``kv=None``: attention over the fed sequence through the flash
    forward; returns ``(x, k, v)`` with the rotated keys and the values
    ``[B, Hkv, S, D]`` this pass's cache layer takes. ``kv={"k", "v",
    "tables", "pos"}``: the paged decode step (S = 1): this token's key
    and value are appended through the pass's block table and the query
    reads the pool; returns ``(x, new_k_pool, new_v_pool)``."""
    pre = f"layer_{idx}"
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    inv_freq = rope_inv_freq(cfg)

    def heads(t, n):
        return T.transpose(T.reshape(t, [0, 0, n, d]), [0, 2, 1, 3])

    # Wq | Wk | Wv are one stored matrix: one product of three times the
    # columns, and XLA keeps it as it lies (three square matrices it laid
    # out transposed, a copy of each hoisted out of the loop of passes:
    # 1.2 GB of temporaries at 48 layers)
    a = _norm(cfg, x, f"{pre}_attn_in_norm")
    qkv = _proj(cfg, a, (hq + 2 * hkv) * d, f"{pre}_qkv_proj")
    q, k, v = T.split(qkv, [hq * d, hkv * d, hkv * d], dim=-1)
    q, k, v = heads(q, hq), heads(k, hkv), heads(v, hkv)
    q = layers.nn.rotary_embedding(q, pos_ids, inv_freq)
    k = layers.nn.rotary_embedding(k, pos_ids, inv_freq)
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
    attn = _proj(cfg, ctx, cfg.hidden_size, f"{pre}_o_proj")
    x = M.elementwise_add(x, _norm(cfg, attn, f"{pre}_attn_out_norm"))

    f = _norm(cfg, x, f"{pre}_mlp_in_norm")
    gate = layers.nn.swish(_proj(cfg, f, cfg.intermediate_size,
                                 f"{pre}_gate_proj"))
    up = _proj(cfg, f, cfg.intermediate_size, f"{pre}_up_proj")
    mlp = _proj(cfg, M.elementwise_mul(gate, up), cfg.hidden_size,
                f"{pre}_down_proj")
    x = M.elementwise_add(x, _norm(cfg, mlp, f"{pre}_mlp_out_norm"))
    return x, new_k, new_v


def _exit_gate(cfg, h):
    """[B, S, hidden] -> [B, S, 1]: float32 matrix, bias and product."""
    return layers.nn.sigmoid(layers.fc(
        h, 1, num_flatten_dims=2, param_attr=_param(cfg, "exit_gate.w_0"),
        bias_attr=ParamAttr(name="exit_gate.b_0",
                            initializer=I.Constant(0.0))))


def _pass_tables(tables, first_block):
    """The block table of one pass: the rows' block ids moved into the
    pass's own section of every pool array (a free slot's zeros land on
    the section's first block, the pass's trash block)."""
    return M.elementwise_add(tables, first_block)


def run_passes(cfg, x, pos_ids, pools=None):
    """``total_ut_steps`` passes of the whole stack over ``x`` [B, S,
    hidden], one loop. ``pools=None``: every pass attends over the fed
    sequence; returns ``(h, gates, keys, values)`` with ``gates`` [U, B,
    S, 1] and ``keys`` / ``values`` a list a weight layer of ``[U, B,
    Hkv, S, D]`` (the pass in front). ``pools={"k": [...], "v": [...],
    "tables", "pos"}``: the paged decode step; a weight layer's pool
    array holds every pass's blocks, pass ``u``'s at ``tables + u * N``
    with ``N`` the array's blocks over the passes, and is the loop's
    memory: appended to in place. Returns ``(h, gates, new_k_pools,
    new_v_pools)``."""
    n, passes = cfg.num_hidden_layers, cfg.total_ut_steps
    step = T.reshape(T.arange(0, passes, dtype="int32"), [passes, 1])
    if pools is not None:
        # block ids of a pass start where the last pass's end
        per_pass = M.elementwise_floordiv(
            T.slice(T.shape(pools["k"][0]), axes=[0], starts=[0], ends=[1]),
            T.fill_constant([1], "int32", passes))
        step = M.elementwise_mul(step, per_pass)
    rnn = StaticRNN(scope="loop/pass")
    with rnn.step():
        first_block = rnn.step_input(step)                       # [1]
        h = h_mem = rnn.memory(init=x)
        if pools is not None:
            k_mem = [rnn.memory(init=p) for p in pools["k"]]
            v_mem = [rnn.memory(init=p) for p in pools["v"]]
            tables = _pass_tables(pools["tables"], first_block)
        for i in range(n):
            kv = None if pools is None else {
                "k": k_mem[i], "v": v_mem[i], "tables": tables,
                "pos": pools["pos"]}
            h, new_k, new_v = decoder_layer(cfg, h, i, pos_ids, kv=kv)
            if pools is None:
                rnn.step_output(new_k)
                rnn.step_output(new_v)
            else:
                rnn.update_memory(k_mem[i], new_k)
                rnn.update_memory(v_mem[i], new_v)
        h = _norm(cfg, h, "final_norm")
        rnn.update_memory(h_mem, h)
        rnn.step_output(_exit_gate(cfg, h))
    outs, finals = rnn(), rnn.final_states()
    outs = outs if isinstance(outs, list) else [outs]
    if pools is None:
        return finals[0], outs[-1], outs[0:-1:2], outs[1:-1:2]
    return finals[0], outs[-1], finals[1:1 + n], finals[1 + n:]


def exit_distribution(cfg, gates):
    """``gates``, a [B, 1] a pass -> ``(probs [B, U], exit_pass [B]
    int32)``:
    ``p_u = g_u prod_{j<u}(1 - g_j)``, the last pass taking what is
    left, and the first pass (from 1) at which their running sum reaches
    ``EXIT_REPORT_AT``."""
    passes = cfg.total_ut_steps
    one = T.fill_constant([1], "float32", 1.0)
    stay, probs = None, []
    for u, g in enumerate(gates):
        if u == passes - 1:
            probs.append(stay if stay is not None else
                         M.elementwise_add(M.scale(g, 0.0), one))
            break
        probs.append(g if stay is None else M.elementwise_mul(g, stay))
        left = M.elementwise_sub(one, g)
        stay = left if stay is None else M.elementwise_mul(stay, left)
    probs = T.concat(probs, axis=1)                               # [B, U]
    short = M.less_than(T.cumsum(probs, axis=1),
                        T.fill_constant([1], "float32", EXIT_REPORT_AT))
    exit_pass = M.elementwise_min(
        M.elementwise_add(
            M.reduce_sum(T.cast(short, "int32"), dim=[1]),
            T.fill_constant([1], "int32", 1)),
        T.fill_constant([1], "int32", passes))
    return probs, exit_pass


def _head(cfg, h, gates, last_pos):
    """The untied head and the exit distribution at each row's own last
    real position: ``h`` [B, S, hidden] (final-normed by the last pass),
    ``gates`` [U, B, S, 1]."""
    logits = _proj(cfg, layers.nn.row_gather(h, last_pos),
                   cfg.vocab_size, "lm_head")
    probs, exit_pass = exit_distribution(cfg, [
        layers.nn.row_gather(g, last_pos) for g in T.unstack(
            gates, axis=0, num=cfg.total_ut_steps)])
    return logits, {"exit_probs": probs, "exit_pass": exit_pass}


def _prompt_feeds(batch_size, seq_len):
    tokens = T.data("tokens", [batch_size, seq_len], dtype="int32")
    pos_ids = T.data("pos_ids", [batch_size, seq_len], dtype="int32")
    last_pos = T.data("last_pos", [batch_size], dtype="int32")
    return tokens, pos_ids, last_pos


def ouro_logits(cfg, batch_size=-1, seq_len=-1):
    """Full-sequence forward -> next-token logits, no cache: what
    ``Executor`` runs, and the prefill's parity reference. Feeds as
    ``gpt_logits``: tokens, pos_ids [B, S] int32, last_pos [B] int32."""
    tokens, pos_ids, last_pos = _prompt_feeds(batch_size, seq_len)
    h, gates, _, _ = run_passes(cfg, _embed(cfg, tokens), pos_ids)
    logits, aux = _head(cfg, h, gates, last_pos)
    return {"feed_names": ["tokens", "pos_ids", "last_pos"],
            "logits": logits, "aux": aux}


def ouro_prefill(cfg, kv_dtype="bf16", batch_size=-1, seq_len=-1):
    """Prompt ingestion: the forward of :func:`ouro_logits` that also
    returns, a weight layer, the keys and values of all its passes
    ``[U, B, Hkv, S, D]`` at the bucket's length in the pool's dtype
    (the pool scatters pass ``u``'s into the blocks at ``table + u *
    N``)."""
    cache_dt = {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype]
    tokens, pos_ids, last_pos = _prompt_feeds(batch_size, seq_len)
    h, gates, keys, values = run_passes(cfg, _embed(cfg, tokens), pos_ids)
    logits, aux = _head(cfg, h, gates, last_pos)
    return {"feed_names": ["tokens", "pos_ids", "last_pos"],
            "logits": logits,
            "cache_k": [T.cast(k, cache_dt) for k in keys],
            "cache_v": [T.cast(v, cache_dt) for v in values],
            "aux": aux}


def ouro_decode_step_paged(cfg, kv_dtype="bf16", batch_size=-1):
    """ONE paged decode step: every pass appends this token's keys and
    values to its own cache layers and reads them. Feeds: token, pos [B]
    int32, ``block_tables`` [B, nblk] (one table for every pass: all see
    the same positions), then the pools ``cache_pk_<i>`` /
    ``cache_pv_<i>``, an array a WEIGHT layer, stored ``[U * N, Hkv *
    bs, D]``. Fetches: logits, the updated pools in
    ``serving.kvpool.pool_feed_names`` order, then ``exit_pass``, 0 in
    a free slot's row (its table starts at the trash block)."""
    from ..serving.kvpool import pool_feed_names
    cache_dt = {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype]
    token = T.data("token", [batch_size], dtype="int32")
    pos = T.data("pos", [batch_size], dtype="int32")
    tables = T.data("block_tables", [batch_size, -1], dtype="int32")
    n, d = cfg.num_hidden_layers, cfg.head_dim
    cache_names = pool_feed_names(n, False)
    pool = {name: T.data(name, [-1, -1, d], dtype=cache_dt)
            for name in cache_names}
    x = T.reshape(_embed(cfg, token), [-1, 1, cfg.hidden_size])
    h, gates, new_k, new_v = run_passes(
        cfg, x, T.reshape(pos, [-1, 1]),
        pools={"k": [pool[f"cache_pk_{i}"] for i in range(n)],
               "v": [pool[f"cache_pv_{i}"] for i in range(n)],
               "tables": tables, "pos": pos})
    zero = T.fill_constant_batch_size_like(token, [-1], "int32", 0)
    logits, aux = _head(cfg, h, gates, zero)
    live = T.cast(M.not_equal(
        T.reshape(T.slice(tables, axes=[1], starts=[0], ends=[1]), [-1]),
        T.fill_constant([1], "int32", 0)), "int32")
    # the distribution itself stays on the device: a step's span reads
    # ``exit_pass`` alone
    aux = {"exit_pass": M.elementwise_mul(aux["exit_pass"], live)}
    return {"feed_names": ["token", "pos", "block_tables"] + cache_names,
            "logits": logits, "cache_names": cache_names,
            "cache_vars": new_k + new_v, "aux": aux}


class OuroServing:
    """What the serving path asks an architecture for (``GPTServing`` and
    ``MellumServing`` are the other two): its program builders, the
    layout of its keys and values in the pool, and the bytes a prefill
    hands back. Cache layers are not weight layers here: the pool holds
    ``total_ut_steps`` of them a weight layer under one block table."""

    name = "ouro"
    supports_tp = False
    kv_dtypes = ("fp32", "bf16")

    def __init__(self, cfg):
        if cfg.early_exit_threshold < 1.0:
            # rows of one step would leave the loop at different passes
            raise UnsupportedPathError(
                self.name, f"early_exit_threshold "
                           f"{cfg.early_exit_threshold:g} below 1")
        self.cfg = cfg

    @property
    def ut_steps(self):
        """Passes over the weights an executable runs a token."""
        return self.cfg.total_ut_steps

    def eager_builders(self, max_len):
        return {"logits": lambda: ouro_logits(self.cfg)}

    def build(self, kind, max_len):
        """The program of a lazily built ``kind``; the paths this block
        has no program for raise :class:`UnsupportedPathError`."""
        kv_dtype = kind.rsplit("_", 1)[-1]
        if kind.startswith("prefill_") and not kind.startswith(
                "prefill_chunk_"):
            return ouro_prefill(self.cfg, kv_dtype=kv_dtype)
        if kind.startswith("decode_paged_"):
            return ouro_decode_step_paged(self.cfg, kv_dtype=kv_dtype)
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
        """One full group of ``total_ut_steps x num_hidden_layers``
        cache layers: cache layer ``u * num_hidden_layers + i`` is pass
        ``u`` of weight layer ``i``, and the pool keeps a weight layer's
        ``passes`` in one array."""
        return [{"name": "full", "window": None,
                 "layers": list(range(self.cfg.cache_layers)),
                 "passes": self.cfg.total_ut_steps}]

    def prefill_bytes(self, rows, seq, max_len, kv_elem_bytes):
        """Device bytes one prefill of ``rows`` x ``seq`` holds at its
        peak beyond the weights: the keys and values of every cache
        layer it returns, the logits, and the MLP's widest rows (gate,
        up and their product in float32, the product again in the
        matrices' dtype)."""
        cfg = self.cfg
        tokens = int(rows) * int(seq)
        kv = 2 * cfg.cache_layers * cfg.num_key_value_heads \
            * cfg.head_dim * tokens * kv_elem_bytes
        mlp = tokens * cfg.intermediate_size * (4 + 4 + 4 + 2)
        return kv + int(rows) * cfg.vocab_size * 4 + mlp
