"""The Xing-4.0 family (XingChen-AGI ``Xing4.0-29B-A4B``), as the benchmark
knows it: multi-head latent attention (a latent and one rotary key cached
a position), a residual state of ``hc_mult`` streams mixed by
manifold-constrained hyper-connections (mHC: a Sinkhorn-normalised
``n x n`` mix a token), two dense SwiGLU layers, then expert layers of
sigmoid-routed top-k of 64 experts beside one shared expert (``noaux_tc``:
a selection bias the weights do not see, the weights renormalised and
scaled by ``routed_scaling_factor``), and an untied head.

What lives here and nowhere in the program, as in ``families/mellum.py``:

* ``init_params``: every weight from ``--seed``, made on the device. The
  matrices are drawn in float32 and rounded once to bfloat16, the type the
  configuration serves them in, so that program and reference hold the
  same numbers; the router, its selection bias, the mHC ``phi``,
  ``alpha`` and ``bias`` and the RMSNorm gains stay float32.
* ``build_generator``: how a cell hands the model to the program
  (``models/xing.py`` through ``GPTGenerator``).
* the closed-form counts the readers use, from shapes, whatever
  implements them: ``serve_flops`` (the expanded prefill and the absorbed
  decode), ``causal_attention_flops`` (keys of ``dn + R`` = 192, values of
  ``dv`` = 128), ``kv_bytes_per_position`` (8,064 B at the cut: 7 layers
  x 576 x 2), ``mla_decode_bytes`` and ``expert_layer_work`` (the
  signature Mellum's has).
* ``reference_logits`` / ``reference_served_gaps``: the model written from
  its equations in plain ``jax.numpy`` at float32 with
  ``precision=highest``: attention expanded (every head's keys and
  values from the latent) in blocks of query rows, the Sinkhorn as
  written, every expert applied to every token under the router's mask,
  no kernel, no cache, no batching. It imports nothing of ``paddle_tpu``
  and is never given the program's routing. ``mode`` lowers every matrix
  product's operands but the router's and the mHC projection's (float32
  by the configuration) to ``bf16`` or ``fp8``: the controls that
  ``correct`` has to fail.

Choices ``config.json`` does not fix (the configuration file lists them
under ``assumed``): the embedding is copied into the ``hc_mult`` streams;
a head mix ``sigmoid(alpha xbar phi + b)`` reduces them after the last
layer; ``hc_eps`` sits in both of Sinkhorn's denominators and the clamp
comes before the exponential; mHC's norm has no gain; alpha starts at
0.1, the pre and post biases at 0 and the residual mix's at 2 on its
diagonal (near the identity), the selection bias normal(0, 0.02); the
rotary key's lanes pair half with half.
"""
import functools
import math

import numpy as np

from .gpt import _matmul, _seed_key, leaf_norms  # noqa: F401

INT_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_nextn_predict_layers", "num_attention_heads",
            "num_key_value_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
            "first_k_dense_replace", "moe_layer_freq", "n_group",
            "topk_group", "hc_mult", "hc_sinkhorn_iters",
            "max_position_embeddings")
FLOAT_KEYS = ("rms_norm_eps", "rope_theta", "routed_scaling_factor",
              "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
RES_DIAGONAL = 2.0      # the residual mix's bias on its diagonal
ALPHA = 0.1             # every mHC gate's alpha at the start


# ------------------------------------------------------------------ sizes

class Sizes:
    """The numbers of one configuration file (or of its ``rehearsal``
    group, for the CPU dry run), under the source's key names. Hashable
    by identity: the jitted references are cached on it."""

    def __init__(self, config, rehearsal=False):
        src = dict(config)
        if rehearsal:
            src.update(config["rehearsal"])
        for key in INT_KEYS:
            setattr(self, key, int(src[key]))
        for key in FLOAT_KEYS:
            setattr(self, key, float(src[key]))
        self.rope_scaling = dict(src["rope_scaling"])
        self.norm_topk_prob = bool(src["norm_topk_prob"])
        self.initializer_range = float(src.get("initializer_range", 0.02))
        if src["hidden_act"] != "silu" or src["tie_word_embeddings"] \
                or src["attention_bias"] or src["scoring_func"] != "sigmoid" \
                or src["topk_method"] != "noaux_tc" or self.n_group != 1 \
                or self.topk_group != 1 or self.n_shared_experts != 1 \
                or self.moe_layer_freq != 1:
            raise ValueError("this family is silu experts routed by sigmoid "
                             "scores over one group beside one shared "
                             "expert, an untied head, no attention bias")
        if self.num_nextn_predict_layers:
            raise ValueError("the multi-token prediction module is not "
                             "served: num_nextn_predict_layers must be 0")

    def is_moe(self, layer):
        return layer >= self.first_k_dense_replace

    @property
    def moe_layers(self):
        return sum(1 for i in range(self.num_hidden_layers)
                   if self.is_moe(i))

    @property
    def dense_layers(self):
        return self.num_hidden_layers - self.moe_layers

    @property
    def num_experts(self):
        """What ``readers_moe`` reads of an expert configuration."""
        return self.n_routed_experts

    @property
    def position_width(self):
        """What a position keeps a layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


def param_shapes(sz):
    """name -> (shape, kind) under the names ``models/xing.py`` gives
    them; kind is ``matrix`` (bfloat16), or one of the float32 kinds
    ``router``, ``e_bias``, ``gain``, ``hc_phi``, ``hc_alpha``,
    ``hc_bias`` (pre | post | res, res row-major) and ``head_bias``."""
    h, n = sz.hidden_size, sz.hc_mult
    H, dn, R, dv = (sz.num_attention_heads, sz.qk_nope_head_dim,
                    sz.qk_rope_head_dim, sz.v_head_dim)
    C, e, f = sz.kv_lora_rank, sz.n_routed_experts, sz.moe_intermediate_size
    shapes = {"embed_tokens": ((sz.vocab_size, h), "matrix")}
    for i in range(sz.num_hidden_layers):
        pre = f"layer_{i}"
        for sub in ("attn", "ffn"):
            shapes.update({
                f"{pre}_hc_{sub}_phi": ((n * h, n * (n + 2)), "hc_phi"),
                f"{pre}_hc_{sub}_alpha": ((3,), "hc_alpha"),
                f"{pre}_hc_{sub}_bias": ((n * (n + 2),), "hc_bias")})
        shapes.update({
            f"{pre}_input_norm_scale": ((h,), "gain"),
            f"{pre}_q_a_proj.w_0": ((h, sz.q_lora_rank), "matrix"),
            f"{pre}_q_a_norm_scale": ((sz.q_lora_rank,), "gain"),
            f"{pre}_q_b_proj.w_0": ((sz.q_lora_rank, H * (dn + R)),
                                    "matrix"),
            f"{pre}_kv_a_proj.w_0": ((h, C + R), "matrix"),
            f"{pre}_kv_a_norm_scale": ((C,), "gain"),
            f"{pre}_kv_b_proj.w_0": ((C, H * (dn + dv)), "matrix"),
            f"{pre}_o_proj.w_0": ((H * dv, h), "matrix"),
            f"{pre}_post_attn_norm_scale": ((h,), "gain")})
        if sz.is_moe(i):
            shapes.update({
                f"{pre}_router.w_0": ((h, e), "router"),
                f"{pre}_router_e_bias": ((e,), "e_bias"),
                f"{pre}_experts_gate.w_0": ((e, h, f), "matrix"),
                f"{pre}_experts_up.w_0": ((e, h, f), "matrix"),
                f"{pre}_experts_down.w_0": ((e, f, h), "matrix"),
                f"{pre}_shared_gate_proj.w_0": ((h, f), "matrix"),
                f"{pre}_shared_up_proj.w_0": ((h, f), "matrix"),
                f"{pre}_shared_down_proj.w_0": ((f, h), "matrix")})
        else:
            width = sz.intermediate_size
            shapes.update({
                f"{pre}_gate_proj.w_0": ((h, width), "matrix"),
                f"{pre}_up_proj.w_0": ((h, width), "matrix"),
                f"{pre}_down_proj.w_0": ((width, h), "matrix")})
    shapes.update({"hc_head_phi": ((n * h, n), "hc_phi"),
                   "hc_head_alpha": ((1,), "hc_alpha"),
                   "hc_head_bias": ((n,), "head_bias"),
                   "final_norm_scale": ((h,), "gain"),
                   "lm_head.w_0": ((h, sz.vocab_size), "matrix")})
    return shapes


def param_count(sz):
    return sum(math.prod(s) for s, _ in param_shapes(sz).values())


def weight_bytes(sz):
    """Device bytes of the weights as served: matrices in bfloat16, the
    rest float32."""
    return sum(math.prod(s) * (2 if kind == "matrix" else 4)
               for s, kind in param_shapes(sz).values())


def active_matmul_params(sz):
    """Weights that take part in a product for one token: every layer's
    five attention projections (``W_kvb`` once, expanded at prefill and
    absorbed at decode alike: 2 x H x dn x C + 2 x H x dv x C operations
    either way), both mHC projections, the dense MLP or the router,
    ``num_experts_per_tok`` experts and the shared expert; the head mix
    and the head. The embedding is a look-up."""
    h, n = sz.hidden_size, sz.hc_mult
    H, dn, R, dv = (sz.num_attention_heads, sz.qk_nope_head_dim,
                    sz.qk_rope_head_dim, sz.v_head_dim)
    C, f = sz.kv_lora_rank, sz.moe_intermediate_size
    attn = (h * sz.q_lora_rank + sz.q_lora_rank * H * (dn + R)
            + h * (C + R) + C * H * (dn + dv) + H * dv * h)
    hc = 2 * n * h * n * (n + 2)
    dense = 3 * h * sz.intermediate_size
    moe = h * sz.n_routed_experts + (sz.num_experts_per_tok + 1) * 3 * h * f
    return (sz.num_hidden_layers * (attn + hc) + sz.dense_layers * dense
            + sz.moe_layers * moe + n * h * n + h * sz.vocab_size)


def causal_attention_flops(sz, batch, seq, backward=False):
    """QK^T over keys of ``dn + R`` lanes and PV over values of ``dv``
    of every layer, the lower triangle of ``seq`` positions: what the
    expanded prefill computes."""
    if backward:
        raise ValueError("this family is served, not trained")
    pairs = seq * (seq + 1) / 2
    return 2 * batch * sz.num_attention_heads * pairs * sz.num_hidden_layers \
        * (sz.qk_nope_head_dim + sz.qk_rope_head_dim + sz.v_head_dim)


def kv_bytes_per_position(sz, kv_bytes):
    """What a position keeps in every layer: the latent and the rotary
    key (8,064 B at the cut's 7 layers in bfloat16)."""
    return sz.num_hidden_layers * sz.position_width * kv_bytes


def mla_decode_bytes(sz, positions, row_layers, kv_bytes=2):
    """Bytes the latent decode kernel must move for ``positions`` latent
    positions read (summed over the layers) and ``row_layers`` rows a
    layer: each position's latent and rotary key once, and a row's
    absorbed query (latent and rotary parts) in and its latent output
    out, float32 a head."""
    H = sz.num_attention_heads
    per_row = 4 * H * (2 * sz.kv_lora_rank + sz.qk_rope_head_dim)
    return positions * sz.position_width * kv_bytes + row_layers * per_row


def serve_flops(sz, prompt_len, new_tokens):
    """What one served reply needs: every prompt and every new token once
    through the active matrices, the expanded causal attention over the
    prompt, and each decoded token's absorbed query against every
    position its layers keep (C + R lanes a score, C an output)."""
    ctx = prompt_len + np.arange(1, new_tokens)   # token j reads prompt + j
    per_pos = 2 * sz.num_attention_heads * sz.num_hidden_layers \
        * (2 * sz.kv_lora_rank + sz.qk_rope_head_dim)
    return (2 * active_matmul_params(sz) * (prompt_len + new_tokens - 1)
            + causal_attention_flops(sz, 1, prompt_len)
            + per_pos * float(ctx.sum()))


def expert_layer_work(sz, assignments, experts_hit, weight_bytes=2):
    """``(flops, bytes)`` of the routed expert products of expert-layer
    calls that routed ``assignments`` (token, expert) pairs in all and
    touched ``experts_hit`` experts in all: three d x f products a pair;
    each touched expert's three matrices once, and a pair's row in
    (``weight_bytes`` an element) and out (float32). The shared expert is
    a dense product of its own, not counted here."""
    h, f = sz.hidden_size, sz.moe_intermediate_size
    flops = assignments * 3 * 2 * h * f
    nbytes = experts_hit * 3 * h * f * weight_bytes \
        + assignments * h * (weight_bytes + 4)
    return flops, nbytes


# ---------------------------------------------------------------- weights

@functools.lru_cache(maxsize=4)
def _init_fn(shape_items, std, n):
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shape_items):
            k = jax.random.fold_in(key, i)
            if kind == "gain":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "hc_alpha":
                out[name] = jnp.full(shape, ALPHA, jnp.float32)
            elif kind == "head_bias":
                out[name] = jnp.zeros(shape, jnp.float32)
            elif kind == "hc_bias":
                res = RES_DIAGONAL * jnp.eye(n, dtype=jnp.float32).reshape(-1)
                out[name] = jnp.concatenate(
                    [jnp.zeros((2 * n,), jnp.float32), res])
            else:
                w = std * jax.random.normal(k, shape, jnp.float32)
                out[name] = w.astype(jnp.bfloat16) if kind == "matrix" else w
        return out

    return jax.jit(make)


_LAST = {}      # (shapes, std, seed) -> the last weights made


def init_params(sz, seed):
    """Module docstring's initialisation; on the device, one jitted call.
    The last result is kept and handed out again for the same sizes and
    seed: the weights are 9.84 GB of a 16 GB chip, so the program (which
    binds them where they lie) and the reference read one copy, which
    neither writes."""
    items = tuple(param_shapes(sz).items())
    key = (items, sz.initializer_range, int(seed))
    if key not in _LAST:
        _LAST.clear()           # the other seed's go before these come
        _LAST[key] = _init_fn(items, sz.initializer_range, sz.hc_mult)(
            _seed_key(seed))
    return dict(_LAST[key])


# ------------------------------------------------- handing it to the program

def program_config(sz):
    from paddle_tpu.models import xing
    keys = [k for k in INT_KEYS if k not in ("n_group", "topk_group")]
    return xing.XingConfig(
        rope_scaling=sz.rope_scaling, norm_topk_prob=sz.norm_topk_prob,
        initializer_range=sz.initializer_range, dtype="bfloat16",
        **{key: getattr(sz, key) for key in keys + list(FLOAT_KEYS)})


def build_generator(sz, serve, seed):
    """A ``GPTGenerator`` bound to ``init_params``, with the cache type,
    the block size and the pool's blocks the configuration pins (flags of
    the process: the server's pool reads them)."""
    import paddle_tpu as fluid
    from paddle_tpu import flags
    from paddle_tpu.models import xing
    from paddle_tpu.models.generation import GPTGenerator
    flags.set_flags({"FLAGS_kv_cache_dtype": serve["kv_cache_dtype"],
                     "FLAGS_kv_block_size": serve["kv_block_size"],
                     "FLAGS_kv_pool_blocks": serve["kv_pool_blocks"]})
    cfg = program_config(sz)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xing.xing_logits(cfg)
    have = {p.name: tuple(p.shape) for p in main.all_parameters()}
    want = {n: tuple(s) for n, (s, _) in param_shapes(sz).items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise RuntimeError(f"the program's parameters are not the "
                           f"family's: {odd}")
    # 9.84 GB of weights: bound where they lie, not pulled through the
    # host and put back as the scope's values would be
    gen = GPTGenerator(cfg, fluid.Scope(), max_len=serve["max_len"])
    gen.bind_params(init_params(sz, seed))
    return gen


# -------------------------------------------------------------- reference

def yarn_table(sz):
    """``(inv_freq [R / 2], cos and sin factor, softmax scale)`` of the
    rotary key's R lanes: YaRN (Peng et al. 2023) as the source's code
    computes it once from ``rope_scaling``: the plain frequencies and
    their ``factor``-fold interpolation blended linearly between the
    dimensions that make ``beta_fast`` and ``beta_slow`` turns over the
    original context; cos and sin x ``mscale(mscale) /
    mscale(mscale_all_dim)``; the scale ``mscale(mscale_all_dim)^2 /
    sqrt(dn + R)``, with ``mscale(m) = 0.1 m ln(factor) + 1``."""
    par = sz.rope_scaling
    d, base = sz.qk_rope_head_dim, sz.rope_theta
    factor = float(par["factor"])
    orig = float(par["original_max_position_embeddings"])
    plain = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(par["beta_fast"])), 0)
    high = min(math.ceil(dim_of(par["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    inv_freq = plain / factor * (1.0 - keep) + plain * keep

    def mscale(m):
        return 0.1 * float(m) * math.log(factor) + 1.0

    ratio = mscale(par["mscale"]) / mscale(par["mscale_all_dim"])
    scale = mscale(par["mscale_all_dim"]) ** 2 / math.sqrt(
        sz.qk_nope_head_dim + d)
    return inv_freq.astype(np.float32), ratio, scale


def _rms_norm(x, gain, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def _rope(x, positions, inv_freq, factor):
    """x [..., seq, d]; lane i pairs with lane i + d/2."""
    import jax.numpy as jnp
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hc_weights(sz, x, phi, alpha, bias):
    """mHC of the streams ``x`` [seq, n, C]: ``(pre [seq, n], post
    [seq, n], res [seq, n, n])``, float32 at ``precision=highest``:
    sigmoid gates, and the clamped logits' exponential normalised by
    rows then columns ``hc_sinkhorn_iters`` times, ``hc_eps`` in each
    denominator."""
    import jax
    import jax.numpy as jnp
    n = sz.hc_mult
    flat = x.reshape(x.shape[0], -1)
    xbar = flat / jnp.sqrt(jnp.mean(jnp.square(flat), -1, keepdims=True)
                           + sz.rms_norm_eps)
    proj = jnp.matmul(xbar, phi, precision=jax.lax.Precision.HIGHEST)
    pre = jax.nn.sigmoid(alpha[0] * proj[:, :n] + bias[:n])
    post = 2 * jax.nn.sigmoid(alpha[1] * proj[:, n:2 * n] + bias[n:2 * n])
    res = jnp.exp(jnp.clip(
        (alpha[2] * proj[:, 2 * n:] + bias[2 * n:]).reshape(-1, n, n),
        sz.mhc_h_res_clamp_min, sz.mhc_h_res_clamp_max))
    for _ in range(sz.hc_sinkhorn_iters):
        res = res / (res.sum(-1, keepdims=True) + sz.hc_eps)
        res = res / (res.sum(-2, keepdims=True) + sz.hc_eps)
    return pre, post, res


def _sublayer(sz, params, x, name, fn):
    """``x <- res x + post^T fn(sum_i pre_i x_i)`` for the streams ``x``
    [seq, n, C]."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    pre, post, res = hc_weights(sz, x, params[f"{name}_phi"],
                                params[f"{name}_alpha"],
                                params[f"{name}_bias"])
    out = fn(jnp.einsum("sn,snc->sc", pre, x, precision=hi))
    return jnp.einsum("sij,sjc->sic", res, x, precision=hi) \
        + post[:, :, None] * out[:, None, :]


def _attention(sz, params, i, h, mm, row_block):
    """MLA of layer ``i`` over one sequence ``h`` [seq, hidden], the
    latent expanded into every head's keys and values, causal, a block
    of query rows at a time."""
    import jax
    import jax.numpy as jnp
    seq = h.shape[0]
    H, dn, R, dv = (sz.num_attention_heads, sz.qk_nope_head_dim,
                    sz.qk_rope_head_dim, sz.v_head_dim)
    C, eps, pre = sz.kv_lora_rank, sz.rms_norm_eps, f"layer_{i}"
    inv_freq, factor, scale = yarn_table(sz)
    positions = jnp.arange(seq)

    def w(name):
        return params[f"{pre}_{name}.w_0"].astype(jnp.float32)

    a = _rms_norm(h, params[f"{pre}_input_norm_scale"], eps)
    c_q = _rms_norm(mm(a, w("q_a_proj")), params[f"{pre}_q_a_norm_scale"],
                    eps)
    q = mm(c_q, w("q_b_proj")).reshape(seq, H, dn + R).transpose(1, 0, 2)
    kv = mm(a, w("kv_a_proj"))
    c_kv = _rms_norm(kv[:, :C], params[f"{pre}_kv_a_norm_scale"], eps)
    k_pe = _rope(kv[:, C:], positions, inv_freq, factor)      # [seq, R]
    up = mm(c_kv, w("kv_b_proj")).reshape(seq, H, dn + dv).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], positions,
                                            inv_freq, factor)], -1)
    k = jnp.concatenate([up[..., :dn], jnp.broadcast_to(
        k_pe, (H, seq, R))], -1)
    v = up[..., dn:]
    cols = jnp.arange(seq)[None, :]

    def block(start):
        rows = start + jnp.arange(row_block)[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, start, row_block, axis=1)
        s = mm(qb, jnp.swapaxes(k, -1, -2)) * scale
        p = jax.nn.softmax(jnp.where(cols <= rows, s, -jnp.inf), axis=-1)
        return mm(p, v)                                   # [H, rb, dv]

    out = jax.lax.map(block, jnp.arange(0, seq, row_block))
    ctx = out.transpose(0, 2, 1, 3).reshape(seq, H * dv)  # [nb, rb, H, dv]
    return mm(ctx, w("o_proj"))


def _swiglu(x, gate, up, down, mm):
    import jax
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _ffn(sz, params, i, h, mm):
    """The dense MLP, or the routed experts (every expert over every
    token, kept where the router chose it) and the shared expert."""
    import jax
    import jax.numpy as jnp
    pre = f"layer_{i}"
    f = _rms_norm(h, params[f"{pre}_post_attn_norm_scale"], sz.rms_norm_eps)

    def w(name):
        return params[f"{pre}_{name}.w_0"].astype(jnp.float32)

    if not sz.is_moe(i):
        return _swiglu(f, w("gate_proj"), w("up_proj"), w("down_proj"), mm)
    scores = jax.nn.sigmoid(jnp.matmul(
        f, params[f"{pre}_router.w_0"], precision=jax.lax.Precision.HIGHEST))
    _, top_i = jax.lax.top_k(scores + params[f"{pre}_router_e_bias"],
                             sz.num_experts_per_tok)
    top_w = jnp.take_along_axis(scores, top_i, -1)
    if sz.norm_topk_prob:
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    top_w = top_w * sz.routed_scaling_factor
    weight = jnp.sum(jax.nn.one_hot(top_i, sz.n_routed_experts,
                                    dtype=jnp.float32) * top_w[..., None], 1)
    gates, ups, downs = (params[f"{pre}_experts_{k}.w_0"]
                         for k in ("gate", "up", "down"))

    def one(acc, e):
        y = _swiglu(f, gates[e].astype(jnp.float32),
                    ups[e].astype(jnp.float32),
                    downs[e].astype(jnp.float32), mm)
        return acc + weight[:, e][:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(f),
                             jnp.arange(sz.n_routed_experts))
    return routed + _swiglu(f, w("shared_gate_proj"), w("shared_up_proj"),
                            w("shared_down_proj"), mm)


def reference_hidden(sz, params, tokens, mode="highest", row_block=None):
    """One sequence's forward pass: ``[seq]`` ids to the final-normed
    hidden state ``[seq, hidden]`` after the head mix. Right padding does
    not reach an earlier position."""
    import jax
    import jax.numpy as jnp
    mm = _matmul(mode)
    seq = tokens.shape[0]
    row_block = row_block or math.gcd(seq, 512)
    n = sz.hc_mult
    e = params["embed_tokens"][tokens].astype(jnp.float32)
    x = jnp.broadcast_to(e[:, None, :], (seq, n, e.shape[-1]))
    for i in range(sz.num_hidden_layers):
        pre = f"layer_{i}"
        x = _sublayer(sz, params, x, f"{pre}_hc_attn",
                      lambda h: _attention(sz, params, i, h, mm, row_block))
        x = _sublayer(sz, params, x, f"{pre}_hc_ffn",
                      lambda h: _ffn(sz, params, i, h, mm))
    flat = x.reshape(seq, -1)
    xbar = flat / jnp.sqrt(jnp.mean(jnp.square(flat), -1, keepdims=True)
                           + sz.rms_norm_eps)
    mix = jax.nn.sigmoid(params["hc_head_alpha"][0] * jnp.matmul(
        xbar, params["hc_head_phi"], precision=jax.lax.Precision.HIGHEST)
        + params["hc_head_bias"])
    h = jnp.einsum("sn,snc->sc", mix, x, precision=jax.lax.Precision.HIGHEST)
    return _rms_norm(h, params["final_norm_scale"], sz.rms_norm_eps)


def reference_logits(sz, params, tokens, mode="highest"):
    """``[rows, seq]`` ids to ``[rows, seq, vocab]`` logits (small sizes:
    the tests' comparison; the served comparison takes the logits at the
    served positions only)."""
    import jax.numpy as jnp
    mm = _matmul(mode)
    head = params["lm_head.w_0"].astype(jnp.float32)
    return jnp.stack([mm(reference_hidden(sz, params, row, mode), head)
                      for row in tokens])


@functools.lru_cache(maxsize=None)
def _gap_fn(sz, mode, span):
    import jax
    import jax.numpy as jnp

    def gaps(params, tokens, first):
        """tokens [pad_to + 1]; the logits at positions ``first`` ..
        ``first + span - 1`` choose tokens[first + 1 ..]."""
        head = params["lm_head.w_0"].astype(jnp.float32)

        def logits(m):
            hidden = reference_hidden(sz, params, tokens[:-1], m)
            rows = jax.lax.dynamic_slice_in_dim(hidden, first, span, 0)
            return _matmul(m)(rows, head)                # [span, vocab]

        ref = logits("highest")
        best = jnp.max(ref, axis=-1)
        if mode == "highest":
            chosen = jax.lax.dynamic_slice_in_dim(tokens, first + 1, span)
        else:
            # the control does not decode: at each position of the same
            # prompt and tokens, the token the lower precision puts first
            chosen = jnp.argmax(logits(mode), axis=-1)
        got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
        return best - got

    return jax.jit(gaps)


def reference_served_gaps(sz, seed, rows, pad_to, mode="highest"):
    """For each served reply, by how much the reference's logit of every
    served token lies below the reference's best at that position.
    ``rows`` holds ``(prompt, served)`` id arrays; each row is run once,
    teacher-forced, alone, padded on the right to ``pad_to`` (one
    compiled shape)."""
    import jax.numpy as jnp
    if not rows:
        return []
    params = init_params(sz, seed)
    span = max(served.size for _, served in rows)
    # a power of two at least the longest reply: few compiled shapes
    span = min(1 << (span - 1).bit_length(), pad_to)
    fn = _gap_fn(sz, mode, span)
    out = []
    for prompt, served in rows:
        packed = np.zeros(pad_to + 1, np.int32)
        packed[:prompt.size + served.size] = np.concatenate(
            [prompt, served])
        first = prompt.size - 1             # logits here pick served[0]
        gaps = np.asarray(fn(params, jnp.asarray(packed),
                             jnp.int32(min(first, pad_to - span))))
        shift = first - min(first, pad_to - span)
        out.append(gaps[shift:shift + served.size])
    return out
