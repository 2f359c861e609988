"""The Mellum-2 family (JetBrains ``Mellum2-12B-A2.5B-Instruct``), as the
benchmark knows it: a decoder of RMSNorm, rotary positions, grouped-query
attention in window and full layers, and a top-k routed SwiGLU expert
layer in every block, with an untied head.

What lives here and nowhere in the program, as in ``families/gpt.py``:

* ``init_params``: every weight from ``--seed``, made on the device. The
  matrices are drawn in float32 and rounded once to bfloat16, the type the
  configuration serves them in, so that program and reference hold the
  same numbers; RMSNorm gains (ones) and the router stay float32.
* ``build_generator``: how a cell hands the model to the program
  (``models/mellum.py`` through ``GPTGenerator``).
* the closed-form counts the readers use: ``serve_flops`` (active
  parameters: ``num_experts_per_tok`` experts a token), the window-aware
  attention operations and key/value bytes, the expert layer's
  operations and bytes.
* ``reference_logits`` / ``reference_served_gaps``: the model written from
  its equations in plain ``jax.numpy`` at float32 with
  ``precision=highest``: no kernel, no cache, no batching, every expert
  applied to every token under the router's mask. It imports nothing of
  ``paddle_tpu`` and is never given the program's routing. Attention runs
  in blocks of query rows and the logits are taken at the served
  positions only, so that a 6,400-token reply fits the chip. ``mode``
  lowers every product's operands but the router's to ``bf16`` or ``fp8``:
  the controls that ``correct`` has to fail.

Departures from the source, each for want of a key in ``config.json``
(the configuration file lists them under ``assumed``): no RMSNorm on q
and k; weights normal(0, 0.02), gains 1; the router is softmax over all
experts, then top-k, then renormalised (``norm_topk_prob``);
``intermediate_size`` is unused (every MLP is sparse); no multi-token
prediction head.
"""
import functools
import math

import numpy as np

from .gpt import _matmul, _seed_key

SLIDING, FULL = "sliding_attention", "full_attention"
INT_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window", "num_experts", "num_experts_per_tok",
            "moe_intermediate_size", "max_position_embeddings")


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
        self.layer_types = tuple(src["layer_types"])
        self.rope_parameters = src["rope_parameters"]
        self.rms_norm_eps = float(src["rms_norm_eps"])
        self.norm_topk_prob = bool(src["norm_topk_prob"])
        self.initializer_range = float(src.get("initializer_range", 0.02))
        if src["hidden_act"] != "silu" or src["tie_word_embeddings"] \
                or src.get("attention_bias"):
            raise ValueError("this family is silu experts, an untied "
                             "head and no attention bias")
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types must name every layer")

    def window(self, layer):
        return self.sliding_window \
            if self.layer_types[layer] == SLIDING else None


def param_shapes(sz):
    """name -> (shape, kind) under the names ``models/mellum.py`` gives
    them; kind is ``matrix`` (bfloat16), ``router`` or ``gain``."""
    h, d = sz.hidden_size, sz.head_dim
    hq, hkv = sz.num_attention_heads, sz.num_key_value_heads
    e, f = sz.num_experts, sz.moe_intermediate_size
    shapes = {"embed_tokens": ((sz.vocab_size, h), "matrix")}
    for i in range(sz.num_hidden_layers):
        pre = f"layer_{i}"
        shapes.update({
            f"{pre}_input_norm_scale": ((h,), "gain"),
            f"{pre}_q_proj.w_0": ((h, hq * d), "matrix"),
            f"{pre}_k_proj.w_0": ((h, hkv * d), "matrix"),
            f"{pre}_v_proj.w_0": ((h, hkv * d), "matrix"),
            f"{pre}_o_proj.w_0": ((hq * d, h), "matrix"),
            f"{pre}_post_attn_norm_scale": ((h,), "gain"),
            f"{pre}_router.w_0": ((h, e), "router"),
            f"{pre}_experts_gate.w_0": ((e, h, f), "matrix"),
            f"{pre}_experts_up.w_0": ((e, h, f), "matrix"),
            f"{pre}_experts_down.w_0": ((e, f, h), "matrix"),
        })
    shapes.update({"final_norm_scale": ((h,), "gain"),
                   "lm_head.w_0": ((h, sz.vocab_size), "matrix")})
    return shapes


def param_count(sz):
    return sum(math.prod(s) for s, _ in param_shapes(sz).values())


def active_matmul_params(sz):
    """Weights that take part in a product for one token: the four
    projections, the router and ``num_experts_per_tok`` experts in every
    layer, and the head. The embedding is a look-up."""
    h, d = sz.hidden_size, sz.head_dim
    attn = 2 * h * d * (sz.num_attention_heads + sz.num_key_value_heads)
    experts = sz.num_experts_per_tok * 3 * h * sz.moe_intermediate_size
    return sz.num_hidden_layers * (attn + h * sz.num_experts + experts) \
        + h * sz.vocab_size


def _pairs(seq, window):
    """(query, key) pairs a causal layer scores over ``seq`` positions:
    the lower triangle, cut to the last ``window`` keys of each query."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2
    return window * (window + 1) / 2 + (seq - window) * window


def causal_attention_flops(sz, batch, seq, backward=False):
    """QK^T and PV of every layer over ``[batch, heads, seq, head_dim]``:
    2 x 2 x head_dim operations a scored pair a query head, the pairs
    window-limited where the layer is."""
    if backward:
        raise ValueError("this family is served, not trained")
    pairs = sum(_pairs(seq, sz.window(i))
                for i in range(sz.num_hidden_layers))
    return 2 * 2 * batch * sz.num_attention_heads * sz.head_dim * pairs


def decode_positions(sz, context):
    """Cache positions, summed over the layers, that one decode step of a
    row at ``context`` positions reads: all in a full layer, the last
    ``sliding_window`` in a window layer."""
    return sum(min(context, sz.window(i) or context)
               for i in range(sz.num_hidden_layers))


def kv_bytes_per_layer_position(sz, kv_bytes):
    """Keys and values of one position in one layer."""
    return 2 * sz.num_key_value_heads * sz.head_dim * kv_bytes


def serve_flops(sz, prompt_len, new_tokens):
    """What one served reply needs: every prompt and every new token once
    through the active matrices, window-limited causal attention over the
    prompt, and each decoded token's query against what its layer keeps."""
    ctx = prompt_len + np.arange(1, new_tokens)   # token j reads prompt + j
    per_pos = 2 * 2 * sz.num_attention_heads * sz.head_dim
    decode_attn = per_pos * float(sum(decode_positions(sz, int(c))
                                      for c in ctx))
    return (2 * active_matmul_params(sz) * (prompt_len + new_tokens - 1)
            + causal_attention_flops(sz, 1, prompt_len) + decode_attn)


def expert_layer_work(sz, assignments, experts_hit, weight_bytes=2):
    """``(flops, bytes)`` of the expert products of expert-layer calls
    that routed ``assignments`` (token, expert) pairs in all and touched
    ``experts_hit`` experts in all: three d x f products a pair; each
    touched expert's three matrices once, and a pair's row in
    (``weight_bytes`` an element) and out (float32)."""
    h, f = sz.hidden_size, sz.moe_intermediate_size
    flops = assignments * 3 * 2 * h * f
    nbytes = experts_hit * 3 * h * f * weight_bytes \
        + assignments * h * (weight_bytes + 4)
    return flops, nbytes


# ---------------------------------------------------------------- weights

@functools.lru_cache(maxsize=4)
def _init_fn(shape_items, std):
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shape_items):
            if kind == "gain":
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            out[name] = w if kind == "router" else w.astype(jnp.bfloat16)
        return out

    return jax.jit(make)


_LAST = {}      # (shapes, std, seed) -> the last weights made


def init_params(sz, seed):
    """normal(0, initializer_range) matrices, rounded once to bfloat16,
    a float32 router, unit RMSNorm gains; on the device, one jitted
    call. The last result is kept and handed out again for the same
    sizes and seed: the weights are 7.6 GB of a 16 GB chip, so the
    program (which binds them where they lie) and the reference read
    one copy, which neither writes."""
    items = tuple(param_shapes(sz).items())
    key = (items, sz.initializer_range, int(seed))
    if key not in _LAST:
        _LAST.clear()           # the other seed's go before these come
        _LAST[key] = _init_fn(items, sz.initializer_range)(_seed_key(seed))
    return dict(_LAST[key])


# ------------------------------------------------- handing it to the program

def program_config(sz):
    from paddle_tpu.models import mellum
    return mellum.MellumConfig(
        layer_types=list(sz.layer_types),
        rope_parameters=sz.rope_parameters, rms_norm_eps=sz.rms_norm_eps,
        norm_topk_prob=sz.norm_topk_prob, tie_word_embeddings=False,
        initializer_range=sz.initializer_range, dtype="bfloat16",
        **{key: getattr(sz, key) for key in INT_KEYS})


def build_generator(sz, serve, seed):
    """A ``GPTGenerator`` bound to ``init_params``, with the cache type
    the configuration pins."""
    import paddle_tpu as fluid
    from paddle_tpu import flags
    from paddle_tpu.models import mellum
    from paddle_tpu.models.generation import GPTGenerator
    flags.set_flags({"FLAGS_kv_cache_dtype": serve["kv_cache_dtype"]})
    cfg = program_config(sz)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        mellum.mellum_logits(cfg)
    have = {p.name: tuple(p.shape) for p in main.all_parameters()}
    want = {n: tuple(s) for n, (s, _) in param_shapes(sz).items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise RuntimeError(f"the program's parameters are not the "
                           f"family's: {odd}")
    # 7.6 GB of weights: bound where they lie, not pulled through the
    # host and put back as the scope's values would be
    gen = GPTGenerator(cfg, fluid.Scope(), max_len=serve["max_len"])
    gen.bind_params(init_params(sz, seed))
    return gen


# -------------------------------------------------------------- reference

def rope_table(sz, layer_type):
    """``(inv_freq [head_dim / 2], attention_factor)``: plain rotary
    ``theta ** (-2i / d)``, or YaRN (Peng et al. 2023) as the source's
    library computes it once from the config: the plain frequencies and
    their ``factor``-fold interpolation, blended linearly between the
    dimensions that make ``beta_fast`` and ``beta_slow`` turns over the
    original context; cos and sin are scaled by ``attention_factor``."""
    par = sz.rope_parameters[layer_type]
    d, base = sz.head_dim, float(par["rope_theta"])
    plain = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if par["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    factor = float(par["factor"])
    orig = float(par["original_max_position_embeddings"])

    def dim_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(par["beta_fast"])), 0)
    high = min(math.ceil(dim_of(par["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    inv_freq = plain / factor * (1.0 - keep) + plain * keep
    return inv_freq.astype(np.float32), float(par["attention_factor"])


def _rms_norm(x, gain, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def _rope(x, positions, inv_freq, factor):
    """x [heads, seq, d]; lane i pairs with lane i + d/2."""
    import jax.numpy as jnp
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(sz, q, k, v, window, mm, row_block):
    """Causal (window-limited) grouped-query attention of one sequence,
    a block of query rows at a time. q [Hq, S, d], k, v [Hkv, S, d]."""
    import jax
    import jax.numpy as jnp
    hq, seq, d = q.shape
    rep = hq // k.shape[0]
    kr, vr = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    cols = jnp.arange(seq)[None, :]

    def block(start):
        rows = start + jnp.arange(row_block)[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, start, row_block, axis=1)
        s = mm(qb, jnp.swapaxes(kr, -1, -2)) / math.sqrt(d)
        keep = cols <= rows
        if window is not None:
            keep = keep & (cols > rows - window)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return mm(p, vr)                                 # [Hq, rb, d]

    starts = jnp.arange(0, seq, row_block)
    out = jax.lax.map(block, starts)                     # [nb, Hq, rb, d]
    return out.transpose(1, 0, 2, 3).reshape(hq, seq, d)


def _experts(sz, x, router, w_gate, w_up, w_down, mm):
    """Every expert over every token, kept where the router chose it:
    softmax over all experts in float32, the k largest, renormalised."""
    import jax
    import jax.numpy as jnp
    probs = jax.nn.softmax(
        jnp.matmul(x, router, precision=jax.lax.Precision.HIGHEST), -1)
    top_w, top_i = jax.lax.top_k(probs, sz.num_experts_per_tok)
    if sz.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    # [S, E]: the weight of expert e for the token, 0 where not chosen
    weight = jnp.sum(jax.nn.one_hot(top_i, sz.num_experts,
                                    dtype=jnp.float32)
                     * top_w[..., None], axis=1)

    def one(acc, e):
        g = mm(x, w_gate[e].astype(jnp.float32))
        u = mm(x, w_up[e].astype(jnp.float32))
        y = mm(jax.nn.silu(g) * u, w_down[e].astype(jnp.float32))
        return acc + weight[:, e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(sz.num_experts))
    return out, top_i, top_w


def reference_hidden(sz, params, tokens, mode="highest", row_block=None):
    """One sequence's forward pass: ``[seq]`` ids to the final-normed
    hidden states ``[seq, hidden]``. Right padding does not reach an
    earlier position."""
    import jax.numpy as jnp
    mm = _matmul(mode)
    seq = tokens.shape[0]
    row_block = row_block or math.gcd(seq, 512)
    hq, hkv, d = (sz.num_attention_heads, sz.num_key_value_heads,
                  sz.head_dim)
    eps = sz.rms_norm_eps
    positions = jnp.arange(seq)

    def w(name):
        return params[name].astype(jnp.float32)

    x = w("embed_tokens")[tokens]
    for i in range(sz.num_hidden_layers):
        pre = f"layer_{i}"
        inv_freq, factor = rope_table(sz, sz.layer_types[i])
        a = _rms_norm(x, params[f"{pre}_input_norm_scale"], eps)

        def heads(t, n):
            return t.reshape(seq, n, d).transpose(1, 0, 2)

        q = _rope(heads(mm(a, w(f"{pre}_q_proj.w_0")), hq), positions,
                  inv_freq, factor)
        k = _rope(heads(mm(a, w(f"{pre}_k_proj.w_0")), hkv), positions,
                  inv_freq, factor)
        v = heads(mm(a, w(f"{pre}_v_proj.w_0")), hkv)
        ctx = _attention(sz, q, k, v, sz.window(i), mm, row_block)
        ctx = ctx.transpose(1, 0, 2).reshape(seq, hq * d)
        x = x + mm(ctx, w(f"{pre}_o_proj.w_0"))
        f = _rms_norm(x, params[f"{pre}_post_attn_norm_scale"], eps)
        moe, _, _ = _experts(
            sz, f, params[f"{pre}_router.w_0"],
            params[f"{pre}_experts_gate.w_0"],
            params[f"{pre}_experts_up.w_0"],
            params[f"{pre}_experts_down.w_0"], mm)
        x = x + moe
    return _rms_norm(x, params["final_norm_scale"], eps)


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
