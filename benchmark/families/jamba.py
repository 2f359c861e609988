"""The Jamba family (AI21 ``AI21-Jamba2-3B``), as the benchmark knows it:
a decoder of Mamba-1 state-space layers with an attention layer every
``attn_layer_period`` (grouped queries, no rotary and no other positional
encoding), a SwiGLU MLP after every mixer, RMSNorm before each sub-layer
and on the mixer's step, input map and output map, and a head tied to the
embedding.

What lives here and nowhere in the program, as in ``families/gpt.py``,
``families/mellum.py`` and ``families/ouro.py``:

* ``init_params``: every weight from ``--seed``, made on the device. The
  matrices are drawn in float32 and rounded once to bfloat16, the type the
  configuration serves them in, so that program and reference hold the
  same numbers; the convolution, ``a_log``, ``d``, ``dt_bias`` and the
  RMSNorm gains stay float32.
* ``build_generator``: how a cell hands the model to the program
  (``models/jamba.py`` through ``GPTGenerator``).
* the closed-form counts the readers use: ``serve_flops`` (matrices, both
  attentions, the convolution and the recurrence), ``kv_bytes_per_position``
  (the attention layers alone: 1,024 B at the published sizes),
  ``state_bytes_per_row`` (what a served row keeps in every Mamba layer,
  whatever its context's length), ``stack_weight_bytes`` /
  ``head_weight_bytes`` and ``selective_scan_work`` (the operations and
  bytes the recurrence needs, from shapes, whatever implements it).
* ``reference_logits`` / ``reference_served_gaps``: the model written from
  its equations in plain ``jax.numpy`` at float32 with
  ``precision=highest``: the recurrence a ``lax.scan`` over single tokens,
  the convolution four shifted products, no kernel, no cache, no chunking,
  no batching. It imports nothing of ``paddle_tpu``. The weights are
  upcast a layer at a time from the one bfloat16 copy. ``mode`` lowers
  every matrix product's operands to ``bf16`` or ``fp8``: the controls
  that ``correct`` has to fail. The convolution and the recurrence are
  float32 in every mode, as the configuration states them.

Departures from the source, each for want of a key in ``config.json``
(the configuration file lists them under ``assumed``): which layers are
attention (``i % attn_layer_period == attn_layer_offset``), the three
inner RMSNorms, pre-norm residuals and the absence of any positional
encoding are the family's published code, not keys; no projection bias
but the convolution's and the step's; matrices normal(0, 0.02), the
convolution's taps uniform in +-1/sqrt(d_conv) and ``a_log`` = log(1 ..
d_state) a channel, ``dt_bias`` the inverse softplus of a step log-uniform
in 1e-3 to 1e-1 (the family's initialisation: the state neither dies nor
saturates on random weights), ``d`` = 1.
"""
import functools
import math

import numpy as np

from .gpt import _matmul, _seed_key

INT_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "attn_layer_period", "attn_layer_offset",
            "expert_layer_period", "expert_layer_offset", "num_experts",
            "num_experts_per_tok", "mamba_expand", "mamba_d_state",
            "mamba_d_conv", "mamba_dt_rank", "num_logits_to_keep",
            "max_position_embeddings")
BOOL_KEYS = ("mamba_conv_bias", "mamba_proj_bias", "use_mamba_kernels",
             "tie_word_embeddings")
DT_MIN, DT_MAX = 1e-3, 1e-1


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
        for key in BOOL_KEYS:
            setattr(self, key, bool(src[key]))
        self.rms_norm_eps = float(src["rms_norm_eps"])
        self.initializer_range = float(src.get("initializer_range", 0.02))
        if src["hidden_act"] != "silu" or src.get("sliding_window") \
                or self.num_experts != 1 or not self.tie_word_embeddings \
                or not self.mamba_conv_bias or self.mamba_proj_bias:
            raise ValueError("this family is a silu MLP in every layer, a "
                             "tied head, a biased convolution, unbiased "
                             "projections and no window")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError("heads must divide the hidden size, and "
                             "query heads into the KV heads")
        self.head_dim = self.hidden_size // self.num_attention_heads
        self.inner = self.mamba_expand * self.hidden_size

    @property
    def layers_block_type(self):
        return ["attention" if i % self.attn_layer_period
                == self.attn_layer_offset else "mamba"
                for i in range(self.num_hidden_layers)]

    @property
    def attention_layers(self):
        return self.layers_block_type.count("attention")

    @property
    def mamba_layers(self):
        return self.layers_block_type.count("mamba")


def param_shapes(sz):
    """name -> (shape, kind) under the names ``models/jamba.py`` gives
    them; kind is ``matrix`` (bfloat16), ``gain``, ``taps``, ``bias``,
    ``a_log``, ``d`` or ``dt_bias`` (float32 all)."""
    h, d, f = sz.hidden_size, sz.head_dim, sz.intermediate_size
    hq, hkv = sz.num_attention_heads, sz.num_key_value_heads
    inner, n, rank = sz.inner, sz.mamba_d_state, sz.mamba_dt_rank
    shapes = {"embed_tokens": ((sz.vocab_size, h), "matrix")}
    for i, kind in enumerate(sz.layers_block_type):
        pre = f"layer_{i}"
        shapes[f"{pre}_in_norm_scale"] = ((h,), "gain")
        if kind == "attention":
            shapes.update({
                # Wq | Wk | Wv side by side: one stored array
                f"{pre}_qkv_proj.w_0": ((h, (hq + 2 * hkv) * d), "matrix"),
                f"{pre}_o_proj.w_0": ((hq * d, h), "matrix")})
        else:
            shapes.update({
                f"{pre}_in_proj.w_0": ((h, 2 * inner), "matrix"),   # x | z
                f"{pre}_conv.w_0": ((sz.mamba_d_conv, inner), "taps"),
                f"{pre}_conv.b_0": ((inner,), "bias"),
                f"{pre}_x_proj.w_0": ((inner, rank + 2 * n), "matrix"),
                f"{pre}_dt_norm_scale": ((rank,), "gain"),
                f"{pre}_b_norm_scale": ((n,), "gain"),
                f"{pre}_c_norm_scale": ((n,), "gain"),
                f"{pre}_dt_proj.w_0": ((rank, inner), "matrix"),
                f"{pre}_dt_bias": ((inner,), "dt_bias"),
                f"{pre}_a_log": ((inner, n), "a_log"),
                f"{pre}_d": ((inner,), "d"),
                f"{pre}_out_proj.w_0": ((inner, h), "matrix")})
        shapes.update({
            f"{pre}_ff_norm_scale": ((h,), "gain"),
            f"{pre}_gate_proj.w_0": ((h, f), "matrix"),
            f"{pre}_up_proj.w_0": ((h, f), "matrix"),
            f"{pre}_down_proj.w_0": ((f, h), "matrix")})
    shapes["final_norm_scale"] = ((h,), "gain")
    return shapes


def param_count(sz):
    return sum(math.prod(s) for s, _ in param_shapes(sz).values())


def _matrix_params(sz, of):
    """Elements of the ``matrix`` parameters whose name ``of`` keeps."""
    return sum(math.prod(s) for name, (s, kind) in param_shapes(sz).items()
               if kind == "matrix" and of(name))


def matmul_params_per_token(sz):
    """Weights that take part in a product for one token: every layer's
    matrices and the tied head. The embedding is a look-up, counted once
    as the head."""
    return _matrix_params(sz, lambda name: True)


def stack_weight_bytes(sz, weight_bytes=2):
    """What one pass over the stack must read: every layer's matrices
    (``weight_bytes`` an element) and its float32 vectors."""
    shapes = param_shapes(sz)
    vectors = sum(math.prod(s) for s, kind in shapes.values()
                  if kind != "matrix")
    return _matrix_params(sz, lambda name: name != "embed_tokens") \
        * weight_bytes + vectors * 4


def head_weight_bytes(sz, weight_bytes=2):
    """The embedding table, read whole once an executable as the tied
    head (the look-up reads a row a token)."""
    return sz.hidden_size * sz.vocab_size * weight_bytes


def causal_attention_flops(sz, batch, seq, backward=False):
    """QK^T and PV of the attention layers over ``[batch, heads, seq,
    head_dim]``: 2 x 2 x head_dim operations a scored pair a head, the
    lower triangle."""
    if backward:
        raise ValueError("this family is served, not trained")
    pairs = seq * (seq + 1) / 2
    return 2 * 2 * batch * sz.num_attention_heads * sz.head_dim * pairs \
        * sz.attention_layers


def kv_bytes_per_position(sz, kv_bytes):
    """Keys and values of one position in the attention layers."""
    return 2 * sz.attention_layers * sz.num_key_value_heads * sz.head_dim \
        * kv_bytes


def state_bytes_per_row(sz):
    """What a served row keeps in every Mamba layer whatever its
    context's length: the state ``[d_state, inner]`` and the
    convolution's last ``d_conv - 1`` inputs, float32."""
    return sz.mamba_layers * sz.inner * 4 \
        * (sz.mamba_d_state + sz.mamba_d_conv - 1)


def selective_scan_work(sz, tokens, rows):
    """``(operations, bytes)`` the recurrences of every Mamba layer need
    for ``tokens`` real tokens in ``rows`` rows, whatever implements
    them. A token a channel a state: the decay's argument, the decay
    times the state, the input's product and its sum, the output's
    product and its sum (six; the exponential is not counted). Bytes: x,
    the step, the gate in and y out (float32, ``inner`` each) and the
    two maps a token; the state in and out a row."""
    per_token = 6 * sz.inner * sz.mamba_d_state
    token_bytes = 4 * (4 * sz.inner + 2 * sz.mamba_d_state)
    row_bytes = 2 * 4 * sz.inner * sz.mamba_d_state
    return (sz.mamba_layers * tokens * per_token,
            sz.mamba_layers * (tokens * token_bytes + rows * row_bytes))


def serve_flops(sz, prompt_len, new_tokens):
    """What one served reply needs: every prompt and every new token once
    through the matrices and the head, through every Mamba layer's
    convolution (``d_conv`` multiply-adds a channel) and recurrence
    (``selective_scan_work``), causal attention over the prompt in the
    attention layers and each decoded token's query against its context
    there."""
    tokens = prompt_len + new_tokens - 1
    ctx = prompt_len + np.arange(1, new_tokens)   # token j reads prompt + j
    decode_attn = 2 * 2 * sz.num_attention_heads * sz.head_dim \
        * sz.attention_layers * float(ctx.sum())
    conv = 2 * sz.mamba_d_conv * sz.inner * sz.mamba_layers * tokens
    return (2 * matmul_params_per_token(sz) * tokens
            + selective_scan_work(sz, tokens, 1)[0] + conv
            + causal_attention_flops(sz, 1, prompt_len) + decode_attn)


# ---------------------------------------------------------------- weights

@functools.lru_cache(maxsize=4)
def _init_fn(shape_items, std):
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shape_items):
            k = jax.random.fold_in(key, i)
            if kind in ("gain", "d"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "a_log":
                out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[1] + 1, dtype=jnp.float32)), shape)
            elif kind == "dt_bias":
                step = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                               * (math.log(DT_MAX) - math.log(DT_MIN))
                               + math.log(DT_MIN))
                # softplus(dt_bias) = step
                out[name] = step + jnp.log(-jnp.expm1(-step))
            elif kind == "taps":
                bound = 1.0 / math.sqrt(shape[0])
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -bound, bound)
            else:
                w = std * jax.random.normal(k, shape, jnp.float32)
                out[name] = w.astype(jnp.bfloat16) if kind == "matrix" else w
        return out

    return jax.jit(make)


_LAST = {}      # (shapes, std, seed) -> the last weights made


def init_params(sz, seed):
    """Module docstring's initialisation; on the device, one jitted call.
    The last result is kept and handed out again for the same sizes and
    seed: the weights are 6.06 GB of a 16 GB chip, so the program (which
    binds them where they lie) and the reference read one copy, which
    neither writes."""
    items = tuple(param_shapes(sz).items())
    key = (items, sz.initializer_range, int(seed))
    if key not in _LAST:
        _LAST.clear()           # the other seed's go before these come
        _LAST[key] = _init_fn(items, sz.initializer_range)(_seed_key(seed))
    return dict(_LAST[key])


# ------------------------------------------------- handing it to the program

def program_config(sz):
    from paddle_tpu.models import jamba
    return jamba.JambaConfig(
        rms_norm_eps=sz.rms_norm_eps, hidden_act="silu",
        sliding_window=None, initializer_range=sz.initializer_range,
        dtype="bfloat16",
        **{key: getattr(sz, key) for key in INT_KEYS + BOOL_KEYS})


def build_generator(sz, serve, seed):
    """A ``GPTGenerator`` bound to ``init_params``, with the cache type
    the configuration pins."""
    import paddle_tpu as fluid
    from paddle_tpu import flags
    from paddle_tpu.models import jamba
    from paddle_tpu.models.generation import GPTGenerator
    flags.set_flags({"FLAGS_kv_cache_dtype": serve["kv_cache_dtype"]})
    cfg = program_config(sz)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        jamba.jamba_logits(cfg)
    have = {p.name: tuple(p.shape) for p in main.all_parameters()}
    want = {n: tuple(s) for n, (s, _) in param_shapes(sz).items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise RuntimeError(f"the program's parameters are not the "
                           f"family's: {odd}")
    # 6 GB of weights: bound where they lie, not pulled through the host
    # and put back as the scope's values would be
    gen = GPTGenerator(cfg, fluid.Scope(), max_len=serve["max_len"])
    gen.bind_params(init_params(sz, seed))
    return gen


# -------------------------------------------------------------- reference

def _rms_norm(x, gain, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def _attention(q, k, v, mm):
    """Causal attention of one sequence, no positional encoding. q [Hq,
    S, d], k, v [Hkv, S, d] (a KV head serves ``Hq / Hkv`` query heads)."""
    import jax
    import jax.numpy as jnp
    hq, seq, d = q.shape
    rep = hq // k.shape[0]
    kr, vr = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    s = mm(q, jnp.swapaxes(kr, -1, -2)) / math.sqrt(d)
    keep = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    return mm(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1), vr)


def reference_attention(sz, params, i, x, mm):
    import jax.numpy as jnp
    seq = x.shape[0]
    hq, hkv, d = (sz.num_attention_heads, sz.num_key_value_heads,
                  sz.head_dim)
    pre = f"layer_{i}"

    def heads(t, n):
        return t.reshape(seq, n, d).transpose(1, 0, 2)

    a = _rms_norm(x, params[f"{pre}_in_norm_scale"], sz.rms_norm_eps)
    w_q, w_k, w_v = jnp.split(
        params[f"{pre}_qkv_proj.w_0"].astype(jnp.float32),
        [hq * d, (hq + hkv) * d], 1)
    ctx = _attention(heads(mm(a, w_q), hq), heads(mm(a, w_k), hkv),
                     heads(mm(a, w_v), hkv), mm)
    return mm(ctx.transpose(1, 0, 2).reshape(seq, hq * d),
              params[f"{pre}_o_proj.w_0"].astype(jnp.float32))


def reference_mamba(sz, params, i, x, mm):
    """The Mamba-1 mixer of layer ``i`` over one sequence ``x`` [seq,
    hidden], token by token from a zero state."""
    import jax
    import jax.numpy as jnp
    seq = x.shape[0]
    inner, n, rank, taps = (sz.inner, sz.mamba_d_state, sz.mamba_dt_rank,
                            sz.mamba_d_conv)
    eps, pre = sz.rms_norm_eps, f"layer_{i}"

    def w(name):
        return params[f"{pre}_{name}.w_0"].astype(jnp.float32)

    a = _rms_norm(x, params[f"{pre}_in_norm_scale"], eps)
    xi, z = jnp.split(mm(a, w("in_proj")), 2, axis=-1)
    # x_t from x_{t - taps + 1 .. t}: the last tap is the token's own
    padded = jnp.concatenate([jnp.zeros((taps - 1, inner)), xi])
    conv = params[f"{pre}_conv.b_0"] + sum(
        padded[j:j + seq] * params[f"{pre}_conv.w_0"][j]
        for j in range(taps))
    xc = jax.nn.silu(conv)
    dt, b, c = jnp.split(mm(xc, w("x_proj")), [rank, rank + n], axis=-1)
    dt = _rms_norm(dt, params[f"{pre}_dt_norm_scale"], eps)
    b = _rms_norm(b, params[f"{pre}_b_norm_scale"], eps)
    c = _rms_norm(c, params[f"{pre}_c_norm_scale"], eps)
    step = jax.nn.softplus(mm(dt, w("dt_proj")) + params[f"{pre}_dt_bias"])
    neg_a = -jnp.exp(params[f"{pre}_a_log"])                 # [inner, N]

    def token(state, at):
        step_t, x_t, b_t, c_t = at
        state = jnp.exp(step_t[:, None] * neg_a) * state \
            + (step_t * x_t)[:, None] * b_t[None, :]
        return state, jnp.sum(state * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((inner, n)), (step, xc, b, c))
    y = (y + params[f"{pre}_d"] * xc) * jax.nn.silu(z)
    return mm(y, w("out_proj"))


def reference_forward(sz, params, tokens, mode="highest"):
    """One sequence's forward pass: ``[seq]`` ids to the final-normed
    hidden states ``[seq, hidden]``. Right padding does not reach an
    earlier position: every mixer is causal."""
    import jax
    import jax.numpy as jnp
    mm = _matmul(mode)
    eps = sz.rms_norm_eps
    x = params["embed_tokens"].astype(jnp.float32)[tokens]
    for i, kind in enumerate(sz.layers_block_type):
        mixer = reference_attention if kind == "attention" \
            else reference_mamba
        x = x + mixer(sz, params, i, x, mm)
        pre = f"layer_{i}"
        f = _rms_norm(x, params[f"{pre}_ff_norm_scale"], eps)
        x = x + mm(jax.nn.silu(mm(f, params[f"{pre}_gate_proj.w_0"].astype(
            jnp.float32))) * mm(f, params[f"{pre}_up_proj.w_0"].astype(
                jnp.float32)), params[f"{pre}_down_proj.w_0"].astype(
                    jnp.float32))
    return _rms_norm(x, params["final_norm_scale"], eps)


def _head(params, hidden, mm):
    import jax.numpy as jnp
    return mm(hidden, params["embed_tokens"].astype(jnp.float32).T)


def reference_logits(sz, params, tokens, mode="highest"):
    """``[rows, seq]`` ids to ``[rows, seq, vocab]`` logits (small sizes:
    the tests' comparison; the served comparison takes the logits at the
    served positions only)."""
    import jax.numpy as jnp
    mm = _matmul(mode)
    return jnp.stack([_head(params, reference_forward(sz, params, row, mode),
                            mm) for row in tokens])


@functools.lru_cache(maxsize=None)
def _gap_fn(sz, mode, span):
    import jax
    import jax.numpy as jnp

    def gaps(params, tokens, first):
        """tokens [pad_to + 1]; the logits at positions ``first`` ..
        ``first + span - 1`` choose tokens[first + 1 ..]."""

        def logits(m):
            hidden = reference_forward(sz, params, tokens[:-1], m)
            rows = jax.lax.dynamic_slice_in_dim(hidden, first, span, 0)
            return _head(params, rows, _matmul(m))       # [span, vocab]

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
