"""The Ouro family (ByteDance ``Ouro-2.6B``, a looped language model), as
the benchmark knows it: a decoder of RMSNorm before and after each
sub-layer, rotary positions, plain multi-head attention and a SwiGLU MLP,
whose whole stack of layers is run ``total_ut_steps`` times over the same
weights, with a final RMSNorm and an exit gate at the end of every pass
and an untied head.

What lives here and nowhere in the program, as in ``families/gpt.py`` and
``families/mellum.py``:

* ``init_params``: every weight from ``--seed``, made on the device. The
  matrices are drawn in float32 and rounded once to bfloat16, the type the
  configuration serves them in, so that program and reference hold the
  same numbers; RMSNorm gains (ones) and the exit gate stay float32.
* ``build_generator``: how a cell hands the model to the program
  (``models/ouro.py`` through ``GPTGenerator``).
* the closed-form counts the readers use: ``serve_flops`` (every matrix
  ``total_ut_steps`` times a token, attention in every (pass, layer)
  pair), ``kv_bytes_per_position`` (a cache a (pass, layer) pair: more
  cache layers than weight layers), ``stack_weight_bytes`` and
  ``head_weight_bytes`` (what one pass, and one token's logits, must read).
* ``reference_logits`` / ``reference_served_gaps``: the model written from
  its equations in plain ``jax.numpy`` at float32 with
  ``precision=highest``: no kernel, no cache, no batching. It imports
  nothing of ``paddle_tpu``. Every pass is a full causal forward over the
  whole sequence, which is what a cache a (pass, layer) pair has to
  reproduce: the keys of pass ``u`` come from pass ``u``'s hidden states.
  The weights are upcast a layer at a time from the one bfloat16 copy.
  ``mode`` lowers every product's operands but the gate's to ``bf16`` or
  ``fp8``: the controls that ``correct`` has to fail.

Departures from the source, each for want of a key in ``config.json``
(the configuration file lists them under ``assumed``): the four norms a
block, the final norm at the end of every pass and the gate
``sigmoid(h w + b)`` with its bias are the family's published description,
not keys; no projection bias; weights normal(0, 0.02), gains 1, gate bias
0. With ``early_exit_threshold`` 1 every token leaves at the last pass;
below 1 the reference takes, a token, the hidden state of the first pass
at which the exit distribution's running sum reaches the threshold.
"""
import functools
import math

import numpy as np

from .gpt import _matmul, _seed_key

INT_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "total_ut_steps",
            "max_position_embeddings")


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
        self.rms_norm_eps = float(src["rms_norm_eps"])
        self.rope_theta = float(src["rope_theta"])
        self.early_exit_threshold = float(src["early_exit_threshold"])
        self.initializer_range = float(src.get("initializer_range", 0.02))
        if src["hidden_act"] != "silu" or src["tie_word_embeddings"] \
                or src.get("rope_scaling") or src.get("sliding_window"):
            raise ValueError("this family is a silu MLP, an untied head, "
                             "plain rotary positions and no window")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into the KV heads")

    @property
    def cache_layers(self):
        """A KV cache a (pass, layer) pair."""
        return self.total_ut_steps * self.num_hidden_layers


def param_shapes(sz):
    """name -> (shape, kind) under the names ``models/ouro.py`` gives
    them; kind is ``matrix`` (bfloat16), ``gate`` or ``bias`` (float32)
    or ``gain``."""
    h, d, f = sz.hidden_size, sz.head_dim, sz.intermediate_size
    hq, hkv = sz.num_attention_heads, sz.num_key_value_heads
    shapes = {"embed_tokens": ((sz.vocab_size, h), "matrix")}
    for i in range(sz.num_hidden_layers):
        pre = f"layer_{i}"
        shapes.update({
            f"{pre}_attn_in_norm_scale": ((h,), "gain"),
            # Wq | Wk | Wv side by side: one stored array, three products
            f"{pre}_qkv_proj.w_0": ((h, (hq + 2 * hkv) * d), "matrix"),
            f"{pre}_o_proj.w_0": ((hq * d, h), "matrix"),
            f"{pre}_attn_out_norm_scale": ((h,), "gain"),
            f"{pre}_mlp_in_norm_scale": ((h,), "gain"),
            f"{pre}_gate_proj.w_0": ((h, f), "matrix"),
            f"{pre}_up_proj.w_0": ((h, f), "matrix"),
            f"{pre}_down_proj.w_0": ((f, h), "matrix"),
            f"{pre}_mlp_out_norm_scale": ((h,), "gain"),
        })
    shapes.update({"final_norm_scale": ((h,), "gain"),
                   "exit_gate.w_0": ((h, 1), "gate"),
                   "exit_gate.b_0": ((1,), "bias"),
                   "lm_head.w_0": ((h, sz.vocab_size), "matrix")})
    return shapes


def param_count(sz):
    return sum(math.prod(s) for s, _ in param_shapes(sz).values())


def layer_matmul_params(sz):
    """The seven matrices of one block (q, k and v stored as one)."""
    h, d = sz.hidden_size, sz.head_dim
    return 2 * h * d * (sz.num_attention_heads + sz.num_key_value_heads) \
        + 3 * h * sz.intermediate_size


def matmul_params_per_token(sz):
    """Weights that take part in a product for one token: every block's
    matrices once a PASS, the gate once a pass, the head once. The
    embedding is a look-up."""
    return sz.total_ut_steps * (
        sz.num_hidden_layers * layer_matmul_params(sz) + sz.hidden_size) \
        + sz.hidden_size * sz.vocab_size


def stack_weight_bytes(sz, weight_bytes=2):
    """What ONE pass over the stack must read: every block's matrices
    (``weight_bytes`` an element) and its four float32 gains, the final
    norm and the gate."""
    return sz.num_hidden_layers * (
        layer_matmul_params(sz) * weight_bytes + 4 * sz.hidden_size * 4) \
        + (2 * sz.hidden_size + 1) * 4


def head_weight_bytes(sz, weight_bytes=2):
    """The untied head, read once an executable."""
    return sz.hidden_size * sz.vocab_size * weight_bytes


def causal_attention_flops(sz, batch, seq, backward=False):
    """QK^T and PV of every (pass, layer) pair over ``[batch, heads,
    seq, head_dim]``: 2 x 2 x head_dim operations a scored pair a head,
    the lower triangle."""
    if backward:
        raise ValueError("this family is served, not trained")
    pairs = seq * (seq + 1) / 2
    return 2 * 2 * batch * sz.num_attention_heads * sz.head_dim * pairs \
        * sz.cache_layers


def kv_bytes_per_position(sz, kv_bytes):
    """Keys and values of one position in every CACHE layer: a cache a
    (pass, layer) pair, ``total_ut_steps`` times the weight layers'."""
    return 2 * sz.cache_layers * sz.num_key_value_heads * sz.head_dim \
        * kv_bytes


def serve_flops(sz, prompt_len, new_tokens):
    """What one served reply needs: every prompt and every new token
    ``total_ut_steps`` times through the stack and once through the
    head, causal attention over the prompt in every (pass, layer) pair,
    and each decoded token's query against its context there."""
    ctx = prompt_len + np.arange(1, new_tokens)   # token j reads prompt + j
    decode_attn = 2 * 2 * sz.num_attention_heads * sz.head_dim \
        * sz.cache_layers * float(ctx.sum())
    return (2 * matmul_params_per_token(sz) * (prompt_len + new_tokens - 1)
            + causal_attention_flops(sz, 1, prompt_len) + decode_attn)


# ---------------------------------------------------------------- weights

@functools.lru_cache(maxsize=4)
def _init_fn(shape_items, std):
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shape_items):
            if kind in ("gain", "bias"):
                out[name] = jnp.full(shape, float(kind == "gain"),
                                     jnp.float32)
                continue
            w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            out[name] = w if kind == "gate" else w.astype(jnp.bfloat16)
        return out

    return jax.jit(make)


_LAST = {}      # (shapes, std, seed) -> the last weights made


def init_params(sz, seed):
    """normal(0, initializer_range) matrices, rounded once to bfloat16,
    a float32 exit gate with a zero bias, unit RMSNorm gains; on the
    device, one jitted call. The last result is kept and handed out
    again for the same sizes and seed: the weights are 5.3 GB and the
    pool 8.1 GB of a 16 GB chip, so the program (which binds them where
    they lie) and the reference read one copy, which neither writes."""
    items = tuple(param_shapes(sz).items())
    key = (items, sz.initializer_range, int(seed))
    if key not in _LAST:
        _LAST.clear()           # the other seed's go before these come
        _LAST[key] = _init_fn(items, sz.initializer_range)(_seed_key(seed))
    return dict(_LAST[key])


# ------------------------------------------------- handing it to the program

def program_config(sz):
    from paddle_tpu.models import ouro
    return ouro.OuroConfig(
        rms_norm_eps=sz.rms_norm_eps, rope_theta=sz.rope_theta,
        early_exit_threshold=sz.early_exit_threshold,
        tie_word_embeddings=False,
        initializer_range=sz.initializer_range, dtype="bfloat16",
        **{key: getattr(sz, key) for key in INT_KEYS})


def build_generator(sz, serve, seed):
    """A ``GPTGenerator`` bound to ``init_params``, with the cache type
    the configuration pins."""
    import paddle_tpu as fluid
    from paddle_tpu import flags
    from paddle_tpu.models import ouro
    from paddle_tpu.models.generation import GPTGenerator
    flags.set_flags({"FLAGS_kv_cache_dtype": serve["kv_cache_dtype"]})
    cfg = program_config(sz)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ouro.ouro_logits(cfg)
    have = {p.name: tuple(p.shape) for p in main.all_parameters()}
    want = {n: tuple(s) for n, (s, _) in param_shapes(sz).items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise RuntimeError(f"the program's parameters are not the "
                           f"family's: {odd}")
    # 5.3 GB of weights: bound where they lie, not pulled through the
    # host and put back as the scope's values would be
    gen = GPTGenerator(cfg, fluid.Scope(), max_len=serve["max_len"])
    gen.bind_params(init_params(sz, seed))
    return gen


# -------------------------------------------------------------- reference

def rope_table(sz):
    """Plain rotary frequencies ``theta ** (-2i / d)`` (``rope_scaling``
    is null in the source)."""
    d = sz.head_dim
    return (sz.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
            ).astype(np.float32)


def _rms_norm(x, gain, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def _rope(x, positions, inv_freq):
    """x [heads, seq, d]; lane i pairs with lane i + d/2."""
    import jax.numpy as jnp
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, mm):
    """Causal attention of one sequence. q [Hq, S, d], k, v [Hkv, S, d]
    (a KV head serves ``Hq / Hkv`` query heads)."""
    import jax
    import jax.numpy as jnp
    hq, seq, d = q.shape
    rep = hq // k.shape[0]
    kr, vr = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    s = mm(q, jnp.swapaxes(kr, -1, -2)) / math.sqrt(d)
    keep = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    return mm(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1), vr)


def reference_block(sz, params, i, x, positions, mm):
    """Block ``i`` over one sequence ``x`` [seq, hidden]: a norm before
    and after each sub-layer; its matrices upcast here, from the one
    bfloat16 copy."""
    import jax
    import jax.numpy as jnp
    seq = x.shape[0]
    hq, hkv, d = (sz.num_attention_heads, sz.num_key_value_heads,
                  sz.head_dim)
    eps, inv_freq, pre = sz.rms_norm_eps, rope_table(sz), f"layer_{i}"

    def w(name):
        return params[f"{pre}_{name}.w_0"].astype(jnp.float32)

    def gain(name):
        return params[f"{pre}_{name}_scale"]

    def heads(t, n):
        return t.reshape(seq, n, d).transpose(1, 0, 2)

    a = _rms_norm(x, gain("attn_in_norm"), eps)
    w_q, w_k, w_v = jnp.split(w("qkv_proj"), [hq * d, (hq + hkv) * d], 1)
    q = _rope(heads(mm(a, w_q), hq), positions, inv_freq)
    k = _rope(heads(mm(a, w_k), hkv), positions, inv_freq)
    v = heads(mm(a, w_v), hkv)
    ctx = _attention(q, k, v, mm).transpose(1, 0, 2).reshape(seq, hq * d)
    x = x + _rms_norm(mm(ctx, w("o_proj")), gain("attn_out_norm"), eps)
    f = _rms_norm(x, gain("mlp_in_norm"), eps)
    mlp = mm(jax.nn.silu(mm(f, w("gate_proj"))) * mm(f, w("up_proj")),
             w("down_proj"))
    return x + _rms_norm(mlp, gain("mlp_out_norm"), eps)


def exit_distribution(gates):
    """``gates`` [U, ...] -> the same shape: ``p_u = g_u prod_{j<u}(1 -
    g_j)`` for every pass but the last, which takes what is left."""
    import jax.numpy as jnp
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(gates * before)[:-1], before[-1:]], axis=0)


def reference_forward(sz, params, tokens, mode="highest"):
    """One sequence's forward pass: ``[seq]`` ids to ``(hidden [seq,
    hidden], exit_probs [seq, U])``, the hidden state final-normed, of
    the pass each token leaves at. Right padding does not reach an
    earlier position. The passes are one ``lax.scan`` over the same
    weights (the body is the whole stack)."""
    import jax
    import jax.numpy as jnp
    mm = _matmul(mode)
    positions = jnp.arange(tokens.shape[0])
    x = params["embed_tokens"].astype(jnp.float32)[tokens]

    def one_pass(h, _):
        for i in range(sz.num_hidden_layers):
            h = reference_block(sz, params, i, h, positions, mm)
        h = _rms_norm(h, params["final_norm_scale"], sz.rms_norm_eps)
        # the gate is float32 at precision=highest in every mode
        gate = jax.nn.sigmoid(jnp.matmul(
            h, params["exit_gate.w_0"],
            precision=jax.lax.Precision.HIGHEST) + params["exit_gate.b_0"])
        return h, (h, gate[:, 0])

    _, (hidden, gates) = jax.lax.scan(one_pass, x, None,
                                      length=sz.total_ut_steps)
    probs = exit_distribution(gates)                     # [U, seq]
    if sz.early_exit_threshold >= 1.0:
        return hidden[-1], probs.T      # always the last pass
    reached = jnp.cumsum(probs, axis=0) >= sz.early_exit_threshold
    leave = jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0),
                      sz.total_ut_steps - 1)
    picked = jnp.take_along_axis(hidden, leave[None, :, None], axis=0)[0]
    return picked, probs.T


def reference_logits(sz, params, tokens, mode="highest"):
    """``[rows, seq]`` ids to ``[rows, seq, vocab]`` logits (small sizes:
    the tests' comparison; the served comparison takes the logits at the
    served positions only)."""
    import jax.numpy as jnp
    mm = _matmul(mode)
    head = params["lm_head.w_0"].astype(jnp.float32)
    return jnp.stack([mm(reference_forward(sz, params, row, mode)[0], head)
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
            hidden, _ = reference_forward(sz, params, tokens[:-1], m)
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
