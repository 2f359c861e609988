"""The GPT-2 family, as the benchmark knows it.

Four things live here and nowhere in the program, so that no later change
to ``paddle_tpu`` can move the yardstick:

* ``init_params``: every weight of the model, made on the device in one
  jitted call from ``--seed``. The program's scope and the reference are
  both given these; the reference takes nothing the program has made.
* ``build_train`` / ``build_generator``: how a cell hands the model to the
  program (``models/gpt.py`` through ``Executor`` or ``GPTGenerator``),
  after ``chip_smoke.py``'s ``_build_train`` and ``_startup_scope``.
* ``param_count``, ``matmul_params``, ``train_flops_per_token`` and the
  attention counts: closed forms from the sizes, never from an executable.
* ``reference_*``: GPT-2 written from its equations (Radford et al. 2019;
  pre-LN decoder, learned positions, tied head) in plain ``jax.numpy`` at
  float32 with ``precision=highest``. It imports nothing of ``paddle_tpu``.
  ``mode`` lowers every matrix product's operands to ``bf16`` or ``fp8``
  (per-tensor scaled E4M3): those are the controls that ``correct`` has to
  fail, never a timed path.
"""
import functools
import math

import numpy as np

SIZE_KEYS = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head",
             "n_inner")


# ------------------------------------------------------------------ sizes

class Sizes:
    """The numbers of one configuration file (or of its ``rehearsal``
    group, for the CPU dry run)."""

    def __init__(self, config, rehearsal=False):
        src = dict(config)
        if rehearsal:
            src.update(config["rehearsal"])
        for key in SIZE_KEYS:
            setattr(self, key, int(src[key]))
        self.layer_norm_epsilon = float(src["layer_norm_epsilon"])
        self.initializer_range = float(src["initializer_range"])
        self.activation_function = src["activation_function"]
        if self.activation_function != "gelu":
            raise ValueError(
                "models/gpt.py has only the erf gelu; the configuration "
                f"file asks for {self.activation_function!r}")
        if self.n_embd % self.n_head:
            raise ValueError("n_embd must divide by n_head")
        self.d_head = self.n_embd // self.n_head


def param_shapes(sz):
    """name -> shape, under the names ``models/gpt.py`` gives them."""
    h, f = sz.n_embd, sz.n_inner
    shapes = {"word_embedding": (sz.vocab_size, h),
              "pos_embedding": (sz.n_positions, h)}
    for i in range(sz.n_layer):
        pre = f"decoder_layer_{i}"
        shapes.update({
            f"{pre}_pre_att_ln_scale": (h,), f"{pre}_pre_att_ln_bias": (h,),
            f"{pre}_qkv.w_0": (h, 3 * h), f"{pre}_qkv.b_0": (3 * h,),
            f"{pre}_att_out.w_0": (h, h), f"{pre}_att_out.b_0": (h,),
            f"{pre}_pre_ffn_ln_scale": (h,), f"{pre}_pre_ffn_ln_bias": (h,),
            f"{pre}_ffn_0.w_0": (h, f), f"{pre}_ffn_0.b_0": (f,),
            f"{pre}_ffn_1.w_0": (f, h), f"{pre}_ffn_1.b_0": (h,),
        })
    shapes.update({"final_ln_scale": (h,), "final_ln_bias": (h,)})
    return shapes


def param_count(sz):
    return sum(math.prod(s) for s in param_shapes(sz).values())


def matmul_params(sz):
    """Weights that take part in a matrix product for every token: the
    four matrices of each layer and the tied head. Position and token
    look-ups, biases and LayerNorm cost no product."""
    h, f = sz.n_embd, sz.n_inner
    return sz.n_layer * (3 * h * h + h * h + 2 * h * f) + sz.vocab_size * h


def causal_attention_flops(sz, batch, seq, backward):
    """QK^T and PV of causal attention over ``[batch, heads, seq, d]`` in
    every layer: the lower triangle only, 2 x seq^2/2 x d multiply-adds
    each. The backward pass needs four such products for the forward's
    two, with nothing recomputed."""
    forward = 2 * 2 * batch * sz.n_head * (seq * seq / 2) * sz.d_head
    return sz.n_layer * forward * (3 if backward else 1)


def train_flops_per_token(sz, seq):
    return 6 * matmul_params(sz) + causal_attention_flops(
        sz, 1, seq, backward=True) / seq


def serve_flops(sz, prompt_len, new_tokens):
    """What one served reply needs: every prompt and every new token once
    through the matrices, causal attention over the prompt, and each
    decoded token's query against the context it follows."""
    ctx = prompt_len + np.arange(1, new_tokens)   # token j reads prompt + j
    decode_attn = 2 * 2 * sz.n_layer * sz.n_embd * float(ctx.sum())
    return (2 * matmul_params(sz) * (prompt_len + new_tokens - 1)
            + causal_attention_flops(sz, 1, prompt_len, backward=False)
            + decode_attn)


def kv_bytes_per_position(sz, kv_bytes):
    """Keys and values of one position in every layer."""
    return 2 * sz.n_layer * sz.n_embd * kv_bytes


# ---------------------------------------------------------------- weights

def _seed_key(seed):
    """A key from any whole number up to 2**63: more than 32 bits hold."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


@functools.lru_cache(maxsize=4)
def _init_fn(shape_items, std):
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shape_items):
            if name.endswith("_scale"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith(("_bias", ".b_0")):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return jax.jit(make)


def init_params(sz, seed):
    """GPT-2's initialisation (normal(0, initializer_range) matrices and
    embeddings, zero biases, unit LayerNorm gains), float32, on the
    device, one jitted call."""
    items = tuple(param_shapes(sz).items())
    return _init_fn(items, sz.initializer_range)(_seed_key(seed))


# ------------------------------------------------- handing it to the program

def program_config(sz, dropout=0.0):
    from paddle_tpu.models import gpt
    return gpt.GPTConfig(
        vocab_size=sz.vocab_size, hidden_size=sz.n_embd,
        num_layers=sz.n_layer, num_heads=sz.n_head, ffn_size=sz.n_inner,
        max_position=sz.n_positions, dropout=dropout,
        initializer_range=sz.initializer_range)


def _place(scope, program, params):
    """Put the benchmark's weights where the startup program put its own,
    and refuse a program whose parameters are not exactly these."""
    have = {p.name: tuple(p.shape) for p in program.all_parameters()}
    want = {n: tuple(a.shape) for n, a in params.items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise RuntimeError(f"the program's parameters are not the "
                           f"family's: {odd}")
    for name, value in params.items():
        scope.set(name, value)


def build_train(sz, train, batch, seq, seed):
    """``gpt_pretrain`` + Adam under bf16 AMP, as ``chip_smoke.py`` builds
    it, started and then given ``init_params``. Returns what a training
    driver needs: the executor, its scope, the program, the loss's name
    and the first-moment variable of every parameter."""
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models import gpt
    if train["optimizer"] != "adam" or train["amp"] != "bfloat16":
        raise ValueError(f"unknown training recipe {train}")
    cfg = program_config(sz)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        out = gpt.gpt_pretrain(cfg, batch, seq)
        adam = fluid.optimizer.AdamOptimizer(
            train["learning_rate"], beta1=train["beta1"],
            beta2=train["beta2"], epsilon=train["epsilon"])
        mp.decorate(adam, init_loss_scaling=1.0,
                    use_dynamic_loss_scaling=False).minimize(out["loss"])
    exe, scope = fluid.Executor(), fluid.Scope()

    def restart(seed):
        """Fresh optimizer state and the weights of ``seed``."""
        with fluid.scope_guard(scope):
            exe.run(startup)
        _place(scope, main, init_params(sz, seed))

    restart(seed)
    moment1 = {name: var.name
               for name, var in adam._accumulators["moment1"].items()}
    return {"exe": exe, "scope": scope, "main": main, "restart": restart,
            "loss": out["loss"].name, "moment1": moment1}


def train_feed(tokens):
    """The program's four feeds from ``[batch, seq + 1]`` token ids."""
    batch, seq = tokens.shape[0], tokens.shape[1] - 1
    return {"tokens": tokens[:, :-1].copy(), "labels": tokens[:, 1:].copy(),
            "loss_mask": np.ones((batch, seq), np.float32),
            "pos_ids": np.broadcast_to(np.arange(seq, dtype=np.int32),
                                       (batch, seq)).copy()}


def build_generator(sz, serve, seed):
    """A scope holding ``init_params`` and a ``GPTGenerator`` over it, with
    the cache type the configuration pins."""
    import paddle_tpu as fluid
    from paddle_tpu import flags
    from paddle_tpu.models import gpt
    from paddle_tpu.models.generation import GPTGenerator
    flags.set_flags({"FLAGS_kv_cache_dtype": serve["kv_cache_dtype"]})
    cfg = program_config(sz)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    scope = fluid.Scope()
    _place(scope, main, init_params(sz, seed))
    return GPTGenerator(cfg, scope, max_len=serve["max_len"])


# -------------------------------------------------------------- reference

def _quantise(x, mode):
    import jax.numpy as jnp
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    # fp8 E4M3 with a per-tensor scale to its largest magnitude, as fp8
    # recipes do; without it gradients of 1e-5 would all round to nought
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.lru_cache(maxsize=None)
def _matmul(mode):
    """``a @ b`` over the last two axes. ``highest`` is the reference;
    ``bf16`` and ``fp8`` round the operands of the product and of both
    products of its backward pass, and accumulate in float32."""
    import jax
    import jax.numpy as jnp
    hi = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    if mode == "highest":
        return hi

    def swap(x):
        return jnp.swapaxes(x, -1, -2)

    def unbroadcast(g, like):
        while g.ndim > like.ndim:
            g = g.sum(0)
        return g

    @jax.custom_vjp
    def mm(a, b):
        return hi(_quantise(a, mode), _quantise(b, mode))

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        g, a, b = (_quantise(x, mode) for x in (g, a, b))
        return (unbroadcast(hi(g, swap(b)), a),
                unbroadcast(hi(swap(a), g), b))

    mm.defvjp(fwd, bwd)
    return mm


def _layer_norm(x, gain, bias, eps):
    import jax.numpy as jnp
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def reference_logits(sz, params, tokens, mode="highest"):
    """GPT-2's forward pass: ``[rows, seq]`` ids to ``[rows, seq, vocab]``
    logits. Right padding does not reach an earlier position."""
    import jax
    import jax.numpy as jnp
    mm = _matmul(mode)
    rows, seq = tokens.shape
    eps, nh, d = sz.layer_norm_epsilon, sz.n_head, sz.d_head
    x = params["word_embedding"][tokens] + params["pos_embedding"][:seq]
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def heads(t):
        return t.reshape(rows, seq, nh, d).transpose(0, 2, 1, 3)

    for i in range(sz.n_layer):
        pre = f"decoder_layer_{i}"
        a = _layer_norm(x, params[f"{pre}_pre_att_ln_scale"],
                        params[f"{pre}_pre_att_ln_bias"], eps)
        qkv = mm(a, params[f"{pre}_qkv.w_0"]) + params[f"{pre}_qkv.b_0"]
        q, k, v = (heads(t) for t in jnp.split(qkv, 3, axis=-1))
        scores = mm(q, jnp.swapaxes(k, -1, -2)) / math.sqrt(d)
        scores = jnp.where(causal, scores, -jnp.inf)
        ctx = mm(jax.nn.softmax(scores, axis=-1), v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(rows, seq, sz.n_embd)
        x = x + mm(ctx, params[f"{pre}_att_out.w_0"]) \
            + params[f"{pre}_att_out.b_0"]
        f = _layer_norm(x, params[f"{pre}_pre_ffn_ln_scale"],
                        params[f"{pre}_pre_ffn_ln_bias"], eps)
        f = jax.nn.gelu(mm(f, params[f"{pre}_ffn_0.w_0"])
                        + params[f"{pre}_ffn_0.b_0"], approximate=False)
        x = x + mm(f, params[f"{pre}_ffn_1.w_0"]) \
            + params[f"{pre}_ffn_1.b_0"]
    x = _layer_norm(x, params["final_ln_scale"], params["final_ln_bias"],
                    eps)
    return mm(x, params["word_embedding"].T)


@functools.lru_cache(maxsize=None)
def _block_grad(sz, mode):
    import jax
    import jax.numpy as jnp

    def summed_loss(params, tokens):
        logits = reference_logits(sz, params, tokens[:, :-1], mode)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.sum(picked)

    return jax.jit(jax.value_and_grad(summed_loss))


@functools.lru_cache(maxsize=None)
def _adam_fn(lr, beta1, beta2, eps):
    import jax
    import jax.numpy as jnp

    def update(params, grads, m, v, step):
        # Kingma & Ba 2015, section 2, the form with the bias corrections
        # folded into the step size
        lr_t = lr * jnp.sqrt(1 - beta2 ** step) / (1 - beta1 ** step)
        m = jax.tree.map(lambda m, g: beta1 * m + (1 - beta1) * g, m, grads)
        v = jax.tree.map(lambda v, g: beta2 * v + (1 - beta2) * g * g,
                         v, grads)
        params = jax.tree.map(
            lambda p, m, v: p - lr_t * m / (jnp.sqrt(v) + eps),
            params, m, v)
        return params, m, v

    return jax.jit(update, donate_argnums=(0, 2, 3))


@functools.lru_cache(maxsize=None)
def _norms_fn():
    import jax
    import jax.numpy as jnp

    def norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            a[k].astype(jnp.float32) - b[k].astype(jnp.float32))))
            for k in a}

    return jax.jit(norms)


def leaf_norms(a, b=None):
    """name -> Euclidean norm of ``a[name] - b[name]`` (of ``a[name]``
    without ``b``), computed on the device, as floats."""
    import jax
    import jax.numpy as jnp
    if b is None:
        b = {k: jnp.zeros((), jnp.float32) for k in a}
    return {k: float(x) for k, x in
            jax.device_get(_norms_fn()(a, b)).items()}


def reference_train(sz, train, seed, batches, mode="highest",
                    rows_per_block=2, keep_rows=None):
    """Follow the first ``len(batches)`` training steps from ``--seed``:
    the mean next-token loss over ``[batch, seq + 1]`` ids, its gradient
    (rows are independent, so it is summed block of rows by block), Adam.
    Returns each step's loss, the norm of every leaf of the first
    gradient and of every leaf's change over the steps.

    ``keep_rows`` takes the loss over the first rows only: the fault of a
    step that leaves part of its batch out."""
    import jax
    import jax.numpy as jnp
    params = init_params(sz, seed)
    grad_fn = _block_grad(sz, mode)
    adam = _adam_fn(train["learning_rate"], train["beta1"], train["beta2"],
                    train["epsilon"])
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for step, tokens in enumerate(batches, 1):
        tokens = np.asarray(tokens)[:keep_rows]
        count = tokens.shape[0] * (tokens.shape[1] - 1)
        total, grads = 0.0, None
        for r in range(0, tokens.shape[0], rows_per_block):
            loss, g = grad_fn(params, jnp.asarray(tokens[r:r + rows_per_block]))
            total += float(loss)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        grads = jax.tree.map(lambda g: g / count, grads)
        losses.append(total / count)
        if grad_norms is None:
            grad_norms = leaf_norms(grads)
        params, m, v = adam(params, grads, m, v, float(step))
    change_norms = leaf_norms(params, init_params(sz, seed))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}


@functools.lru_cache(maxsize=None)
def _gap_fn(sz, mode):
    import jax
    import jax.numpy as jnp

    def gaps(params, tokens):
        ref = reference_logits(sz, params, tokens[:, :-1], "highest")
        best = jnp.max(ref, axis=-1)
        if mode == "highest":
            chosen = tokens[:, 1:]
        else:
            # the control does not decode: at each position of the same
            # prompt and tokens, the token the lower precision puts first
            chosen = jnp.argmax(reference_logits(
                sz, params, tokens[:, :-1], mode), axis=-1)
        got = jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
        return best - got

    return jax.jit(gaps)


def reference_served_gaps(sz, seed, rows, pad_to, mode="highest",
                          rows_per_block=4):
    """For each served reply, by how much the reference's logit of every
    served token lies below the reference's best at that position.
    ``rows`` holds ``(prompt, served)`` id arrays; each row is run once,
    teacher-forced, padded on the right to ``pad_to``."""
    import jax.numpy as jnp
    params = init_params(sz, seed)
    fn = _gap_fn(sz, mode)
    packed = np.zeros((len(rows), pad_to + 1), np.int32)
    for r, (prompt, served) in enumerate(rows):
        packed[r, :prompt.size + served.size] = np.concatenate(
            [prompt, served])
    out = []
    for r0 in range(0, len(rows), rows_per_block):
        block = packed[r0:r0 + rows_per_block]
        if block.shape[0] < rows_per_block:     # one compiled shape
            block = np.concatenate([block, np.zeros(
                (rows_per_block - block.shape[0], pad_to + 1), np.int32)])
        gaps = np.asarray(fn(params, jnp.asarray(block)))
        for r in range(r0, min(r0 + rows_per_block, len(rows))):
            prompt, served = rows[r]
            first = prompt.size - 1         # logits here pick served[0]
            out.append(gaps[r - r0, first:first + served.size])
    return out
