#!/usr/bin/env python3
"""Do two trees lower the serve cells' executables to one text?

    python tools/lowering_identity.py lower ROOT OUT [gpt2-medium] \
        [mellum] [ouro] [jamba]
    python tools/lowering_identity.py diff OUT_A OUT_B

``lower`` imports ``paddle_tpu`` and ``benchmark`` from the tree at ROOT (a
checkout, or a ``git archive`` of a commit) and lowers, with abstract
weights and an abstract pool (nothing is placed on a device), the kinds of
executable the GPT-2 medium, Mellum, Ouro and Jamba serve cells warm (all
four unless some are named): prefills of 1 to 8 rows at 32 to 6,144 with the
scatter of each and, on a tree that has it, the admission that runs both
and the greedy pick as one executable, the paged decode step, the picks. It writes each ``.mlir`` text and the feed and fetch names under
OUT. With ``IDENTITY_TPU_HERE=1`` the kernels take their TPU branch and the
text is lowered for the TPU platform with no chip; Mosaic's serialized
bodies are in it, and their ``loc(...)`` carry the checkout's path, so
``diff`` decodes each body and takes the path out before it compares
two such outputs; it exits 1 on any difference. One process a tree: the
module under ROOT is what ``import paddle_tpu`` finds. PR 31 wrote it, PR 33 used it again
(``PERF.md`` section 6), PR 37 gave it the Jamba cell (the scatter of a
pool with a state group takes the rows' slots too).
"""
import base64
import hashlib
import inspect
import json
import os
import re
import sys

CONFIGS = {
    # name: (configuration file, family, prefills (rows, length), pick rows)
    "gpt2-medium": ("gpt2-medium.json", "gpt",
                    [(1, 64), (4, 256), (8, 1024)], [1, 4, 8, 32]),
    "mellum": ("mellum2-12b-a2.5b.json", "mellum",
               [(1, 1024), (2, 3072), (4, 6144)], [1, 2, 4, 32]),
    "ouro": ("ouro-2.6b.json", "ouro",
             [(1, 32), (2, 64), (4, 128)], [1, 2, 4, 16]),
    "jamba": ("jamba2-3b.json", "jamba",
              [(1, 256), (2, 1024), (4, 2048)], [1, 2, 4, 64]),
}


def lower_tree(root, out, which):
    root, out = os.path.abspath(root), os.path.abspath(out)
    sys.path.insert(0, root)
    os.chdir(root)
    os.makedirs(out, exist_ok=True)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as fluid
    assert os.path.abspath(fluid.__file__).startswith(root), fluid.__file__
    from paddle_tpu import flags
    from paddle_tpu.models.generation import GPTGenerator
    from paddle_tpu.serving import GenerationEngine
    from paddle_tpu.serving.kvpool import decode_feed

    SDS = jax.ShapeDtypeStruct
    tpu_here = os.environ.get("IDENTITY_TPU_HERE") == "1"
    if tpu_here:
        from paddle_tpu.kernels import _dispatch
        _dispatch.auto_impl = lambda: "pallas"
    report = {"device": str(jax.devices()[0]), "root": root, "kinds": {},
              "lowered_for": "tpu (described, no chip)" if tpu_here
              else jax.devices()[0].platform}

    def lower(jitted, *args):
        if tpu_here:
            return jitted.trace(*args).lower(lowering_platforms=("tpu",))
        return jitted.lower(*args)

    def i32(*shape):
        return SDS(shape, jnp.int32)

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda a: SDS(np.shape(a), a.dtype), tree)

    def save(tag, lowered, names=None):
        # the one place the tree's own path shows: put it aside
        text = lowered.as_text().replace(root, "<root>")
        with open(os.path.join(out, tag + ".mlir"), "w") as fh:
            fh.write(text)
        report["kinds"][tag] = {
            "sha256": hashlib.sha256(text.encode()).hexdigest(),
            "bytes": len(text), **(names or {})}
        print(tag, len(text), flush=True)

    def build(config_file, family):
        with open(os.path.join(root, "benchmark", "configs",
                               config_file)) as fh:
            cfg_json = json.load(fh)
        serve = cfg_json["serve"]
        fam = __import__("benchmark.families." + family, fromlist=["x"])
        sz = fam.Sizes(cfg_json)
        flags.set_flags({"FLAGS_kv_cache_dtype": serve["kv_cache_dtype"]})
        cfg = fam.program_config(sz)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            model = __import__("paddle_tpu.models." + family,
                               fromlist=["x"])
            getattr(model, family + "_logits")(cfg)
        gen = GPTGenerator(cfg, fluid.Scope(), max_len=serve["max_len"])
        gen.bind_params({
            p.name: SDS(tuple(p.shape), jnp.dtype(str(p.dtype)))
            for p in main.all_parameters()})
        eng = GenerationEngine(gen, slots=serve["decode_slots"])
        pool = eng.pool
        shapes = jax.eval_shape(lambda: dict(type(pool).arrays(pool)))
        pool._arrays = shapes     # abstract: nothing is placed on a device
        report[config_file] = {
            "slots": eng.slots, "max_len": gen.max_len,
            "pool_dtype": pool.dtype, "pool_blocks": pool.num_blocks,
            "block_size": pool.block_size,
            "pool_arrays": {n: [list(a.shape), str(a.dtype)]
                            for n, a in shapes.items()}}
        return gen, eng, pool

    def lower_kind(gen, tag, kind, feed):
        jitted, state = gen._ensure_fn(kind)
        caches = {n: a for n, a in feed.items() if n.startswith("cache_")}
        rest = {n: a for n, a in feed.items()
                if not n.startswith("cache_")}
        key = jax.random.PRNGKey(0)
        lowered = lower(jitted, state, caches, rest, key)
        outs = gen._ensure_prog(kind)[1]
        feed_names = list(outs["feed_names"])
        fetch_names = gen._fetch_names(outs)
        save(tag, lowered, {
            "feed_names": feed_names, "fetch_names": fetch_names,
            "cache_places": gen._cache_places(outs, feed_names,
                                              fetch_names)})
        return jitted, state, caches, rest, key

    def unpack_caches(gen, kind, fetches):
        # PR 33 gave _unpack_caches the kind: either tree's form
        if len(inspect.signature(gen._unpack_caches).parameters) == 2:
            return gen._unpack_caches(kind, fetches)
        return gen._unpack_caches(fetches)

    for name in which:
        config_file, family, prefills, pick_rows = CONFIGS[name]
        tag = config_file[:-len(".json")]
        gen, eng, pool = build(config_file, family)
        kv = pool.dtype
        pre_kind = gen.arch.prefill_kind(kv)
        for rows, s in prefills:
            feed = {"tokens": i32(rows, s), "pos_ids": i32(rows, s),
                    "last_pos": i32(rows)}
            jitted, state, caches, rest, key = lower_kind(
                gen, f"{tag}.{pre_kind}.r{rows}.s{s}", pre_kind, feed)
            # the scatter of that prefill's row caches into the pool
            fetched, kept, _ = jax.eval_shape(jitted, state, caches, rest,
                                              key)
            _, row_caches = unpack_caches(
                gen, pre_kind, gen._unpack[pre_kind](fetched, kept))
            nblk = pool.blocks_for_tokens(s)
            ring = (None, None)
            if pool.window is not None:
                ring = (i32(rows, pool.window.ring),
                        i32(rows * pool.window.ring))
            state = (i32(rows),) if pool.state_arrays else ()
            save(f"{tag}.scatter_prefill.r{rows}.b{nblk}", lower(
                pool._scatter()._jit, dict(pool._arrays),
                abstract(dict(row_caches)), i32(rows, nblk), *ring, *state))
            if hasattr(pool, "scatter_layout"):
                # the served admission: that prefill, its greedy pick and
                # that scatter in one executable
                kind = f"{pre_kind}+sample_greedy"
                jitted, state = gen._ensure_fn(kind, pool.scatter_layout())
                rest = dict(feed, **abstract(pool.scatter_indices(
                    list(range(rows)), s, [s] * rows)))
                save(f"{tag}.{kind}.r{rows}.s{s}", lower(
                    jitted, state, dict(pool._arrays), rest, key))
        slots = eng.slots
        feed = abstract(decode_feed(pool, np.zeros(slots, np.int32),
                                    np.zeros(slots, np.int32)))
        lower_kind(gen, f"{tag}.decode_paged_{kv}.r{slots}",
                   f"decode_paged_{kv}", feed)
        v = gen.cfg.vocab_size
        for rows in pick_rows:
            logits = SDS((rows, v), jnp.float32)
            temp, topk = SDS((rows,), jnp.float32), i32(rows)
            lower_kind(gen, f"{tag}.sample_greedy.r{rows}",
                       "sample_greedy", {"logits": logits})
            lower_kind(gen, f"{tag}.sample_temp.r{rows}", "sample_temp",
                       {"logits": logits, "temperature": temp})
            lower_kind(gen, f"{tag}.sample.r{rows}", "sample",
                       {"logits": logits, "temperature": temp,
                        "top_k": topk})
    with open(os.path.join(out, "report.json"), "w") as fh:
        json.dump(report, fh, indent=1, sort_keys=True)
    print("done", len(report["kinds"]), "kinds", report["device"])
    return 0


def _uncounted(names):
    """Variable names less the global unique-name counter of their op."""
    return [re.sub(r"_\d+(?=\.tmp_)", "", n) for n in names or ()]


def _plain(text, root):
    """``text`` with every Mosaic body (base64 in ``backend_config``)
    decoded, the tree's own path taken out of its ``loc(...)``, and put
    back as a digest: two trees at different paths then compare equal
    where their kernels and the lines that call them are."""
    def body(found):
        text = found.group(1)           # its padding may be left off
        raw = base64.b64decode(text + "=" * (-len(text) % 4))
        return "body <%s>" % hashlib.sha256(
            raw.replace(root.encode(), b"<root>")).hexdigest()
    # {\22custom_call_config\22: {\22body\22: \22<base64>\22, ...
    return re.sub(r"body\\22: \\22([A-Za-z0-9+/=]+)\\22", body, text)


def diff(a, b):
    with open(os.path.join(a, "report.json")) as fh:
        ra = json.load(fh)
    with open(os.path.join(b, "report.json")) as fh:
        rb = json.load(fh)
    bad = 0
    print("devices:", ra["device"], "|", rb["device"])
    for cfg in sorted(k for k in ra if k.endswith(".json")):
        same = ra[cfg] == rb.get(cfg)
        bad += not same
        print(cfg, "sizes equal" if same else "SIZES DIFFER",
              {k: v for k, v in ra[cfg].items() if k != "pool_arrays"})
    kinds = sorted(set(ra["kinds"]) | set(rb["kinds"]))
    for k in kinds:
        ka, kb = ra["kinds"].get(k), rb["kinds"].get(k)
        if ka is None or kb is None:
            print(k, "MISSING on one side")
            bad += 1
            continue
        with open(os.path.join(a, k + ".mlir")) as fh:
            ta = _plain(fh.read(), ra["root"])
        with open(os.path.join(b, k + ".mlir")) as fh:
            tb = _plain(fh.read(), rb["root"])
        text = ta == tb
        feeds = ka.get("feed_names") == kb.get("feed_names")
        fetch = ka.get("fetch_names") == kb.get("fetch_names")
        if not fetch and _uncounted(ka["fetch_names"]) == _uncounted(
                kb["fetch_names"]):
            fetch = True
            print(f"{k}: fetch names differ only by the unique-name "
                  f"counter")
        places = ka.get("cache_places") == kb.get("cache_places")
        print(f"{k}: text {'IDENTICAL' if text else 'DIFFERS'} "
              f"({len(ta)} bytes, sha256 {ka['sha256'][:12]}); feeds "
              f"{'equal' if feeds else 'DIFFER'}; "
              f"{len(ka.get('fetch_names') or ())} fetch names "
              f"{'equal' if fetch else 'DIFFER'}; cache places "
              f"{'equal' if places else 'DIFFER'}")
        bad += (not text) + (not feeds) + (not fetch) + (not places)
    print("TOTAL", len(kinds), "kinds;", "ALL IDENTICAL" if not bad
          else f"{bad} DIFFERENCES")
    return 1 if bad else 0


def main(argv):
    if len(argv) >= 3 and argv[0] == "lower":
        return lower_tree(argv[1], argv[2], argv[3:] or list(CONFIGS))
    if len(argv) == 3 and argv[0] == "diff":
        return diff(argv[1], argv[2])
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
