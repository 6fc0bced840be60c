"""Driver for the cells that serve Falcon-H1's decoder (a Mamba-2
state-space mixer and grouped-query attention side by side in every block):
``InferenceEngine(HybridConfig, params, ServeConfig(...)).build()`` under
``ContinuousBatchingScheduler``.

The loop, the window, the stamps and the procedure of `correct` are
``gpt_serve``'s, by import, as in ``ling_serve``: ``gpt_serve.drive`` offers
the planned requests and stamps the tokens, ``gpt_serve.summarize`` reduces
them, ``gpt_serve.sample_served`` draws the finished requests that are
checked (the longest always in) and ``gpt_serve.counts`` reads the fault
counters and leaked pages.  This file differs in what it builds and what it
knows about it:

- **the model**: :func:`program_config` reads the configuration's
  published keys into a ``HybridConfig`` whose pattern is ``("ssm_gqa",
  "dense")`` a layer; :func:`seeded_weights` draws the weights from
  ``--seed`` leaf by leaf, in each leaf's own dtype, on the device, by the
  laws of the file's ``assumed.weights`` (:func:`leaf_law`).
- **the reference**: ``benchmark/reference/falcon_h1.py`` through
  :func:`reference_hidden` (one jitted call a LAYER: the f32 upcast of the
  bf16 weights never exceeds a layer, 1.7 GB) and :func:`head_blocks` (the
  head in blocks of the vocabulary: the f32 head alone is 5.3 GB).
  :func:`served_token_gaps` is ``gpt_serve``'s comparison: one reference
  forward a sampled request over prompt + served tokens; the widest gap by
  which a served token's logit lies below the reference's best; with
  ``control`` the token a lower precision's forward puts first.
- **the facts**: operations and bytes from ``benchmark/flops_falcon_h1.py``;
  a record, one entry a ``sched.step()``, of what the engine's calls of that
  step did (riders and live context summed over a decode block's
  iterations, prefills) for the per-layer readers in
  ``benchmark/falcon_h1_readers.py``; leaked decode SLOTS beside leaked
  pages.

Planted faults (``--plant``, rehearsal only): ``altered_token`` (a served
token altered where it is produced), ``state_not_reset`` (a prefill leaves
the slot's old state-space state under the new sequence's),
``dropped_attention_branch`` (the attention branch's output left out of
every block).
"""

from __future__ import annotations

import contextlib
import copy
import gc
import time

import numpy as np

from benchmark import flops_falcon_h1 as flops_h1
from benchmark.drivers import gpt_serve as base
from benchmark.reference import falcon_h1 as ref_h1

PLANTS = ("altered_token", "state_not_reset", "dropped_attention_branch")

#: vocabulary columns a head call of the reference takes
HEAD_BLOCK = 32640


# ---------------------------------------------------------------------------
# program objects from the configuration's published keys
# ---------------------------------------------------------------------------

def program_config(cfg):
    import jax.numpy as jnp
    from apex_tpu.models.hybrid import HybridConfig

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        # every layer is the parallel block (attn_layer_indices null)
        pattern=(("ssm_gqa", "dense"),) * cfg["num_hidden_layers"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        conv_kernel=cfg["mamba_d_conv"], ssm_heads=cfg["mamba_n_heads"],
        ssm_head_dim=cfg["mamba_d_head"], ssm_groups=cfg["mamba_n_groups"],
        ssm_state=cfg["mamba_d_state"], ssm_chunk=cfg["mamba_chunk_size"],
        embedding_multiplier=cfg["embedding_multiplier"],
        lm_head_multiplier=cfg["lm_head_multiplier"],
        attention_in_multiplier=cfg["attention_in_multiplier"],
        attention_out_multiplier=cfg["attention_out_multiplier"],
        key_multiplier=cfg["key_multiplier"],
        ssm_in_multiplier=cfg["ssm_in_multiplier"],
        ssm_out_multiplier=cfg["ssm_out_multiplier"],
        ssm_multipliers=tuple(cfg["ssm_multipliers"]),
        mlp_multipliers=tuple(cfg["mlp_multipliers"]),
        dtype=dt[cfg["compute_dtype"]], param_dtype=dt[cfg["param_dtype"]],
    )


def leaf_law(names, cfg):
    """A leaf's law by its name (the file's ``assumed.weights``):
    ``(law, a, b)`` as ``models.hybrid.draw_leaf`` takes them, or
    ``("normal_columns", blocks)`` — N(0, 1) times one spread a column
    block, ``blocks = ((columns, spread), ...)``.

    A matrix's spread is ``target / (sqrt(fan_in) * m)``, ``m`` the product
    of the published multipliers on that column's output: each
    pre-activation, each branch's contribution to the residual and the
    logits then have about unit spread over a unit-RMS input, where N(0,
    0.02) under multipliers meant for trained weights leaves logits of
    spread 0.01."""
    h = cfg["hidden_size"]
    ds, nh = cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    nq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    inter = cfg["intermediate_size"]
    name = names[-2] if names[-1] == "weight" else names[-1]
    if names[-1] == "scale" or name == "d":
        return "ones", 0.0, 0.0
    if name == "conv_bias":
        return "zeros", 0.0, 0.0
    if name == "a_log":
        return "log_of_uniform", 1.0, 16.0
    if name == "dt_bias":
        return "inv_softplus_log_uniform", 1e-3, 1e-1
    if name == "conv":
        return "normal", 0.0, 0.5

    def spread(fan_in, mult, target=1.0):
        return target / (fan_in ** 0.5 * mult)

    if name == "word_embeddings":
        return "normal", 0.0, 1.0 / cfg["embedding_multiplier"]
    if name == "lm_head":
        return "normal", 0.0, spread(h, cfg["lm_head_multiplier"])
    if name == "in_proj":
        m_in = cfg["ssm_in_multiplier"]
        mz, mx, mb, mc, mdt = cfg["ssm_multipliers"]
        return "normal_columns", tuple(
            (n, spread(h, m_in * m)) for n, m in (
                (ds, mz), (ds, mx), (gn, mb), (gn, mc), (nh, mdt)))
    if name == "out_proj":
        return "normal", 0.0, spread(ds, cfg["ssm_out_multiplier"])
    if name == "wqkv":
        m_in = cfg["attention_in_multiplier"]
        return "normal_columns", (
            (nq * d, spread(h, m_in)),
            (kv * d, spread(h, m_in * cfg["key_multiplier"])),
            (kv * d, spread(h, m_in)))
    if name == "wo":
        # a context row is an average of value rows: about a quarter of
        # their spread at a few hundred keys
        return "normal", 0.0, spread(
            nq * d, cfg["attention_out_multiplier"], 4.0)
    if name == "gate":
        return "normal", 0.0, spread(h, cfg["mlp_multipliers"][0])
    if name == "up":
        return "normal", 0.0, spread(h, 1.0)
    if name == "down":
        return "normal", 0.0, spread(inter, cfg["mlp_multipliers"][1])
    raise KeyError(f"no law for leaf {'/'.join(names)}")


def seeded_weights(shapes, seed: int, cfg):
    """Every leaf of ``shapes`` from ``seed``, one jitted call a leaf (one
    compile a law, shape and dtype), in the leaf's own dtype."""
    import functools

    import jax
    import jax.numpy as jnp
    from apex_tpu.models.hybrid import draw_leaf, path_names

    @functools.partial(jax.jit, static_argnums=(0, 1, 2))
    def draw_columns(blocks, shape, dtype, key):
        spreads = np.repeat(
            np.asarray([s for _, s in blocks], np.float32),
            [n for n, _ in blocks])
        return (jax.random.normal(key, shape, jnp.float32)
                * spreads).astype(dtype)

    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(leaves):
        law, *args = leaf_law(path_names(path), cfg)
        k = jax.random.fold_in(key, i)
        if law == "normal_columns":
            out.append(draw_columns(args[0], tuple(s.shape), s.dtype, k))
        else:
            out.append(draw_leaf(law, tuple(s.shape), s.dtype, k, *args))
    return jax.tree_util.tree_unflatten(treedef, out)


def to_reference(tree, cfg):
    """Program tree -> the reference's layout (slices, no arithmetic)."""
    t = tree["params"]
    nq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layers = []
    for lp in t["layers"]:
        s, a, f = lp["ssm"], lp["attn"], lp["mlp"]
        w = a["wqkv"]["weight"]
        layers.append(dict(
            norm1=lp["norm_mixer"]["scale"], norm2=lp["norm_ffn"]["scale"],
            w_in=s["in_proj"]["weight"], conv_w=s["conv"],
            conv_b=s["conv_bias"], dt_bias=s["dt_bias"], a_log=s["a_log"],
            d=s["d"], ssm_norm=s["norm"]["scale"],
            w_out=s["out_proj"]["weight"],
            wq=w[:, :nq * d], wk=w[:, nq * d:(nq + kv) * d],
            wv=w[:, (nq + kv) * d:], wo=a["wo"]["weight"],
            w_gate=f["gate"]["weight"], w_up=f["up"]["weight"],
            w_down=f["down"]["weight"]))
    return {"embed": t["word_embeddings"]["weight"],
            "head": t["lm_head"]["weight"], "norm_f": t["norm_f"]["scale"],
            "layers": layers}


# ---------------------------------------------------------------------------
# gpt_serve's loop, told this model's counts
# ---------------------------------------------------------------------------

class _Counts:
    """This model's operation counts under the names ``gpt_serve`` calls."""

    def __init__(self, cfg):
        # a token's count is linear in its context: the loop asks for it
        # once a token, 128 times a step
        self._flat = flops_h1.token_flops(cfg, 0, False)
        self._per_key = flops_h1.token_flops(cfg, 1, False) - self._flat
        self._logits = flops_h1.token_flops(cfg, 0, True) - self._flat

    def gpt_prefill_flops(self, cfg, n):
        return flops_h1.prefill_flops(cfg, n)

    def gpt_token_flops(self, cfg, ctx, logits):
        return self._flat + self._per_key * ctx + (
            self._logits if logits else 0.0)

    def gpt_kv_bytes_per_token(self, cfg, _bytes):
        return flops_h1.kv_bytes_per_token(cfg)

    def gpt_weight_bytes(self, cfg, _bytes):
        return flops_h1.weight_bytes(cfg)


@contextlib.contextmanager
def _as_gpt_serve(ctx):
    """``ctx`` as ``gpt_serve``'s functions read it — this configuration's
    keys under GPT-2's names too — with this model's counts in place of
    ``flops.gpt_*`` for the length of the block."""
    cfg = ctx.config
    seen = copy.copy(ctx)
    seen.config = dict(
        cfg, n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], n_embd=cfg["hidden_size"],
        n_inner=cfg["intermediate_size"],
        n_positions=cfg["max_position_embeddings"],
    )
    real, base.flops = base.flops, _Counts(cfg)
    try:
        yield seen
    finally:
        base.flops = real


def _watch(prog):
    """Record, one entry a ``sched.step()``, what the engine's calls of that
    step did: the decode program's rider-iterations (a decode block runs
    several iterations, each slot up to its own budget), the live context
    summed over them, and the prefills."""
    engine, sched, log = prog["engine"], prog["sched"], prog["step_log"]
    now = {}

    def fresh():
        now.clear()
        now.update(decodes=0, riders=0, ctx_sum=0, prefills=0)

    real_decode, real_prefill, real_step = (
        engine.decode, engine.prefill, sched.step)

    def decode(tokens, lengths, *a, **k):
        out = real_decode(tokens, lengths, *a, **k)
        lengths = np.asarray(lengths, np.int64)
        its = np.asarray(k["steps"], np.int64) if k.get("steps") is not None \
            else (lengths > 0).astype(np.int64)
        # iteration j of a slot reads its context at lengths + j
        now.update(decodes=now["decodes"] + 1,
                   riders=now["riders"] + int(its.sum()),
                   ctx_sum=now["ctx_sum"] + int(
                       (its * lengths + its * (its - 1) // 2).sum()))
        return out

    def prefill(*a, **k):
        out = real_prefill(*a, **k)
        now["prefills"] += 1
        return out

    def step():
        fresh()
        real_step()
        log.append(dict(now))

    fresh()
    engine.decode, engine.prefill, sched.step = decode, prefill, step


def _plant(ctx, prog):
    import jax

    engine, cfg = prog["engine"], ctx.config
    if ctx.planted == "altered_token":
        real = engine.decode

        def decode(*a, **k):
            logits, toks = real(*a, **k)
            return logits, (np.array(toks) + 1) % cfg["vocab_size"]

        engine.decode = decode
    elif ctx.planted == "dropped_attention_branch":
        tree = jax.tree_util.tree_map(lambda x: x, prog["params"])
        for lp in tree["params"]["layers"]:
            lp["attn"]["wo"]["weight"] = lp["attn"]["wo"]["weight"] * 0
        engine.params = tree        # the reference keeps prog["params"]
    elif ctx.planted == "state_not_reset":
        real = engine.prefill

        def prefill(prompt, pages, *, slot, **k):
            old = engine.cache["ssm"][:, slot]
            out = real(prompt, pages, slot=slot, **k)
            engine.cache = dict(engine.cache, ssm=engine.cache[
                "ssm"].at[:, slot].add(old))
            return out

        engine.prefill = prefill
    elif ctx.planted:
        raise SystemExit(f"unknown fault {ctx.planted!r}: {PLANTS}")


def build(ctx):
    """Weights from the seed, the engine with the cell's own programs, a
    scheduler, and a warm-up through every bucket the traffic can hit."""
    from apex_tpu.models.hybrid import param_shapes
    from apex_tpu.observability import MetricRegistry
    from apex_tpu.serve import (
        ContinuousBatchingScheduler, InferenceEngine, Request, ServeConfig,
    )

    cfg, mix = ctx.config, ctx.traffic
    pcfg = program_config(cfg)
    params = seeded_weights(param_shapes(pcfg), ctx.seed, cfg)
    sv = cfg["serve"]
    serve = ServeConfig(
        page_size=sv["page_size"], num_pages=sv["num_pages"],
        max_batch=sv["max_batch"], max_pages_per_seq=sv["max_pages_per_seq"],
        prefill_buckets=tuple(sv["prefill_buckets"]),
        decode_block=sv.get("decode_block", 1),
    )
    registry = MetricRegistry(fetch_every=1)
    engine = InferenceEngine(pcfg, params, serve, registry=registry)
    lo, hi = mix["prompt"].get("min", 1), mix["prompt"]["max"]
    buckets = sorted({engine.bucket_for(n) for n in range(lo, hi + 1)})
    engine.build(buckets=tuple(buckets))
    # the pool's accounting is proven once, after the drain (`run`), not at
    # every retirement (the proof walks every page)
    sched = ContinuousBatchingScheduler(
        engine, registry=registry, leak_checks=False)
    prog = {
        "params": params, "engine": engine, "sched": sched,
        "registry": registry, "buckets": buckets, "step_log": [],
    }
    _plant(ctx, prog)
    rng = np.random.default_rng(ctx.seed)
    warm = [
        sched.submit(Request(
            prompt=[int(t) for t in rng.integers(0, cfg["vocab_size"], size=n)],
            max_new_tokens=3,
        ))
        for b in buckets for n in (b, max(lo, b - 7))
    ]
    sched.run()
    if any(r.status != "done" for r in warm) or engine.pool.in_use \
            or sched.slots_in_use():
        raise SystemExit("warm-up did not complete cleanly")
    prog["compiled"] = dict(engine.compile_counts)
    _watch(prog)
    return prog


# ---------------------------------------------------------------------------
# `correct`: served tokens against the reference
# ---------------------------------------------------------------------------

_REFERENCE = {}


def _reference_programs(cfg, prec):
    """The reference's jitted pieces for one configuration and precision,
    made once a process: the embedding lookup, the block, the head over a
    block of the vocabulary.  ``prec`` is one of the references'
    precisions, or ``"bf16_state"``: f32 products with the state-space
    state rounded to bf16 after every token (the control of the
    configuration's ``assumed.state_dtype``)."""
    import json

    import jax
    import jax.numpy as jnp

    key = (json.dumps({k: v for k, v in cfg.items()
                       if not isinstance(v, dict)}, sort_keys=True), prec)
    if key in _REFERENCE:
        return _REFERENCE[key]

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    state_prec = "bf16" if prec == "bf16_state" else "f32"
    prec = "f32" if prec == "bf16_state" else prec
    programs = {
        "embed": jax.jit(lambda table, ids: ref_h1.embed(table, ids, cfg)),
        "block": jax.jit(lambda lp, x: ref_h1.block(
            f32(lp), x, cfg, prec, state_prec=state_prec)),
        "head": jax.jit(lambda norm, w, x: ref_h1.head(
            f32(norm), f32(w), x, cfg, prec)),
    }
    _REFERENCE[key] = programs
    return programs


def reference_hidden(cfg, weights, ids, prec="f32"):
    """``benchmark/reference/falcon_h1.py``'s forward up to the last block,
    one jitted call a layer: each call upcasts its own layer's weights and
    nothing else."""
    ref = _reference_programs(cfg, prec)
    x = ref["embed"](weights["embed"], ids)
    for lp in weights["layers"]:
        x = ref["block"](lp, x)
    return x


def head_blocks(cfg, weights, x, prec="f32"):
    """The reference's logits over ``x``, a block of the vocabulary at a
    time: yields ``(first column, logits (S, block))``."""
    ref = _reference_programs(cfg, prec)
    v = cfg["vocab_size"]
    step = min(HEAD_BLOCK, v)
    for first in range(0, v, step):
        yield first, ref["head"](
            weights["norm_f"], weights["head"][:, first:first + step], x)


def reference_logits(cfg, weights, ids, prec="f32"):
    """All the logits at once (the tests' and the rehearsal's sizes)."""
    import jax.numpy as jnp

    x = reference_hidden(cfg, weights, ids, prec)
    return jnp.concatenate(
        [blk for _, blk in head_blocks(cfg, weights, x, prec)], axis=-1)


def served_token_gaps(cfg, weights, seqs, *, control=None):
    """``gpt_serve.served_token_gaps`` for this reference: for each (prompt,
    served tokens) one forward over the whole sequence, padded to the
    longest a slot can hold, and for every served token the gap by which
    its logit lies below the reference's best at that position — the head
    walked in blocks of the vocabulary, a running best and the token's own
    logit kept."""
    import jax
    import jax.numpy as jnp

    sv = cfg["serve"]
    pad_to = sv["page_size"] * sv["max_pages_per_seq"]

    @jax.jit
    def fold(best, arg, blk, first):
        top = jnp.max(blk, axis=-1)
        return jnp.maximum(best, top), jnp.where(
            top > best, first + jnp.argmax(blk, axis=-1), arg)

    @jax.jit
    def pick(got, blk, first, tok):
        here = (tok >= first) & (tok < first + blk.shape[-1])
        at = jnp.take_along_axis(
            blk, jnp.clip(tok - first, 0, blk.shape[-1] - 1)[:, None],
            axis=-1)[:, 0]
        return jnp.where(here, at, got)

    def walk(x, prec, tok):
        """(best, argmax, the logit of ``tok``) over the whole vocabulary."""
        n = x.shape[0]
        best = jnp.full((n,), -jnp.inf, jnp.float32)
        arg = jnp.zeros((n,), jnp.int32)
        got = jnp.zeros((n,), jnp.float32)
        for first, blk in head_blocks(cfg, weights, x, prec):
            best, arg = fold(best, arg, blk, first)
            if tok is not None:
                got = pick(got, blk, first, tok)
        return best, arg, got

    worst, scale, n_tok = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for prompt, toks in seqs:
            seq = list(prompt) + list(toks)
            ids = np.zeros((pad_to,), np.int32)
            ids[: len(seq)] = seq
            ids = jnp.asarray(ids)
            x = reference_hidden(cfg, weights, ids)
            if control:
                # the token the lower precision's own forward puts first
                _, tok, _ = walk(
                    reference_hidden(cfg, weights, ids, control), control,
                    None)
            else:
                tok = jnp.roll(ids, -1)   # position i predicts token i+1
            best, _, got = walk(x, "f32", tok)
            pos = np.arange(pad_to)
            live = (pos >= len(prompt) - 1) & (pos <= len(seq) - 2)
            gaps = np.where(live, np.asarray(best - got), 0.0)
            worst = max(worst, float(gaps.max()))
            scale = max(scale, float(
                np.where(live, np.abs(np.asarray(best)), 0.0).max()))
            n_tok += len(toks)
    return worst, scale, n_tok


def run(ctx):
    mix = ctx.traffic
    prog = build(ctx)
    setup_s = time.monotonic() - ctx.t_process
    with _as_gpt_serve(ctx) as seen:
        prog["step_log"].clear()
        res = base.drive(seen, prog)
        if res["compiles_in_window"]:
            raise SystemExit(
                f"{res['compiles_in_window']} programs compiled inside the "
                "measured window"
            )
        memory_peak = ctx.memory_peak_bytes()
        e2e, facts, attempted, failed = base.summarize(seen, prog, res)
    faults, leaked = base.counts(prog)
    slots_leaked = prog["sched"].slots_in_use()
    try:
        # every allocated page's references against the live owners
        prog["sched"].leak_check()
    except ValueError:
        leaked += 1
    steps = prog["step_log"]
    decoded = [s for t, s in zip(facts["steps"], steps)
               if s["decodes"] and t[0] <= facts["window_s"]]
    values = prog["registry"].values()
    cfg = ctx.config
    block = cfg["serve"].get("decode_block", 1)
    facts.update(
        h1_steps=steps, config=cfg, decode_block=block,
        # per ITERATION of the decode program (a block runs several)
        riders_per_decode_step=(
            sum(s["riders"] for s in decoded)
            / max(1, block * len(decoded))),
        ctx_per_rider=(
            sum(s["ctx_sum"] for s in decoded)
            / max(1, sum(s["riders"] for s in decoded))),
        prefills_in_window=sum(
            s["prefills"] for t, s in zip(facts["steps"], steps)
            if t[0] <= facts["window_s"]),
        # the program's own counters (the last step's gauges)
        state_bytes=float(values.get("serve/state/bytes") or 0.0),
        ssm_slots_written=float(values.get("serve/ssm/slots_written") or 0.0),
        ssm_state_bytes_per_iter=float(
            values.get("serve/ssm/state_bytes_per_iter") or 0.0),
        held_weight_bytes=flops_h1.held_weight_bytes(cfg),
    )

    # free the program's state before the reference runs on the chip
    sample = [(list(lv.req.prompt), list(lv.req.tokens))
              for lv in base.sample_served(ctx, res["ended"],
                                           mix["check_requests"])]
    weights = to_reference(prog["params"], cfg)
    prog.clear()
    gc.collect()
    t_ref = time.monotonic()
    gap, scale, n_tok = served_token_gaps(cfg, weights, sample) \
        if sample else (float("inf"), 0.0, 0)
    checks = {
        "served_token_gap": {"value": gap,
                             "limit": mix["limits"]["served_token_gap"]},
        "fault_counters": {"value": faults, "limit": 0},
        "pages_leaked": {"value": leaked, "limit": 0},
        "slots_leaked": {"value": slots_leaked, "limit": 0},
    }
    facts.update(checked_tokens=n_tok, ref_logit_scale=scale,
                 reference_s=time.monotonic() - t_ref)
    return {
        "setup_s": setup_s, "window_s": res["window_s"], "end_to_end": e2e,
        "attempted": attempted, "failed": failed, "checks": checks,
        "memory_peak_bytes": memory_peak,
        "trace_dir": res["trace_dir"],
        "trace_window_s": res["trace_window_s"], "facts": facts,
        "info": {k: facts[k] for k in (
            "window_s", "offered", "requests_in_window",
            "completed_tokens_per_s", "in_flight_at_close",
            "drain_s", "tails_ms", "itl_gaps", "generator_late_p95_ms",
            "queue_wait_p95_ms", "checked_tokens", "ref_logit_scale",
            "reference_s", "riders_per_decode_step", "ctx_per_rider",
            "prefills_in_window", "state_bytes", "ssm_slots_written",
            "ssm_state_bytes_per_iter", "held_weight_bytes")},
    }
