"""Driver for the cells that serve Ling-3.0-flash's language stack as one
chip's share of an expert-parallel group: ``InferenceEngine(HybridConfig,
params, ServeConfig(...)).build()`` under ``ContinuousBatchingScheduler``.

The loop, the window, the stamps and the procedure of `correct` are
``gpt_serve``'s, by import: ``gpt_serve.drive`` offers the planned requests
and stamps the tokens, ``gpt_serve.summarize`` reduces them,
``gpt_serve.sample_served`` draws the finished requests that are checked
(the longest always in) and ``gpt_serve.counts`` reads the fault counters
and leaked pages.  This file differs only in what it builds and what it
knows about it:

- **the model**: :func:`program_config` reads the configuration's
  published keys into a ``HybridConfig`` (held experts and vocabulary slice
  as the file states them); :func:`seeded_weights` draws the weights from
  ``--seed`` leaf by leaf, in each leaf's own dtype, on the device (the laws
  are the file's ``assumed.weights``); no f32 copy of the tree ever exists.
- **the reference**: ``benchmark/reference/ling.py`` through
  :func:`reference_logits`, one jitted call a LAYER, so that the f32 upcast
  the reference makes of the bf16 weights never exceeds a layer (1.6 GB:
  64 experts).  :func:`served_token_gaps` is ``gpt_serve``'s comparison
  (one reference forward a sampled request over prompt + served tokens; the
  widest gap by which a served token's logit lies below the reference's
  best; with ``control`` the token a lower precision's forward puts first).
- **the facts**: operations and bytes from ``benchmark/flops_ling.py``; a
  record, one entry a ``sched.step()``, of what the engine's calls of that
  step did (riders, live context, the MoE counts the program returned) for
  the per-layer readers in ``benchmark/ling_readers.py``; leaked decode
  SLOTS beside leaked pages.

``gpt_serve``'s loop names GPT-2's operation counts (``flops.gpt_*``) and
keys (``n_layer`` ...): :func:`_as_gpt_serve` hands it this model's counts
under those names for the length of a call, and this configuration's keys
under GPT-2's.

Planted faults (``--plant``, rehearsal only): ``altered_token`` (a served
token altered where it is produced), ``dropped_shared_expert`` (the shared
expert's output left out of every routed layer), ``state_not_reset`` (a
prefill leaves the slot's old recurrent state under the new sequence's).
"""

from __future__ import annotations

import contextlib
import copy
import gc
import time

import numpy as np

from benchmark import flops_ling
from benchmark.drivers import gpt_serve as base
from benchmark.reference import ling as ref_ling

PLANTS = ("altered_token", "dropped_shared_expert", "state_not_reset")


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
        num_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        layer_group_size=cfg["layer_group_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["num_experts_published"],
        held_experts=(cfg["held_experts_first"], cfg["num_experts"]),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_intermediate_size=cfg["moe_shared_expert_intermediate_size"],
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        conv_kernel=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        rms_eps=cfg["rms_norm_eps"],
        dtype=dt[cfg["compute_dtype"]], param_dtype=dt[cfg["param_dtype"]],
    )


def reference_config(cfg):
    """The keys the reference reads: the router scores every PUBLISHED
    expert."""
    return dict(cfg, num_experts=cfg["num_experts_published"])


def held_experts(cfg):
    first = cfg["held_experts_first"]
    return list(range(first, first + cfg["num_experts"]))


def _law(names, hidden):
    """A leaf's law by its name (the file's ``assumed.weights``)."""
    name = names[-2] if names[-1] == "weight" else names[-1]
    if names[-1] == "scale":
        return "ones", 0.0, 0.0
    if name == "expert_bias":
        return "zeros", 0.0, 0.0
    if name == "g_bias":
        return "uniform", -6.0, -2.0
    return "normal", 0.0, {"router": hidden ** -0.5, "conv": 0.5}.get(
        name, 0.02)


def seeded_weights(shapes, seed: int, hidden: int):
    """Every leaf of ``shapes`` from ``seed``, one jitted call a leaf (one
    compile a law, shape and dtype), in the leaf's own dtype."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(0, 1, 2))
    def draw(law, shape, dtype, key, a, b):
        if law == "ones":
            return jnp.ones(shape, dtype)
        if law == "zeros":
            return jnp.zeros(shape, dtype)
        if law == "uniform":
            return jax.random.uniform(
                key, shape, jnp.float32, a, b).astype(dtype)
        return (a + b * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)

    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(leaves):
        names = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path)
        law, a, b = _law(names, hidden)
        out.append(draw(law, tuple(s.shape), s.dtype,
                        jax.random.fold_in(key, i), a, b))
    return jax.tree_util.tree_unflatten(treedef, out)


def balance_expert_bias(cfg, params, seed: int, *, tokens: int = 1024,
                        rounds: int = 400, rate: float = 0.5):
    """The routed layers' expert bias, set so that the router's load is
    even (the file's ``assumed.expert_bias``).

    ``moe_router_enable_expert_bias`` is the published mechanism that keeps
    a trained router's load balanced: the bias is added to the scores for
    SELECTION only and is tuned until every expert is chosen equally often.
    With weights drawn at random and a zero bias the load is skewed — the
    layers' outputs share a common direction, and the experts whose router
    column points along it are chosen by most tokens — and the skew differs
    from seed to seed, so a decode step touched 34 of 64 held experts on one
    seed and 37 on another and the cell's speed moved 1.5 % with the seed
    (PERF.md section 6, PR 35).  So the harness does here what training
    does: over ``tokens`` random ids it walks the layers with the plain
    reference's own functions (f32), and at each routed layer runs the
    aux-loss-free rule ``b_e -= rate * (load_e - mean load)`` (loads as
    shares of the tokens) for ``rounds`` rounds on that layer's scores before going on with the
    balanced layer's output.  Returns ``params`` with the biases set."""
    import jax
    import jax.numpy as jnp

    rcfg = reference_config(cfg)
    held = tuple(held_experts(cfg))
    eps = rcfg["rms_norm_eps"]
    weights = to_reference(params, cfg)
    target = rcfg["num_experts_per_tok"] / rcfg["num_experts"]

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    def walk(kind):
        mixer, ffn = kind

        @jax.jit
        def f(lp, x):
            lp = f32(lp)
            y = ref_ling.rms_norm(x, lp["norm1"], eps)
            x = x + (ref_ling.kda if mixer == "kda" else ref_ling.mla)(
                lp, y, rcfg, "f32")
            y = ref_ling.rms_norm(x, lp["norm2"], eps)
            if ffn == "dense":
                return x + ref_ling.swiglu(
                    y, lp["w_gate"], lp["w_up"], lp["w_down"], "f32"), None

            def round_(_, b):
                load = jnp.mean(
                    ref_ling.route(dict(lp, bias=b), y, rcfg) > 0, axis=0)
                return b - rate * (load - target)

            bias = jax.lax.fori_loop(0, rounds, round_, lp["bias"])
            lp = dict(lp, bias=bias)
            return x + ref_ling.moe(lp, y, rcfg, held, "f32"), bias
        return f

    kinds = ref_ling.layer_kinds(rcfg)
    steps = {kind: walk(kind) for kind in set(kinds)}
    ids = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), 0xB1A5),
        (tokens,), 0, cfg["vocab_size"])
    x = weights["embed"][ids].astype(jnp.float32)
    layers = list(params["params"]["layers"])
    with jax.default_matmul_precision("highest"):
        for i, (lp, kind) in enumerate(zip(weights["layers"], kinds)):
            x, bias = steps[kind](lp, x)
            if bias is not None:
                layers[i] = dict(layers[i], moe=dict(
                    layers[i]["moe"], expert_bias=bias))
    return {"params": dict(params["params"], layers=layers)}


def to_reference(tree, cfg):
    """Program tree -> the reference's layout (slices, no arithmetic)."""
    t = tree["params"]
    nd = cfg["num_attention_heads"] * cfg["head_dim"]
    layers = []
    for lp, (mixer, ffn) in zip(t["layers"], ref_ling.layer_kinds(cfg)):
        o = {"norm1": lp["norm_mixer"]["scale"],
             "norm2": lp["norm_ffn"]["scale"]}
        if mixer == "kda":
            k, w = lp["kda"], lp["kda"]["qkvg"]["weight"]
            o.update(
                wq=w[:, :nd], wk=w[:, nd:2 * nd], wv=w[:, 2 * nd:3 * nd],
                wg=w[:, 3 * nd:], bg=k["g_bias"],
                conv_q=k["conv"][:, :nd], conv_k=k["conv"][:, nd:2 * nd],
                conv_v=k["conv"][:, 2 * nd:], wbeta=k["beta"]["weight"],
                wgate=k["ogate"]["weight"], onorm=k["o_norm"]["scale"],
                wo=k["out"]["weight"])
        else:
            m = lp["mla"]
            o.update(
                wq=m["q"]["weight"], wa=m["kv_a"]["weight"],
                anorm=m["kv_norm"]["scale"], wb=m["kv_b"]["weight"],
                wgate=m["ogate"]["weight"], wo=m["out"]["weight"])
        if ffn == "dense":
            f = lp["mlp"]
            o.update(w_gate=f["gate"]["weight"], w_up=f["up"]["weight"],
                     w_down=f["down"]["weight"])
        else:
            f = lp["moe"]
            o.update(
                router=f["router"]["weight"], bias=f["expert_bias"],
                e_gate=f["experts"]["gate"], e_up=f["experts"]["up"],
                e_down=f["experts"]["down"],
                s_gate=f["shared"]["gate"]["weight"],
                s_up=f["shared"]["up"]["weight"],
                s_down=f["shared"]["down"]["weight"])
        layers.append(o)
    return {"embed": t["word_embeddings"]["weight"],
            "head": t["lm_head"]["weight"], "norm_f": t["norm_f"]["scale"],
            "layers": layers}


# ---------------------------------------------------------------------------
# gpt_serve's loop, told this model's counts
# ---------------------------------------------------------------------------

class _Counts:
    """This model's operation counts under the names ``gpt_serve`` calls
    (the routed share at its expected value: ``flops_ling``)."""

    def __init__(self, cfg):
        self._pairs = flops_ling.expected_pairs_per_layer(cfg)
        # a token's count is linear in its context: the loop asks for it
        # once a token, 128 times a step
        self._flat = flops_ling.token_flops(cfg, 0, False, self._pairs)
        self._per_key = flops_ling.token_flops(
            cfg, 1, False, self._pairs) - self._flat
        self._logits = flops_ling.token_flops(
            cfg, 0, True, self._pairs) - self._flat

    def gpt_prefill_flops(self, cfg, n):
        return flops_ling.prefill_flops(cfg, n, self._pairs)

    def gpt_token_flops(self, cfg, ctx, logits):
        return self._flat + self._per_key * ctx + (
            self._logits if logits else 0.0)

    def gpt_kv_bytes_per_token(self, cfg, _bytes):
        return flops_ling.latent_bytes_per_token(cfg)

    def gpt_weight_bytes(self, cfg, _bytes):
        return flops_ling.dense_weight_bytes(cfg)


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
    several iterations, each slot up to its own budget) and the live
    context summed over them, and the MoE counts each program returned
    inside its token readback."""
    engine, sched, log = prog["engine"], prog["sched"], prog["step_log"]
    now = {}

    def fresh():
        now.clear()
        now.update(decodes=0, riders=0, ctx_sum=0, pairs_decode=0,
                   touched_decode=0, prefills=0, pairs_prefill=0,
                   touched_prefill=0)

    real_decode, real_prefill, real_resolve, real_step = (
        engine.decode, engine.prefill, engine.resolve_prefill, sched.step)

    def decode(tokens, lengths, *a, **k):
        out = real_decode(tokens, lengths, *a, **k)
        lengths = np.asarray(lengths, np.int64)
        its = np.asarray(k["steps"], np.int64) if k.get("steps") is not None \
            else (lengths > 0).astype(np.int64)
        pairs, touched = engine.last_moe_counts
        # iteration j of a slot reads its context at lengths + j
        now.update(decodes=now["decodes"] + 1,
                   riders=now["riders"] + int(its.sum()),
                   ctx_sum=now["ctx_sum"] + int(
                       (its * lengths + its * (its - 1) // 2).sum()),
                   pairs_decode=now["pairs_decode"] + int(pairs),
                   touched_decode=now["touched_decode"] + int(touched))
        return out

    def prefill_counts():
        pairs, touched = engine.last_moe_counts
        now.update(pairs_prefill=now["pairs_prefill"] + int(pairs),
                   touched_prefill=now["touched_prefill"] + int(touched))

    def prefill(*a, **k):
        out = real_prefill(*a, **k)
        now["prefills"] += 1
        if not k.get("lazy"):
            prefill_counts()
        return out

    def resolve_prefill(pending):
        # a lazy prefill's counts come with its token
        first = real_resolve(pending)
        prefill_counts()
        return first

    def step():
        fresh()
        real_step()
        log.append(dict(now))

    fresh()
    engine.decode, engine.prefill, sched.step = decode, prefill, step
    engine.resolve_prefill = resolve_prefill


def _plant(ctx, prog):
    import jax

    engine, cfg = prog["engine"], ctx.config
    if ctx.planted == "altered_token":
        real = engine.decode

        def decode(*a, **k):
            logits, toks = real(*a, **k)
            return logits, (np.array(toks) + 1) % cfg["vocab_size"]

        engine.decode = decode
    elif ctx.planted == "dropped_shared_expert":
        tree = jax.tree_util.tree_map(lambda x: x, prog["params"])
        for lp in tree["params"]["layers"]:
            if "moe" in lp:
                down = lp["moe"]["shared"]["down"]
                down["weight"] = down["weight"] * 0
        engine.params = tree        # the reference keeps prog["params"]
    elif ctx.planted == "state_not_reset":
        real = engine.prefill

        def prefill(prompt, pages, *, slot, **k):
            old = engine.cache["state"][:, slot]
            out = real(prompt, pages, slot=slot, **k)
            engine.cache = dict(engine.cache, state=engine.cache[
                "state"].at[:, slot].add(old))
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
    params = seeded_weights(param_shapes(pcfg), ctx.seed, cfg["hidden_size"])
    params = balance_expert_bias(cfg, params, ctx.seed)
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
    # every retirement: the proof walks all 16,385 pages (3.3 ms) and a
    # window retires ~370 requests, eight in every decode block
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
    made once a process: an embedding lookup, one block a layer kind, the
    head."""
    import json

    import jax
    import jax.numpy as jnp

    rcfg = reference_config(cfg)
    key = (json.dumps({k: v for k, v in rcfg.items()
                       if not isinstance(v, (dict, list))}, sort_keys=True),
           prec)
    if key in _REFERENCE:
        return _REFERENCE[key]
    held = tuple(held_experts(cfg))

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    def block(kind):
        return jax.jit(lambda lp, x: ref_ling.block(
            f32(lp), x, kind, rcfg, held, prec))

    programs = {
        "embed": jax.jit(lambda table, ids: table[ids].astype(jnp.float32)),
        "blocks": {k: block(k) for k in set(ref_ling.layer_kinds(rcfg))},
        "head": jax.jit(lambda norm, w, x: ref_ling.einsum(
            "sh,hv->sv",
            ref_ling.rms_norm(x, f32(norm), rcfg["rms_norm_eps"]), f32(w),
            prec)),
        "kinds": ref_ling.layer_kinds(rcfg),
    }
    _REFERENCE[key] = programs
    return programs


def reference_logits(cfg, weights, ids, prec="f32"):
    """``benchmark/reference/ling.py``'s forward, one jitted call a layer:
    each call upcasts its own layer's weights and nothing else."""
    ref = _reference_programs(cfg, prec)
    x = ref["embed"](weights["embed"], ids)
    for lp, kind in zip(weights["layers"], ref["kinds"]):
        x = ref["blocks"][kind](lp, x)
    return ref["head"](weights["norm_f"], weights["head"], x)


def served_token_gaps(cfg, weights, seqs, *, control=None):
    """``gpt_serve.served_token_gaps`` for this reference: for each (prompt,
    served tokens) one forward over the whole sequence, padded to the
    longest a slot can hold, and for every served token the gap by which
    its logit lies below the reference's best at that position."""
    import jax
    import jax.numpy as jnp

    sv = cfg["serve"]
    pad_to = sv["page_size"] * sv["max_pages_per_seq"]

    @jax.jit
    def gaps_of(ref, other, ids, first, last):
        best = jnp.max(ref, axis=-1)
        tok = jnp.roll(ids, -1) if other is None else jnp.argmax(other, -1)
        got = jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
        pos = jnp.arange(ids.shape[0])
        live = (pos >= first) & (pos <= last)
        return jnp.max(jnp.where(live, best - got, 0.0)), jnp.max(
            jnp.where(live, jnp.abs(best), 0.0))

    worst, scale, n_tok = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for prompt, toks in seqs:
            seq = list(prompt) + list(toks)
            ids = np.zeros((pad_to,), np.int32)
            ids[: len(seq)] = seq
            ids = jnp.asarray(ids)
            ref = reference_logits(cfg, weights, ids)
            other = reference_logits(cfg, weights, ids, control) \
                if control else None
            g, s = gaps_of(ref, other, ids, len(prompt) - 1, len(seq) - 2)
            worst, scale = max(worst, float(g)), max(scale, float(s))
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
        ling_steps=steps, config=cfg, decode_block=block,
        # per ITERATION of the decode program (a block runs several)
        touched_per_decode_step=(
            sum(s["touched_decode"] for s in decoded)
            / max(1, block * len(decoded))),
        riders_per_decode_step=(
            sum(s["riders"] for s in decoded)
            / max(1, block * len(decoded))),
        pairs_per_token_layer=(
            sum(s["pairs_decode"] for s in decoded)
            / max(1, sum(s["riders"] for s in decoded))
            / max(1, sum(1 for k in flops_ling.layer_kinds(cfg)
                         if "moe" in k))),
        state_bytes=float(values.get("serve/state/bytes") or 0.0),
        held_weight_bytes=flops_ling.held_weight_bytes(cfg),
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
            "reference_s", "touched_per_decode_step",
            "riders_per_decode_step", "pairs_per_token_layer",
            "state_bytes", "held_weight_bytes")},
    }
