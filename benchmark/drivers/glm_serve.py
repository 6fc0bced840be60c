"""Driver for the cells that serve GLM-4.7-Flash's decoder stack (latent
attention with a low-rank query path in every layer; a dense first layer,
then 64 routed experts top-4 plus a shared expert):
``InferenceEngine(HybridConfig, params, ServeConfig(...)).build()`` under
``ContinuousBatchingScheduler`` with ``prefill_chunk_tokens``, so that every
prompt is prefilled in page-multiple chunks over the latent pages.

The loop, the window, the stamps and the procedure of `correct` are
``gpt_serve``'s, by import, as in ``ling_serve``: ``gpt_serve.drive`` offers
the planned requests and stamps the tokens, ``gpt_serve.summarize`` reduces
them, ``gpt_serve.sample_served`` draws the finished requests that are
checked (the longest always in) and ``gpt_serve.counts`` reads the fault
counters and leaked pages.  This file differs in what it builds and what it
knows about it:

- **the model**: :func:`program_config` reads the configuration's
  published keys into a ``HybridConfig`` whose pattern is ``("mla",
  "dense")`` then ``("mla", "moe")``; :func:`seeded_weights` draws the
  weights from ``--seed`` leaf by leaf, in each leaf's own dtype, on the
  device, by the laws of the file's ``assumed.weights``;
  :func:`balance_expert_bias` sets the correction bias as ``ling_serve``'s
  rule does (the file's ``assumed.expert_bias``).
- **the rate**: a prompt's tokens count in the step whose chunk ran them
  (``gpt_serve.drive`` counts a prompt whole at its first token, which a
  24k-token prompt of twelve chunks would turn into one spike): the step
  record of :func:`_watch` holds each step's chunk tokens and emitted
  tokens, and ``serve.tokens_per_s`` is their sum over the window's steps.
- **the reference**: ``benchmark/reference/glm.py`` through
  :func:`reference_hidden` (one jitted call a LAYER: the f32 upcast of the
  bf16 weights never exceeds a layer, 2.5 GB) and the head over the served
  positions only (:func:`readings`: the f32 logits of every position
  would be 15.5 GB).
- **the facts**: operations and bytes from ``benchmark/flops_glm.py``; a
  record, one entry a ``sched.step()``, of what the engine's calls of that
  step did (chunks with their lengths and offsets, riders and live context)
  for the per-layer readers in ``benchmark/glm_readers.py``; leaked decode
  SLOTS beside leaked pages.

Planted faults (``--plant``; the rehearsal through ``run.py``, the cell's
own size through ``benchmark/calibrate_glm.py``): ``altered_token`` (a
served token altered where it is produced), ``chunk_bf16_softmax`` (the
chunk attention's scores, softmax and sums held in bf16),
``chunk_ignores_history`` (a chunk attends its own rows only),
``dropped_q_a_norm`` (the query path's RMSNorm left out).
"""

from __future__ import annotations

import contextlib
import copy
import gc
import time

import numpy as np

from benchmark import flops_glm
from benchmark.drivers import gpt_serve as base
from benchmark.reference import glm as ref_glm

PLANTS = ("altered_token", "chunk_bf16_softmax", "chunk_ignores_history",
          "dropped_q_a_norm")


# ---------------------------------------------------------------------------
# program objects from the configuration's published keys
# ---------------------------------------------------------------------------

def program_config(cfg):
    import jax.numpy as jnp
    from apex_tpu.models.hybrid import HybridConfig

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    e = cfg["n_routed_experts"]
    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        # no KDA or grouped-query layer reads it
        head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        pattern=tuple(ref_glm.layer_kinds(cfg)),
        first_dense_layers=cfg["first_k_dense_replace"],
        num_experts=e, held_experts=(0, e),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_intermediate_size=(
            cfg["n_shared_experts"] * cfg["moe_intermediate_size"]),
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        q_lora_rank=cfg["q_lora_rank"], mla_output_gate=False,
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"],
        dtype=dt[cfg["compute_dtype"]], param_dtype=dt[cfg["param_dtype"]],
    )


def leaf_law(names, hidden):
    """A leaf's law by its name (the file's ``assumed.weights``), as
    ``models.hybrid.draw_leaf`` takes it: the two latent RMSNorms' scales
    U(2, 4), the other norms' ones; the correction bias zeros (then
    balanced); the router N(0, 1/sqrt(hidden)); every other matrix N(0,
    0.02)."""
    name = names[-2] if names[-1] == "weight" else names[-1]
    if names[-1] == "scale":
        if name in ("q_a_norm", "kv_norm"):
            return "uniform", 2.0, 4.0
        return "ones", 0.0, 0.0
    if name == "expert_bias":
        return "zeros", 0.0, 0.0
    return "normal", 0.0, hidden ** -0.5 if name == "router" else 0.02


def seeded_weights(shapes, seed: int, hidden: int):
    """Every leaf of ``shapes`` from ``seed`` (any integer up to 64 bits),
    one jitted call a leaf, in the leaf's own dtype."""
    import jax

    from apex_tpu.models.hybrid import draw_leaf, path_names

    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(leaves):
        law, a, b = leaf_law(path_names(path), hidden)
        out.append(draw_leaf(law, tuple(s.shape), s.dtype,
                             jax.random.fold_in(key, i), a, b))
    return jax.tree_util.tree_unflatten(treedef, out)


def to_reference(tree, cfg):
    """Program tree -> the reference's layout (slices, no arithmetic)."""
    t = tree["params"]
    layers = []
    for lp, (_, ffn) in zip(t["layers"], ref_glm.layer_kinds(cfg)):
        m = lp["mla"]
        o = {"norm1": lp["norm_mixer"]["scale"],
             "norm2": lp["norm_ffn"]["scale"],
             "wqa": m["q_a"]["weight"], "qnorm": m["q_a_norm"]["scale"],
             "wqb": m["q_b"]["weight"], "wa": m["kv_a"]["weight"],
             "anorm": m["kv_norm"]["scale"], "wb": m["kv_b"]["weight"],
             "wo": m["out"]["weight"]}
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


def balance_expert_bias(cfg, params, seed: int, *, tokens: int = 1024,
                        rounds: int = 400, rate: float = 0.5):
    """The routed layers' correction bias, set so that the router's load is
    even (the file's ``assumed.expert_bias``): ``ling_serve.
    balance_expert_bias``'s rule on this model's reference.  ``noaux_tc``
    is the published mechanism: the bias is added to the scores for
    SELECTION only and tuned in training until every expert is chosen
    equally often; with weights from a seed and a zero bias the load is
    skewed, and differently from seed to seed.  Over ``tokens`` random ids
    the layers are walked with the plain reference's own functions (f32),
    and at each routed layer ``b_e -= rate * (load_e - mean load)`` runs for
    ``rounds`` rounds on that layer's scores before going on with the
    balanced layer's output.  Returns ``params`` with the biases set."""
    import jax
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    weights = to_reference(params, cfg)
    target = cfg["num_experts_per_tok"] / cfg["n_routed_experts"]

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    def walk(ffn):
        @jax.jit
        def f(lp, x):
            lp = f32(lp)
            x = x + ref_glm.mla(lp, ref_glm.rms_norm(x, lp["norm1"], eps),
                                cfg, "f32")
            y = ref_glm.rms_norm(x, lp["norm2"], eps)
            if ffn == "dense":
                return x + ref_glm.swiglu(
                    y, lp["w_gate"], lp["w_up"], lp["w_down"], "f32"), None

            def round_(_, b):
                load = jnp.mean(
                    ref_glm.route(dict(lp, bias=b), y, cfg) > 0, axis=0)
                return b - rate * (load - target)

            bias = jax.lax.fori_loop(0, rounds, round_, lp["bias"])
            lp = dict(lp, bias=bias)
            return x + ref_glm.moe(lp, y, cfg, "f32"), bias
        return f

    kinds = ref_glm.layer_kinds(cfg)
    steps = {kind: walk(kind[1]) for kind in set(kinds)}
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


# ---------------------------------------------------------------------------
# gpt_serve's loop, told this model's counts
# ---------------------------------------------------------------------------

class _Counts:
    """This model's operation counts under the names ``gpt_serve`` calls."""

    def gpt_prefill_flops(self, cfg, n):
        return flops_glm.prefill_flops(cfg, n)

    def gpt_token_flops(self, cfg, ctx, logits):
        return flops_glm.token_flops(cfg, ctx, logits)

    def gpt_kv_bytes_per_token(self, cfg, _bytes):
        return flops_glm.latent_bytes_per_token(cfg)

    def gpt_weight_bytes(self, cfg, _bytes):
        return flops_glm.weight_bytes(cfg)


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
    real, base.flops = base.flops, _Counts()
    try:
        yield seen
    finally:
        base.flops = real


def _watch(prog, cfg):
    """Record, one entry a ``sched.step()``, what the engine's calls of that
    step did: each chunk's ``(tokens, offset)``, the tokens the step
    emitted (a final chunk's first token, a decode call's read), the
    decode call's riders and live context, and the model operations of all
    of it (``flops_glm``).  Keep each request's logits at its last prompt
    position as its final chunk returned them, on the device
    (``prog["prompt_logits"]``, by request), for `correct`."""
    engine, sched, log = prog["engine"], prog["sched"], prog["step_log"]
    kept, last = prog["prompt_logits"], {}
    now = {}

    def fresh():
        now.clear()
        now.update(chunks=[], decodes=0, riders=0, ctx_sum=0, emitted=0,
                   flops=0.0)

    real_decode, real_chunk, real_step = (
        engine.decode, engine.chunk_prefill, sched.step)

    def decode(tokens, lengths, *a, **k):
        out = real_decode(tokens, lengths, *a, **k)
        lengths = np.asarray(lengths, np.int64)
        live = lengths[lengths > 0]
        now.update(decodes=now["decodes"] + 1,
                   riders=now["riders"] + len(live),
                   ctx_sum=now["ctx_sum"] + int(live.sum()),
                   flops=now["flops"] + sum(
                       flops_glm.token_flops(cfg, int(n), True)
                       for n in live))
        return out

    def chunk_prefill(chunk_ids, offset, table, pages, **k):
        out = real_chunk(chunk_ids, offset, table, pages, **k)
        n = len(chunk_ids)
        now["chunks"].append((n, int(offset)))
        now["flops"] += flops_glm.chunk_flops(cfg, n, int(offset))
        # a sequence's first page names it while it holds its slot
        last[int(table[0])] = out[0]
        return out

    def step():
        fresh()
        # the scheduler's own ledger of emitted tokens: a final chunk's
        # first token, a decode call's tokens as the step reads them
        out = sched._tokens_out
        real_step()
        now["emitted"] = sched._tokens_out - out
        for r in sched.slots:
            if r is not None and r.tokens and id(r) not in kept \
                    and r.pages and r.pages[0] in last:
                kept[id(r)] = last.pop(r.pages[0])
        log.append(dict(now))

    fresh()
    engine.decode, engine.chunk_prefill, sched.step = (
        decode, chunk_prefill, step)


def _plant(ctx):
    """Break the timed path underneath, before anything is compiled (the
    faults that live inside the programs are module functions the bodies
    look up when they are traced)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.serve import model as serve_model

    if ctx.planted in (None, "altered_token"):
        return
    if ctx.planted == "dropped_q_a_norm":
        def query(cfg, p, y, dtype):
            c_q = serve_model._matmul(y, p["q_a"]["weight"], dtype)
            return serve_model._matmul(c_q, p["q_b"]["weight"], dtype)

        serve_model._mla_query = query
    elif ctx.planted == "chunk_ignores_history":
        real = serve_model.mla_chunk_attention

        def attend(q, pool, page_table, offset, **k):
            # the table from the chunk's first page on, at offset 0
            page = pool.shape[3]
            return real(q, pool, jnp.roll(page_table, -(offset // page)),
                        jnp.zeros_like(offset), **k)

        serve_model.mla_chunk_attention = attend
    elif ctx.planted == "chunk_bf16_softmax":
        def attend(q, pool, page_table, offset, *, layer, scale):
            # online softmax over blocks of rows, every sum in bf16
            c, heads, w = q.shape
            page, bf = pool.shape[3], jnp.bfloat16
            rows = pool[layer, page_table][:, 0].reshape(-1, w)
            per = page * min(32, page_table.shape[0])
            blocks = rows.reshape(-1, per, w)
            qb = q.astype(bf)
            qpos = offset + jnp.arange(c)

            def step(carry, xs):
                m, l, acc = carry
                kv, j = xs
                s = jnp.einsum("chw,tw->cht", qb, kv,
                               preferred_element_type=bf) * bf(scale)
                pos = j * per + jnp.arange(per)
                s = jnp.where(pos[None, None, :] <= qpos[:, None, None], s,
                              bf(-1e9))
                m_new = jnp.maximum(m, jnp.max(s, -1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l = l * alpha + jnp.sum(p, -1, dtype=bf)
                acc = acc * alpha[..., None] + jnp.einsum(
                    "cht,tw->chw", p, kv, preferred_element_type=bf)
                return (m_new, l, acc), None

            init = (jnp.full((c, heads), -1e9, bf), jnp.zeros((c, heads), bf),
                    jnp.zeros((c, heads, w), bf))
            (_, l, acc), _ = jax.lax.scan(
                step, init, (blocks, jnp.arange(blocks.shape[0])))
            return (acc / l[..., None]).astype(jnp.float32)

        serve_model.mla_chunk_attention = attend
    else:
        raise SystemExit(f"unknown fault {ctx.planted!r}: {PLANTS}")


def build(ctx):
    """Weights from the seed, the engine with the cell's own programs, a
    chunking scheduler, and a warm-up through every chunk bucket the
    traffic can hit."""
    from apex_tpu.models.hybrid import param_shapes
    from apex_tpu.observability import MetricRegistry
    from apex_tpu.serve import (
        ContinuousBatchingScheduler, InferenceEngine, Request, ServeConfig,
    )

    cfg = ctx.config
    _plant(ctx)
    pcfg = program_config(cfg)
    params = seeded_weights(param_shapes(pcfg), ctx.seed, cfg["hidden_size"])
    params = balance_expert_bias(cfg, params, ctx.seed)
    sv = cfg["serve"]
    serve = ServeConfig(
        page_size=sv["page_size"], num_pages=sv["num_pages"],
        max_batch=sv["max_batch"], max_pages_per_seq=sv["max_pages_per_seq"],
        prefill_buckets=tuple(sv["prefill_buckets"]),
        decode_block=sv["decode_block"],
    )
    registry = MetricRegistry(fetch_every=1)
    engine = InferenceEngine(pcfg, params, serve, registry=registry)
    # the decode program now; every prompt runs as chunks, whose programs
    # the warm-up below compiles (the single-bucket prefill is not used)
    engine.build(buckets=())
    chunk = sv["prefill_chunk_tokens"]
    # the pool's accounting is proven once, after the drain (`run`)
    sched = ContinuousBatchingScheduler(
        engine, registry=registry, leak_checks=False,
        prefill_chunk_tokens=chunk)
    buckets = [b for b in engine.serve.buckets() if b <= chunk]
    prog = {
        "params": params, "engine": engine, "sched": sched,
        "registry": registry, "buckets": buckets, "step_log": [],
        "prompt_logits": {},
    }
    if ctx.planted == "altered_token":
        real = engine.decode

        def decode(*a, **k):
            logits, toks = real(*a, **k)
            return logits, (np.array(toks) + 1) % cfg["vocab_size"]

        engine.decode = decode
    # a prompt of two chunks whose tail fills each bucket: every chunk
    # program the traffic reaches, and a chunk at a nonzero offset
    rng = np.random.default_rng(ctx.seed)
    warm = [
        sched.submit(Request(
            prompt=[int(t) for t in rng.integers(
                0, cfg["vocab_size"], size=chunk + b - 3)],
            max_new_tokens=3,
        ))
        for b in buckets
    ]
    sched.run()
    if any(r.status != "done" for r in warm) or engine.pool.in_use \
            or sched.slots_in_use():
        raise SystemExit("warm-up did not complete cleanly")
    prog["compiled"] = dict(engine.compile_counts)
    _watch(prog, cfg)
    return prog


# ---------------------------------------------------------------------------
# `correct`: served tokens against the reference
# ---------------------------------------------------------------------------

_REFERENCE = {}


def _reference_programs(cfg, prec):
    """The reference's jitted pieces for one configuration and precision,
    made once a process: the embedding lookup, one block a layer kind, the
    head over a run of positions."""
    import json

    import jax
    import jax.numpy as jnp

    key = (json.dumps({k: v for k, v in cfg.items()
                       if not isinstance(v, (dict, list))}, sort_keys=True),
           prec)
    if key in _REFERENCE:
        return _REFERENCE[key]

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    def block(kind):
        return jax.jit(lambda lp, x: ref_glm.block(f32(lp), x, kind, cfg,
                                                   prec))

    programs = {
        "embed": jax.jit(lambda table, ids: table[ids].astype(jnp.float32)),
        "blocks": {k: block(k) for k in set(ref_glm.layer_kinds(cfg))},
        "head": jax.jit(lambda norm, w, x: ref_glm.einsum(
            "sh,hv->sv", ref_glm.rms_norm(x, f32(norm), cfg["rms_norm_eps"]),
            f32(w), prec)),
        "kinds": ref_glm.layer_kinds(cfg),
    }
    _REFERENCE[key] = programs
    return programs


def reference_hidden(cfg, weights, ids, prec="f32"):
    """``benchmark/reference/glm.py``'s forward up to the last block, one
    jitted call a layer: each call upcasts its own layer's weights and
    nothing else."""
    ref = _reference_programs(cfg, prec)
    x = ref["embed"](weights["embed"], ids)
    for lp, kind in zip(weights["layers"], ref["kinds"]):
        x = ref["blocks"][kind](lp, x)
    return x


def reference_logits(cfg, weights, ids, prec="f32"):
    """All the logits at once (the tests' and the rehearsal's sizes)."""
    ref = _reference_programs(cfg, prec)
    return ref["head"](weights["norm_f"], weights["head"],
                       reference_hidden(cfg, weights, ids, prec))


def readings(cfg, weights, seqs, *, control=None):
    """What `correct` compares, against this reference.  ``seqs`` holds
    ``(prompt, served tokens, the program's logits at the last prompt
    position)``.  For each, one forward over the whole sequence, padded to
    the longest a slot can hold, and the head over the served positions
    only (the last prompt position and the decode steps), padded to the
    longest output.  Returns ``(numbers, per request)``; the numbers:

    - ``prompt_end_logit_gap``: the MEDIAN over the requests of the widest
      difference in any logit at the prompt's end, where the final chunk
      sampled the first token — the chunked prefill against the whole
      forward.  The median, because a bf16 residual stream moves the
      router across a near-tie now and then (a whole expert's term at that
      row: PERF.md section 2); a fault moves every request;
    - ``served_off_best_share``: the largest share, over the requests, of
      served tokens that are not the reference's best at their position —
      the decode steps (and the first token);
    - ``served_token_gap`` (``gpt_serve``'s statistic: the widest gap by
      which a served token's logit lies below the reference's best),
      ``ref_logit_scale``, ``checked_tokens``: for the look.

    ``control``: a lower precision, whose own forward takes the program's
    place: its logits at the prompt's end, its best token at each position.
    """
    import jax
    import jax.numpy as jnp

    sv = cfg["serve"]
    pad_to = sv["page_size"] * sv["max_pages_per_seq"]
    rows = max(len(t) for _, t, _ in seqs) if seqs else 1

    @jax.jit
    def gaps_of(ref, tok, n, at_end):
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
        live = jnp.arange(ref.shape[0]) < n
        return (jnp.where(live, best - got, 0.0),
                jnp.max(jnp.where(live, jnp.abs(best), 0.0)),
                jnp.max(jnp.abs(at_end - ref[0])))

    def logits_at(ids, first, prec):
        ref = _reference_programs(cfg, prec)
        x = reference_hidden(cfg, weights, ids, prec)
        x = jax.lax.dynamic_slice_in_dim(x, first, rows, 0)
        return ref["head"](weights["norm_f"], weights["head"], x)

    detail = []
    with jax.default_matmul_precision("highest"):
        for prompt, toks, at_end in seqs:
            seq = list(prompt) + list(toks)
            ids = np.zeros((pad_to,), np.int32)
            ids[: len(seq)] = seq
            # position len(prompt) - 1 + i predicts served token i
            first = len(prompt) - 1
            ids = jnp.asarray(ids)
            ref = logits_at(ids, first, "f32")
            if control:
                other = logits_at(ids, first, control)
                tok, at_end = jnp.argmax(other, axis=-1), other[0]
            else:
                t = np.zeros((rows,), np.int32)
                t[: len(toks)] = toks
                tok = jnp.asarray(t)
            g, scale, e = gaps_of(ref, tok, len(toks), jnp.asarray(at_end))
            g = np.asarray(g)[: len(toks)]
            detail.append({
                "prompt": len(prompt), "tokens": len(toks),
                "end_gap": float(e), "off_best": int((g > 0).sum()),
                "token_gap_max": float(g.max()), "scale": float(scale)})
    numbers = {
        "prompt_end_logit_gap": float(np.median(
            [d["end_gap"] for d in detail])),
        "served_off_best_share": max(
            d["off_best"] / d["tokens"] for d in detail),
        "served_token_gap": max(d["token_gap_max"] for d in detail),
        "ref_logit_scale": max(d["scale"] for d in detail),
        "checked_tokens": sum(d["tokens"] for d in detail),
    }
    return numbers, detail


def checked(ctx, prog, res):
    """The requests `correct` checks (``gpt_serve.sample_served``), each as
    ``(prompt, served tokens, logits at its last prompt position)``, the
    logits on the host."""
    kept = prog["prompt_logits"]
    return [(list(lv.req.prompt), list(lv.req.tokens),
             np.asarray(kept[id(lv.req)]))
            for lv in base.sample_served(ctx, res["ended"],
                                         ctx.traffic["check_requests"])]


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
    cfg = ctx.config
    steps = prog["step_log"]
    window = facts["window_s"]
    in_window = [s for t, s in zip(facts["steps"], steps) if t[0] <= window]
    decoded = [s for s in in_window if s["decodes"]]
    # the rate as the chunks run: a prompt's tokens in the steps that
    # prefilled them, an emitted token in the step that produced it
    e2e["serve.tokens_per_s"] = sum(
        sum(n for n, _ in s["chunks"]) + s["emitted"]
        for s in in_window) / window
    values = prog["registry"].values()
    facts.update(
        glm_steps=steps, config=cfg,
        model_flops_in_window=sum(s["flops"] for s in in_window),
        riders_per_decode_step=(
            sum(s["riders"] for s in decoded) / max(1, len(decoded))),
        ctx_per_rider=(
            sum(s["ctx_sum"] for s in decoded)
            / max(1, sum(s["riders"] for s in decoded))),
        chunks_in_window=sum(len(s["chunks"]) for s in in_window),
        # the program's own counters
        prefill_chunks=float(values.get("serve/prefill_chunks") or 0.0),
        latent_rows_read=float(values.get("serve/latent_rows_read") or 0.0),
        held_weight_bytes=flops_glm.weight_bytes(cfg),
    )

    # free the program's state before the reference runs on the chip
    sample = checked(ctx, prog, res)
    weights = to_reference(prog["params"], cfg)
    prog.clear()
    gc.collect()
    t_ref = time.monotonic()
    numbers, detail = readings(cfg, weights, sample) if sample else (
        dict.fromkeys(("prompt_end_logit_gap", "served_off_best_share"),
                      float("inf")), [])
    limits = mix["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in ("prompt_end_logit_gap", "served_off_best_share")}
    checks.update({
        "fault_counters": {"value": faults, "limit": 0},
        "pages_leaked": {"value": leaked, "limit": 0},
        "slots_leaked": {"value": slots_leaked, "limit": 0},
    })
    facts.update(checked=detail, served_token_gap=numbers.get(
                     "served_token_gap"),
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
            "queue_wait_p95_ms", "checked", "served_token_gap",
            "reference_s", "riders_per_decode_step",
            "ctx_per_rider", "chunks_in_window", "prefill_chunks",
            "latent_rows_read", "held_weight_bytes")},
    }
