"""Driver for cells whose program is the serving engine:
``InferenceEngine(cfg, params, ServeConfig(...)).build()`` under
``ContinuousBatchingScheduler``, driven by this file's loop of ``submit``
and ``sched.step()`` in one thread.

The window, for both kinds of loop (the traffic file says which):

- **open**: every request of the run has a due time inside the window
  (``benchmark/traffic/generate.py``).  A request is submitted at the
  first loop iteration at or after its due time; its time to first token
  runs **from the due time**, so a stall charges the requests that waited
  behind it.  After the window closes nothing more is offered and the
  loop goes on until every request has ended, so that each is judged by
  what it says and its latency counts its whole wait.
- **closed**: ``clients`` requests are in flight; a client offers its next
  request in the loop iteration after its last one ended.  The rate counts
  every token processed by the window's steps (a prompt's tokens at its
  prefill, a generated token at its step) over the window; the requests
  still in flight at the close are then drained.

`Request` keeps no per-token times, so the loop reads its own clock after
every ``sched.step()`` and diffs ``len(r.tokens)``: a request's first
token is stamped with the scheduler's ``first_token_at``, every later one
with the end of the step that produced it.
"""

from __future__ import annotations

import gc
import tempfile
import time

import numpy as np

from benchmark import flops
from benchmark.readers import quantile
from benchmark.weights import seeded_weights
from benchmark.reference import gpt2 as ref_gpt2
from benchmark.traffic import generate

DRAIN_LIMIT_S = 60.0


# ---------------------------------------------------------------------------
# program objects from the configuration's published keys
# ---------------------------------------------------------------------------

def program_config(cfg):
    import jax.numpy as jnp
    from apex_tpu.models.gpt import GptConfig

    return GptConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        intermediate_size=cfg["n_inner"] or 4 * cfg["n_embd"],
        max_seq_len=cfg["n_positions"], rotary=False,
        layer_norm_eps=cfg["layer_norm_epsilon"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg["compute_dtype"]],
    )


def to_reference(tree):
    """Program tree -> the reference's flat layout (no arithmetic)."""
    p = tree["params"]
    b = p["layers"]["block"]
    return {
        "wte": p["word_embeddings"]["weight"], "wpe": p["position_embeddings"],
        "lnf_g": p["ln_f"]["scale"], "lnf_b": p["ln_f"]["bias"],
        "ln1_g": b["ln_attn"]["scale"], "ln1_b": b["ln_attn"]["bias"],
        "qkv_w": b["qkv"]["weight"], "qkv_b": b["qkv"]["bias"],
        "out_w": b["out"]["weight"], "out_b": b["out"]["bias"],
        "ln2_g": b["ln_mlp"]["scale"], "ln2_b": b["ln_mlp"]["bias"],
        "fc1_w": b["fc1"]["weight"], "fc1_b": b["fc1"]["bias"],
        "fc2_w": b["fc2"]["weight"], "fc2_b": b["fc2"]["bias"],
    }


def build(ctx):
    """Weights from the seed, the engine with the cell's own programs, a
    scheduler, and a warm-up through every bucket the traffic can hit."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models.gpt import GptModel
    from apex_tpu.observability import MetricRegistry
    from apex_tpu.serve import (
        ContinuousBatchingScheduler, InferenceEngine, Request, ServeConfig,
    )

    cfg, mix = ctx.config, ctx.traffic
    pcfg = program_config(cfg)
    shapes = jax.eval_shape(
        GptModel(pcfg).init, jax.random.PRNGKey(0), jnp.zeros((8, 1), jnp.int32)
    )
    params = seeded_weights(shapes, ctx.seed, cfg["initializer_range"])
    sv = cfg["serve"]
    serve = ServeConfig(
        page_size=sv["page_size"], num_pages=sv["num_pages"],
        max_batch=sv["max_batch"], max_pages_per_seq=sv["max_pages_per_seq"],
        prefill_buckets=tuple(sv["prefill_buckets"]),
    )
    registry = MetricRegistry(fetch_every=1)
    engine = InferenceEngine(pcfg, params, serve, registry=registry)
    lo, hi = mix["prompt"].get("min", 1), mix["prompt"]["max"]
    buckets = sorted({engine.bucket_for(n) for n in range(lo, hi + 1)})
    engine.build(buckets=tuple(buckets))
    sched = ContinuousBatchingScheduler(engine, registry=registry)
    if ctx.planted == "altered_token":
        # a served token altered where it is produced
        real = engine.decode

        def decode(*a, **k):
            logits, toks = real(*a, **k)
            return logits, (np.array(toks) + 1) % cfg["vocab_size"]

        engine.decode = decode
    rng = np.random.default_rng(ctx.seed)
    warm = [
        sched.submit(Request(
            prompt=[int(t) for t in rng.integers(0, cfg["vocab_size"], size=n)],
            max_new_tokens=3,
        ))
        for b in buckets for n in (b, max(lo, b - 7))
    ]
    sched.run()
    if any(r.status != "done" for r in warm) or engine.pool.in_use:
        raise SystemExit("warm-up did not complete cleanly")
    return {
        "params": params, "engine": engine, "sched": sched,
        "registry": registry, "buckets": buckets,
        "compiled": dict(engine.compile_counts),
    }


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------

class _Live:
    __slots__ = ("req", "plan", "due", "seen", "stamps", "offered_at")

    def __init__(self, req, plan, due, offered_at):
        self.req, self.plan, self.due = req, plan, due
        self.seen, self.stamps, self.offered_at = 0, [], offered_at


def drive(ctx, prog):
    """Offer the planned requests, step the scheduler, stamp tokens.
    Returns everything the metrics and `correct` read."""
    import jax

    cfg, mix = ctx.config, ctx.traffic
    from apex_tpu.serve import Request

    sched = prog["sched"]
    plans = generate.plan(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    closed = mix["loop"] == "closed"
    clients = mix.get("clients", 0)
    trace_s = mix["trace_seconds"] if ctx.trace else 0.0
    trace_from = min(mix.get("trace_start_s", 0.0), max(0.0, ctx.seconds - trace_s))

    live, ended, steps, prompts_traced = [], [], [], []
    nxt = 0
    trace_dir = None
    trace_on = False
    trace_window = None
    clock = time.monotonic
    compiles_open = ctx.compiles()
    t0 = clock()
    t_close = None
    compiles_close = None
    while True:
        now = clock()
        el = now - t0
        if trace_s and trace_window is None and (
                el >= trace_from + trace_s if trace_on else el >= trace_from):
            # starting the tracer, and its writing the file at the stop,
            # pause the window: not the program's time, and later
            # requests are due that much later (a traced run reports
            # per-layer metrics only)
            if trace_on:
                trace_window = el - trace_from
                jax.profiler.stop_trace()
            else:
                trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
                jax.profiler.start_trace(trace_dir)
                trace_from = el
            trace_on = not trace_on
            now = clock()
            t0 += now - (t0 + el)
        if t_close is None and el >= ctx.seconds:
            t_close = now
            compiles_close = ctx.compiles()
        if t_close is None:
            if closed:
                while len(live) < clients:
                    p = plans[nxt % len(plans)]
                    nxt += 1
                    req = sched.submit(Request(
                        prompt=p.prompt, max_new_tokens=p.max_new_tokens))
                    live.append(_Live(req, p, now, now))
            else:
                while nxt < len(plans) and plans[nxt].due_s <= el:
                    p = plans[nxt]
                    nxt += 1
                    req = sched.submit(Request(
                        prompt=p.prompt, max_new_tokens=p.max_new_tokens))
                    live.append(_Live(req, p, t0 + p.due_s, now))
        elif not live or now - t_close > DRAIN_LIMIT_S:
            break
        if not sched.pending:
            # idle: sleep to just short of the next due time (or the close)
            nxt_due = plans[nxt].due_s if not closed and nxt < len(plans) \
                else ctx.seconds
            wait = t0 + min(nxt_due, ctx.seconds) - clock()
            if wait > 0.002:
                time.sleep(wait - 0.001)
            continue
        with jax.profiler.TraceAnnotation("bench/sched_step"):
            sched.step()
        t = clock()
        running = ctx_sum = tokens = 0
        pre = dec = 0.0
        still = []
        for lv in live:
            r = lv.req
            n = len(r.tokens)
            if n > lv.seen:
                if lv.seen == 0:
                    lv.stamps.append(r.first_token_at)
                    pre += flops.gpt_prefill_flops(cfg, len(r.prompt))
                    tokens += len(r.prompt) + 1
                    lv.seen = 1
                    if trace_on:
                        prompts_traced.append(len(r.prompt))
                new = n - lv.seen
                if new:
                    lv.stamps += [t] * new
                    tokens += new
                    # decode tokens: token i was computed over a context
                    # of len(prompt) + i positions, itself included
                    for i in range(lv.seen, n):
                        dec += flops.gpt_token_flops(
                            cfg, len(r.prompt) + i, True)
                    lv.seen = n
            if r.status in ("done", "shed"):
                ended.append(lv)
            else:
                still.append(lv)
                if r.status == "running":
                    running += 1
                    ctx_sum += len(r.prompt) + n
        live = still
        steps.append((t - t0, running, ctx_sum, pre, dec, trace_on, tokens))
    if trace_on:
        trace_window = clock() - t0 - trace_from
        jax.profiler.stop_trace()
    return {
        "t0": t0, "t_close": t_close, "window_s": t_close - t0,
        "plans": plans, "offered": nxt, "ended": ended, "unfinished": live,
        "steps": steps, "prompts_traced": prompts_traced,
        "trace_dir": trace_dir, "trace_window_s": trace_window,
        "compiles_in_window": compiles_close - compiles_open,
    }


# ---------------------------------------------------------------------------
# `correct`: served tokens against the reference, and the counts
# ---------------------------------------------------------------------------

def sample_served(ctx, ended, n: int):
    """Finished requests drawn from the seed, the longest always in."""
    done = [lv for lv in ended if lv.req.status == "done"]
    if not done:
        return []
    rng = np.random.default_rng(ctx.seed ^ 0x5EED)
    longest = max(done, key=lambda lv: len(lv.req.prompt) + len(lv.req.tokens))
    rest = [lv for lv in done if lv is not longest]
    pick = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in pick]


def served_token_gaps(cfg, weights, seqs, *, prec="f32", control=None):
    """For each (prompt, served tokens): the reference's logits over the
    whole sequence in one forward, and for every served token the gap by
    which its logit lies below the reference's best at that position.

    ``control``: a lower precision; then the gap is read for the token
    that *that* precision's forward puts first at each position, on the
    same prompts and served tokens."""
    import jax
    import jax.numpy as jnp

    pad_to = cfg["n_positions"]

    @jax.jit
    def gaps_of(p, ids, first, last):
        ref = ref_gpt2.logits(p, ids, cfg, prec)
        best = jnp.max(ref, axis=-1)
        if control:
            tok = jnp.argmax(ref_gpt2.logits(p, ids, cfg, control), axis=-1)
        else:
            tok = jnp.roll(ids, -1)       # position i predicts token i+1
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
            # logits at positions len(prompt)-1 .. len(seq)-2 predict the
            # served tokens
            g, s = gaps_of(weights, jnp.asarray(ids),
                           len(prompt) - 1, len(seq) - 2)
            worst, scale = max(worst, float(g)), max(scale, float(s))
            n_tok += len(toks)
    return worst, scale, n_tok


def counts(prog):
    """Faults the program counted, pages it leaked, programs it built past
    its build: all have to read nought."""
    from apex_tpu.serve.scheduler import SHED_REASONS

    engine, sched, registry = prog["engine"], prog["sched"], prog["registry"]
    registry.fetch()
    c = registry.values()
    names = (
        "serve/engine_faults", "serve/engine_rebuilds", "serve/retries",
        "serve/readmitted", "serve/shed", "serve/decode_timeouts",
        "serve/admission_faults", "serve/kv_alloc_faults", "serve/clamped",
    ) + tuple(f"serve/shed_{r}" for r in SHED_REASONS)
    faults = sum(float(c.get(k) or 0) for k in names)
    faults += engine.rebuilds + engine.retraces + len(sched.shed)
    faults += sum(engine.compile_counts.values()) - sum(prog["compiled"].values())
    return faults, engine.pool.in_use


def summarize(ctx, prog, res):
    """End-to-end values and the facts the per-layer readers use, from one
    drive: every tail is over all requests (or all gaps) of the run."""
    cfg = ctx.config
    t0, t_close = res["t0"], res["t_close"]
    ended, unfinished = res["ended"], res["unfinished"]
    everyone = ended + unfinished
    failed = len(unfinished) + sum(
        1 for lv in ended
        if lv.req.status != "done"
        or len(lv.req.tokens) != lv.plan.max_new_tokens
    )
    ok = [lv for lv in ended if lv.req.status == "done"]
    miss = DRAIN_LIMIT_S * 1e3
    ttft = [1e3 * (lv.stamps[0] - lv.due) if lv.stamps else miss
            for lv in everyone]
    itl = [1e3 * (b - a) for lv in everyone
           for a, b in zip(lv.stamps, lv.stamps[1:])]
    in_window = [lv for lv in ok if lv.req.done_at <= t_close]
    tokens_done = sum(len(lv.req.prompt) + len(lv.req.tokens)
                      for lv in in_window)
    window = res["window_s"]
    in_win_steps = [s for s in res["steps"] if s[0] <= window]
    # every tail the manifest may name; BENCHMARK.json says which are
    # a cell's end-to-end metrics.  The rate counts every token the
    # window's steps processed (a prompt's tokens at its prefill, a
    # generated token at its step): requests retire in bursts at a step's
    # end, so the tokens of *completed* requests swing by a step's worth
    # (1.3 %) with the step the close falls in (`info` keeps that rate too)
    e2e = {"serve.tokens_per_s": sum(s[6] for s in in_win_steps) / window}
    for q in (50, 80, 90, 95, 99):
        e2e[f"serve.ttft_p{q}_ms"] = quantile(ttft, q / 100)
        e2e[f"serve.itl_p{q}_ms"] = quantile(itl, q / 100) if itl else miss
    late = [1e3 * (lv.offered_at - lv.due) for lv in everyone]
    comps = [c for c in (lv.req.ttft_components() for lv in ok) if c]
    facts = {
        "window_s": window,
        "requests_in_window": len(in_window),
        "completed_tokens_per_s": tokens_done / window,
        "offered": res["offered"],
        "in_flight_at_close": sum(
            1 for lv in everyone
            if lv.req.done_at is None or lv.req.done_at > t_close),
        "drain_s": (res["steps"][-1][0] - window) if res["steps"] else 0.0,
        "ttft_p50_ms": e2e["serve.ttft_p50_ms"],
        "itl_p50_ms": e2e["serve.itl_p50_ms"],
        "tails_ms": {k: v for k, v in e2e.items() if k.endswith("_ms")},
        "itl_gaps": len(itl),
        "generator_late_p95_ms": quantile(late, 0.95),
        "queue_wait_p95_ms": quantile([c["queue_wait_ms"] for c in comps], 0.95),
        "steps": res["steps"],
        "model_flops_in_window": sum(s[3] + s[4] for s in in_win_steps),
        "kv_bytes_per_token": flops.gpt_kv_bytes_per_token(cfg, 2),
        "weight_bytes": flops.gpt_weight_bytes(cfg, 4),
        "n_layer": cfg["n_layer"], "n_head": cfg["n_head"],
        "head_dim": cfg["n_embd"] // cfg["n_head"],
        "prompts_traced": res["prompts_traced"],
        "buckets": prog["buckets"],
    }
    return e2e, facts, len(everyone), failed


def run(ctx):
    mix = ctx.traffic
    prog = build(ctx)
    setup_s = time.monotonic() - ctx.t_process
    res = drive(ctx, prog)
    if res["compiles_in_window"]:
        raise SystemExit(
            f"{res['compiles_in_window']} programs compiled inside the "
            "measured window"
        )
    memory_peak = ctx.memory_peak_bytes()
    e2e, facts, attempted, failed = summarize(ctx, prog, res)
    faults, leaked = counts(prog)

    # free the program's state before the reference runs on the chip
    sample = [(list(lv.req.prompt), list(lv.req.tokens))
              for lv in sample_served(ctx, res["ended"], mix["check_requests"])]
    weights = to_reference(prog["params"])
    prog.clear()
    gc.collect()
    t_ref = time.monotonic()
    gap, scale, n_tok = served_token_gaps(ctx.config, weights, sample) \
        if sample else (float("inf"), 0.0, 0)
    limits = mix["limits"]
    checks = {
        "served_token_gap": {"value": gap, "limit": limits["served_token_gap"]},
        "fault_counters": {"value": faults, "limit": 0},
        "pages_leaked": {"value": leaked, "limit": 0},
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
            "queue_wait_p95_ms",
            "checked_tokens", "ref_logit_scale", "reference_s")},
    }
