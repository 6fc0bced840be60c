"""Driver for cells whose program is ``examples/bert/pretrain_bert.py``.

The recipe builds its model, optimizer, mesh and jitted chunk inside
``main`` and takes a step count, not a duration.  This driver calls
``main`` **once** with a step count it can never reach and steers it from
the three names ``main`` looks up in its own module at call time:

- ``BertForPreTraining`` is wrapped so that ``init`` returns weights made
  by the harness from ``--seed`` (one jitted call, on the device);
- ``DevicePrefetcher`` is subclassed (`_Feed`): the recipe's own feed and
  batches, with a hook at every chunk boundary.  ``main`` has just synced
  the previous chunk's losses when it asks for the next batches, so the
  hook's clock reading is that chunk's completion time.  The hook reads
  ``main``'s live ``params`` / ``opt_state`` / ``all_losses`` out of the
  calling frame after the first chunk (the state `correct` compares),
  opens the window after the warm-up chunks, and closes it by raising
  `_WindowClosed` once ``--seconds`` have passed;
- ``print`` is pointed at stderr so that stdout carries the result only.

One compiled step and one state serve the first steps that `correct`
follows and the window: nothing is built twice.  The step itself is never
re-implemented here.
"""

from __future__ import annotations

import gc
import importlib.util
import os
import sys
import tempfile
import time
import traceback

import numpy as np

from benchmark import flops
from benchmark.weights import seeded_weights
from benchmark.reference import bert as ref_bert
from benchmark.reference import lamb as ref_lamb

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class _WindowClosed(Exception):
    """Raised from the feed to end ``main`` when the window is over."""


# ---------------------------------------------------------------------------
# the corpus from the seed
# ---------------------------------------------------------------------------

def write_corpus(path: str, seed: int, vocab: int, tokens: int, floor: int,
                 zipf_a: float) -> str:
    """A Zipf token file (packed uint16, the recipe's ``--data`` format)."""
    rng = np.random.default_rng(seed)
    toks = floor + (rng.zipf(zipf_a, size=tokens) % (vocab - floor))
    toks.astype(np.uint16).tofile(path)
    return path


# ---------------------------------------------------------------------------
# program tree -> the reference's flat layout (reshapes only)
# ---------------------------------------------------------------------------

def to_reference(tree):
    p = tree["params"]
    emb, lay = p["bert"]["embeddings"], p["bert"]["encoder"]["layers"]["layer"]
    return {
        "word": emb["word_embeddings"]["weight"],
        "pos": emb["position_embeddings"],
        "type": emb["token_type_embeddings"],
        "emb_ln_g": emb["ln"]["scale"], "emb_ln_b": emb["ln"]["bias"],
        "qkv_w": lay["attention"]["qkv"]["weight"],
        "qkv_b": lay["attention"]["qkv"]["bias"],
        "out_w": lay["attention"]["out"]["weight"],
        "out_b": lay["attention"]["out"]["bias"],
        "ln1_g": lay["ln_attn"]["scale"], "ln1_b": lay["ln_attn"]["bias"],
        "fc1_w": lay["mlp"]["fc1"]["weight"], "fc1_b": lay["mlp"]["fc1"]["bias"],
        "fc2_w": lay["mlp"]["fc2"]["weight"], "fc2_b": lay["mlp"]["fc2"]["bias"],
        "ln2_g": lay["ln_mlp"]["scale"], "ln2_b": lay["ln_mlp"]["bias"],
        "mlm_w": p["mlm_dense"]["kernel"], "mlm_b": p["mlm_dense"]["bias"],
        "mlm_ln_g": p["mlm_ln"]["scale"], "mlm_ln_b": p["mlm_ln"]["bias"],
        "mlm_bias": p["mlm_bias"],
        "pool_w": p["pooler"]["kernel"], "pool_b": p["pooler"]["bias"],
        "nsp_w": p["nsp_head"]["kernel"], "nsp_b": p["nsp_head"]["bias"],
    }


def leaf_norms(flat):
    """{leaf: l2 norm} as one small device computation."""
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat.items()}


def batch_first(chunk_batches, step: int):
    """Step ``step`` of a chunk of the recipe's seq-first batches, in the
    reference's batch-first layout."""
    b = {k: np.asarray(v[step]) for k, v in chunk_batches.items()}
    return {
        "input_ids": b["input_ids"].T,
        "token_type_ids": b["token_type_ids"].T,
        "attention_mask": b["attention_mask"],
        "mlm_positions": b["mlm_positions"].T,
        "mlm_label_ids": b["mlm_label_ids"].T,
        "mlm_weights": b["mlm_weights"].T.astype(np.float32),
        "nsp_labels": b["nsp_labels"],
    }


# ---------------------------------------------------------------------------
# the reference's first steps
# ---------------------------------------------------------------------------

def reference_steps(cfg, train, weights, batches, *, prec="f32", micro=None,
                    fault=None, m_after=None):
    """Follow the first ``len(batches)`` steps from ``weights`` (flat
    reference layout).  Returns per-step losses and per-leaf norms of the
    first gradient, of the first moment after ``m_after`` steps (default:
    all) and of the parameters' change after the last step.  ``fault`` plants a
    fault in the reference put in the program's place: ``"half_batch"``
    (the second half of every batch left out, the mean over the rest) or
    ``"lamb_no_v"`` (the update's direction from the first moment alone:
    each step still moves every leaf by lr * |p|, another way)."""
    import jax
    import jax.numpy as jnp

    micro = micro or train["reference_micro_batch"]

    @jax.jit
    def one(p, state_m, state_v, count, batch):
        loss, g = ref_bert.loss_and_grad(p, batch, cfg, micro=micro, prec=prec)
        new_p, st = ref_lamb.step(
            p, g, {"count": count, "m": state_m, "v": state_v},
            lr=train["lr"], weight_decay=train["weight_decay"],
            second_moment=fault != "lamb_no_v",
        )
        return loss, new_p, st["m"], st["v"], leaf_norms(g)

    p = dict(weights)
    m = {k: jnp.zeros_like(v) for k, v in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    losses, g1, mn = [], None, None
    m_after = m_after or len(batches)
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches):
            if fault == "half_batch":
                half = batch["input_ids"].shape[0] // 2
                batch = {k: a[:half] for k, a in batch.items()}
            loss, p, m, v, gn = one(p, m, v, jnp.float32(i), batch)
            losses.append(float(loss))
            if g1 is None:
                g1 = {k: float(x) for k, x in gn.items()}
            if i + 1 == m_after:
                mn = jax.jit(leaf_norms)(m)
        dp = jax.jit(lambda a, b: leaf_norms(
            {k: a[k] - b[k] for k in a}))(p, weights)
    return {
        "losses": losses,
        "grad1_norms": g1,
        "m_norms": {k: float(x) for k, x in mn.items()},
        "dp_norms": {k: float(x) for k, x in dp.items()},
    }


def compare(prog, ref):
    """Gaps between the program's readings and the reference's, each
    relative to the reference: each followed step's loss, and, by the worst
    leaf and by the median leaf, the gap in the norm of the first moment
    (after the first chunk: with one step a chunk that is the first
    gradient as the optimizer got it, times 1 - beta1) and in the norm of
    the parameters' change after the steps followed.  A leaf's gap is |program's norm - reference's
    norm| over the reference's norm of that leaf or of the median leaf,
    whichever is larger.  The traffic file's ``limits`` name the ones
    `correct` compares (PERF.md §2 says why those); the others are printed
    for the look, with the leaves that were worst."""
    # leaves whose reference gradient is nought to rounding move under
    # LAMB by round-off alone: out of the norms by a rule on the
    # reference's first gradient, not by name
    g1 = ref["grad1_norms"]
    cut = 1e-3 * float(np.median(list(g1.values())))
    dead = tuple(k for k, x in g1.items() if x < cut)

    def gaps(key):
        a, b = prog[key], ref[key]
        med = float(np.median([b[k] for k in b if k not in dead]))
        return {k: abs(a[k] - b[k]) / max(b[k], med)
                for k in b if k not in dead}

    def rel(i):
        return abs(prog["losses"][i] - ref["losses"][i]) / abs(ref["losses"][i])

    n = min(len(prog["losses"]), len(ref["losses"]))
    gm, gu = gaps("m_norms"), gaps("dp_norms")
    numbers = {f"loss{i}_gap": rel(i) for i in range(n)}
    for name, g in (("moment", gm), ("update", gu)):
        numbers[name + "_median_gap"] = float(np.median(list(g.values())))
        numbers[name + "_worst_gap"] = max(g.values())
    look = {
        "left_out": list(dead),
        "worst_moment_leaf": max(gm, key=gm.get),
        "worst_update_leaf": max(gu, key=gu.get),
    }
    return numbers, look


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class _Steer:
    """What the feed's hook shares with `run`."""

    def __init__(self, ctx):
        train = ctx.traffic
        self.ctx = ctx
        self.warm_chunks = train["warm_chunks"]
        self.trace_chunks = train["trace_chunks"] if ctx.trace else 0
        self.boundaries = []      # host clock at every chunk boundary
        self.follow_chunks = -(-3 // train["chunk"])   # chunks that hold 3 steps
        if self.warm_chunks < self.follow_chunks:
            raise ValueError("warm_chunks must cover the three steps followed")
        self.first_batches = []   # the followed chunks' batches
        self.snapshot = {}        # program's readings after those chunks
        self.init_weights_fn = None
        self.t_open = None
        self.t_close = None
        self.chunks_in_window = 0
        self.compiles_at_open = None
        self.compiles_at_close = None
        self.trace_dir = None
        self.trace_window_s = None
        self._trace_t0 = None
        self.dead_s = 0.0         # tracer start/stop time inside the window
        self.stall_fraction = None


def _load_recipe():
    spec = importlib.util.spec_from_file_location(
        "pretrain_bert", os.path.join(ROOT, "examples", "bert", "pretrain_bert.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _local(locs, name):
    """One of ``main``'s locals by name.  The recipe has no entry that
    hands out its step and state (PERF.md §7, first on the `tracing`
    list), so a renamed local has to fail here, loudly."""
    if name not in locs:
        raise SystemExit(
            f"examples/bert/pretrain_bert.py::main has no local {name!r} any "
            "more: benchmark/drivers/bert_recipe.py reads the state that "
            "`correct` compares from main's frame by that name")
    return locs[name]


def _moment_norms(locs):
    """Per-leaf norms of the program's first moment: small host values,
    so that nothing of the program's state has to outlive the window."""
    import jax

    mn = jax.jit(lambda m: leaf_norms(to_reference(m)))(
        _local(locs, "opt_state").m)
    return {k: float(x) for k, x in mn.items()}


def _change_norms(locs, weights0):
    """Per-leaf norms of the program's parameters' change since the seeded
    weights (made again by the harness for the subtraction)."""
    import jax

    def norms(params, w0):
        fp, f0 = to_reference(params), to_reference(w0)
        return leaf_norms({k: fp[k] - f0[k] for k in fp})

    dp = jax.jit(norms)(_local(locs, "params"), weights0)
    return {k: float(x) for k, x in dp.items()}


def drive(ctx):
    """Run the recipe through set-up and the window.  Returns the `_Steer`
    with the program's readings.  ``ctx.planted`` names a fault for the
    tests (rehearsal only): ``"frozen_state"`` makes the optimizer return
    its state and the parameters unchanged; ``"half_batch"`` leaves the
    second half of every batch out of the loss, the mean over the rest."""
    import jax

    planted = ctx.planted

    cfg, train = ctx.config, ctx.traffic
    recipe = _load_recipe()
    steer = _Steer(ctx)
    seed, std = ctx.seed, cfg["initializer_range"]

    model_cls = recipe.BertForPreTraining

    class SeededModel:
        """The recipe's model with weights from the harness's seed."""

        def __init__(self, mcfg):
            self._m = model_cls(mcfg)
            self.cfg = mcfg

        def init(self, key, ids):
            shapes = jax.eval_shape(self._m.init, key, ids)
            steer.init_weights_fn = lambda: seeded_weights(shapes, seed, std)
            return steer.init_weights_fn()

        def apply(self, *a, **k):
            return self._m.apply(*a, **k)

    class _Feed(recipe.DevicePrefetcher):
        def __next__(self):
            now = time.monotonic()
            _on_boundary(steer, now, sys._getframe(1))
            with jax.profiler.TraceAnnotation("bench/feed_next"):
                item = super().__next__()
            if len(steer.first_batches) < steer.follow_chunks:
                steer.first_batches.append(item)
            steer.stall_fraction = self.stall_fraction
            return item

    recipe.BertForPreTraining = SeededModel
    recipe.DevicePrefetcher = _Feed
    recipe.print = lambda *a, **k: print(*a, file=sys.stderr, **k)
    if planted == "frozen_state":
        real = recipe.fused_lamb

        def frozen(**kw):
            import optax

            tx = real(**kw)
            return optax.GradientTransformation(
                tx.init,
                lambda g, s, p=None: (jax.tree_util.tree_map(
                    lambda x: x * 0, g), s),
            )

        recipe.fused_lamb = frozen
    if planted == "half_batch":
        whole = recipe.bert_pretrain_loss

        def half(p, model, batch):
            n = batch["nsp_labels"].shape[0] // 2
            cut = {k: (v[:n] if k in ("attention_mask", "nsp_labels")
                       else v[:, :n]) for k, v in batch.items()}
            return whole(p, model, cut)

        recipe.bert_pretrain_loss = half

    corpus = write_corpus(
        os.path.join(tempfile.mkdtemp(prefix="bench_corpus_"), "corpus.bin"),
        seed, cfg["vocab_size"], train["corpus_tokens"],
        train["corpus_floor"], train["zipf_a"],
    )
    argv = [
        "--steps", str(10 ** 9), "--batch", str(train["batch_per_chip"] * ctx.chips),
        "--seq-len", str(train["seq_len"]), "--chunk", str(train["chunk"]),
        "--lr", str(train["lr"]),
        "--max-predictions-per-seq", str(train["max_predictions_per_seq"]),
        "--data", corpus,
    ] + (["--tiny"] if ctx.rehearse else [])
    try:
        recipe.main(argv)
        raise RuntimeError("the recipe returned before the window closed")
    except _WindowClosed as e:
        # main's frame holds params and optimizer state: let them go
        traceback.clear_frames(e.__traceback__)
    finally:
        os.remove(corpus)
        os.rmdir(os.path.dirname(corpus))
        # main leaves its mesh registered; a second drive in one process
        # (benchmark/calibrate.py) needs it gone
        from apex_tpu import parallel_state

        parallel_state.destroy_model_parallel()
    gc.collect()
    return steer


def _on_boundary(steer, now, frame):
    import jax

    ctx = steer.ctx
    if frame.f_code.co_name != "main":
        raise RuntimeError("the feed was not called from the recipe's main")
    k = len(steer.boundaries)          # chunks completed so far
    steer.boundaries.append(now)
    if k == 1 and steer.init_weights_fn is None:
        raise SystemExit(
            "examples/bert/pretrain_bert.py::main did not build its model "
            "through its module's BertForPreTraining: the weights are not "
            "the harness's")
    if k in (1, steer.follow_chunks):
        # the first steps went through the window's own program and feed
        # and are synced: read what `correct` compares.  After the first
        # chunk the first moment; after the chunk that holds step 3 the
        # losses and the parameters' change.
        locs = frame.f_locals
        if k == 1:
            steer.snapshot["m_norms"] = _moment_norms(locs)
        if k == steer.follow_chunks:
            steer.snapshot["losses"] = [
                float(x) for x in _local(locs, "all_losses")]
            steer.snapshot["dp_norms"] = _change_norms(
                locs, steer.init_weights_fn())
    if k < steer.warm_chunks:
        return
    if k == steer.warm_chunks:
        steer.compiles_at_open = ctx.compiles()
        if steer.trace_chunks:
            steer.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(steer.trace_dir)
            steer._trace_t0 = time.monotonic()
        steer.t_open = time.monotonic()
        return
    done = k - steer.warm_chunks
    if steer.trace_dir and steer.trace_window_s is None \
            and done >= steer.trace_chunks:
        steer.trace_window_s = time.monotonic() - steer._trace_t0
        jax.profiler.stop_trace()
        # the device sat idle while the tracer wrote its file: not the
        # program's time (a traced run reports per-layer metrics only)
        steer.dead_s = time.monotonic() - steer._trace_t0 - steer.trace_window_s
        now = time.monotonic()
    if now - steer.t_open >= ctx.seconds:
        steer.t_close = now
        steer.chunks_in_window = done
        steer.compiles_at_close = ctx.compiles()
        raise _WindowClosed()


def checks_of(numbers, train):
    """The numbers `correct` compares, each beside its limit."""
    return {k: {"value": numbers[k], "limit": lim}
            for k, lim in train["limits"].items()}


def reference(ctx, steer, *, prec="f32", fault=None):
    """The reference's readings over the first chunk's batches, from the
    same seeded weights (made again by the harness, not taken from the
    program)."""
    import jax.numpy as jnp

    cfg, train = ctx.config, ctx.traffic
    batches = [batch_first(item, i) for item in steer.first_batches
               for i in range(train["chunk"])]
    weights = {k: jnp.asarray(v, jnp.float32)
               for k, v in to_reference(steer.init_weights_fn()).items()}
    return reference_steps(cfg, train, weights, batches, prec=prec,
                           fault=fault, m_after=train["chunk"])


def run(ctx):
    cfg, train = ctx.config, ctx.traffic
    steer = drive(ctx)
    if steer.compiles_at_close != steer.compiles_at_open:
        raise SystemExit(
            f"{steer.compiles_at_close - steer.compiles_at_open} programs "
            "compiled inside the measured window"
        )
    window = steer.t_close - steer.t_open - steer.dead_s
    steps = steer.chunks_in_window * train["chunk"]
    tokens = steps * train["batch_per_chip"] * ctx.chips * train["seq_len"]
    rate = tokens / window / ctx.chips
    memory_peak = ctx.memory_peak_bytes()

    t_ref = time.monotonic()
    ref = reference(ctx, steer)
    t_ref = time.monotonic() - t_ref
    numbers, look = compare(steer.snapshot, ref)
    checks = checks_of(numbers, train)
    f_tok = flops.bert_train_flops_per_token(
        cfg, train["seq_len"], train["max_predictions_per_seq"]
    )
    return {
        "setup_s": steer.t_open - ctx.t_process,
        "window_s": window,
        "end_to_end": {"train.tokens_per_s_per_chip": rate},
        "attempted": steps,
        "failed": 0,
        "checks": checks,
        "memory_peak_bytes": memory_peak,
        "trace_dir": steer.trace_dir,
        "trace_window_s": steer.trace_window_s,
        "info": {"window_s": window, "steps": steps, "reference_s": t_ref,
                 "input_stall_fraction": steer.stall_fraction,
                 "losses": steer.snapshot["losses"],
                 "ref_losses": ref["losses"],
                 "setup_marks_s": [b - ctx.t_process
                                   for b in steer.boundaries[:3]],
                 "numbers": numbers, "look": look},
        "facts": {
            "tokens_per_s_per_chip": rate,
            "train_flops_per_token": f_tok,
            "steps": steps,
            "chunk": train["chunk"],
        },
    }
