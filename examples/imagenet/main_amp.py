"""ResNet-50 mixed-precision training — ≙ ``examples/imagenet/main_amp.py``
(``main``, ``train``, ``data_prefetcher``).

Demonstrates the full single-host recipe: ``amp.initialize`` opt levels
O0–O3, data parallelism over the mesh's ``dp`` axis (apex-DDP analog),
optional SyncBatchNorm, and a prefetching input pipeline (a background
thread stages the next batch while the device runs the current step —
the ``data_prefetcher`` side-stream analog).

Runs on any backend; with no ImageNet on disk it generates synthetic
data (shape-identical), like the reference's ``--prof`` dry runs.

    python examples/imagenet/main_amp.py --opt-level O2 --sync-bn \
        --batch-size 64 --steps 30

On CPU: ``JAX_PLATFORMS=cpu`` and an optional
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for 8-way dp.
"""

import os
import sys

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), "../.."))
)

import argparse
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, parallel_state as ps
from apex_tpu.models import resnet50
from apex_tpu.parallel import all_reduce_gradients
from apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--opt-level", default="O1", choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--batch-size", type=int, default=64, help="global batch")
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--lr", type=float, default=0.02)
    p.add_argument("--sync-bn", action="store_true")
    return p.parse_args()


class data_prefetcher:
    """Background-thread batch staging — ≙ main_amp.py :: data_prefetcher
    (whose CUDA side-stream becomes a host thread + async device_put)."""

    def __init__(self, it, depth: int = 2):
        self.q = queue.Queue(maxsize=depth)
        self.it = it
        self.t = threading.Thread(target=self._fill, daemon=True)
        self.t.start()

    def _fill(self):
        for batch in self.it:
            # device_put is async: the transfer overlaps the running step
            self.q.put(jax.device_put(batch))
        self.q.put(None)

    def __iter__(self):
        return self

    def __next__(self):
        item = self.q.get()
        if item is None:
            raise StopIteration
        return item


def synthetic_loader(args, steps):
    rng = np.random.RandomState(0)
    for _ in range(steps):
        x = rng.randn(
            args.batch_size, args.image_size, args.image_size, 3
        ).astype(np.float32)
        y = rng.randint(0, args.num_classes, (args.batch_size,))
        yield {"image": x, "label": y}


def main():
    args = parse_args()
    enable_compile_cache()
    mesh = ps.initialize_model_parallel()  # all devices on the dp axis
    dp = ps.get_data_parallel_world_size()
    if args.batch_size % dp:
        raise SystemExit(f"--batch-size must be divisible by dp={dp}")

    model = resnet50(
        num_classes=args.num_classes, use_syncbn=args.sync_bn,
        dtype=jnp.bfloat16 if args.opt_level != "O0" else jnp.float32,
    )
    tx = optax.sgd(args.lr, momentum=0.9)

    x0 = jnp.zeros((2, args.image_size, args.image_size, 3))
    variables = model.init(jax.random.PRNGKey(0), x0, train=False)
    params, handle = amp.initialize(
        variables["params"], tx, opt_level=args.opt_level,
        loss_scale=args.loss_scale,
    )
    batch_stats = variables.get("batch_stats", {})
    amp_state = handle.init(params)

    def loss_fn(params, batch_stats, batch):
        logits, updates = model.apply(
            {"params": handle.policy.cast_to_compute(params),
             "batch_stats": batch_stats},
            batch["image"], train=True, mutable=["batch_stats"],
        )
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.mean(
            jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)
        )
        return loss, updates["batch_stats"]

    def train_step(params, batch_stats, amp_state, batch):
        def scaled(p):
            loss, new_stats = loss_fn(p, batch_stats, batch)
            return handle.scale_loss(loss, amp_state), (loss, new_stats)

        (_, (loss, new_stats)), grads = jax.value_and_grad(
            scaled, has_aux=True
        )(params)
        grads = all_reduce_gradients(grads)
        params, amp_state2, _found_inf = handle.step(params, grads, amp_state)
        loss = jax.lax.pmean(loss, ps.DATA_PARALLEL_AXIS)
        return params, new_stats, amp_state2, loss

    sharded = jax.jit(
        jax.shard_map(
            train_step,
            mesh=mesh,
            in_specs=(P(), P(), P(), {"image": P("dp"), "label": P("dp")}),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )
    )

    loader = data_prefetcher(synthetic_loader(args, args.steps))
    t0, seen = time.perf_counter(), 0
    for i, batch in enumerate(loader):
        params, batch_stats, amp_state, loss = sharded(
            params, batch_stats, amp_state, batch
        )
        seen += args.batch_size
        if i % 10 == 0:
            scale = float(handle.state_dict(amp_state)["loss_scale"])
            print(f"step {i:4d}  loss {float(loss):.4f}  scale {scale:.0f}")
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    print(
        f"done: {seen} images in {dt:.1f}s = {seen / dt:.1f} img/s "
        f"(opt_level={args.opt_level}, dp={dp}, sync_bn={args.sync_bn})"
    )


if __name__ == "__main__":
    main()
