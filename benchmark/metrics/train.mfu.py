"""Whole training step's share of the chips' bf16 peak: the model's forward
+ backward operations per token (benchmark/flops.py, from shapes, no
recomputation) x tokens per second per chip over the peak."""


def read(run):
    if not run["peaks"]:
        return None
    f = run["facts"]
    return 100.0 * f["train_flops_per_token"] * f["tokens_per_s_per_chip"] \
        / run["peaks"]["bf16_flops_per_s"]
