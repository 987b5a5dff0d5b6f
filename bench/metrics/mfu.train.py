"""The whole train step's share of the chip's bf16 peak: train tokens per
second times the benchmark's own operations per token (6 N + causal
attention at the mix's sequence length) over the peak."""
from bench.harness import costs


def read(ctx):
    rate = ctx.extra.get("train_tokens_per_s")
    if not rate:
        return None
    f = costs.train_flops_per_token(ctx.config, ctx.mix["seq_len"])
    return 100.0 * rate * f / ctx.peaks["bf16_flops"]
