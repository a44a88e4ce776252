"""CPU milliseconds per page in the tokenizer over a flush's texts
(`engine.embed.tokenize`: `encode_batch`)."""
from _stages import stage_cpu_ms_per_page


def read(ctx):
    return stage_cpu_ms_per_page(ctx, "engine.embed.tokenize")
