"""CPU milliseconds per page in the three handlers' own sections: extract
the page's text and serialise it (`perception.extract`), parse, clean and
split it (`preprocessing.split`), frame the embeddings
(`preprocessing.frame`), decode them and build ids and payloads
(`vector_memory.decode`)."""
from _stages import stage_cpu_ms_per_page


def read(ctx):
    return stage_cpu_ms_per_page(
        ctx, "perception.extract", "preprocessing.split",
        "preprocessing.frame", "vector_memory.decode")
