"""Wall milliseconds per fused search in `engine.qsearch.tokenize`: the
tokenizer over the query, the bucket chosen, the row padded to it. First
part of `qsearch_host_ms.search`."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "span.engine.qsearch.tokenize.ms")
