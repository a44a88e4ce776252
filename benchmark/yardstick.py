"""The yardstick's arithmetic: peaks, operation and byte counts, quantiles.

Everything here is keyed by what the work IS (tokens embedded, rows scored,
tokens decoded at a cache length), never by which executable did it, and
imports nothing of the program: a later PR cannot change what a metric means
by changing the program.

Copies, with their origin (the originals stay where they are; PERF.md lists
them under Open questions for a later PR to delete):
- `CHIP_PEAKS` / `chip_peaks`, `bert_fwd_flops` and the word list of
  `make_sentences`: symbiont_tpu/bench/workload.py at commit e0e7e98. Its
  sentence-length law (lognormal(2.6, 0.7) words, clipped 3-120) is data in
  the traffic mixes, drawn here as stratified quantiles.
"""

from __future__ import annotations

import statistics

import numpy as np

# ------------------------------------------------------------------ peaks

# Published per-chip peaks, keyed by the EXACT `jax.devices()[0].device_kind`
# string. Source: Google Cloud documentation, "TPU v5e" system architecture
# page (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s). A device
# that is not in the table is an error, never a default.
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"device_kind {device_kind!r} is not in benchmark/yardstick."
            f"CHIP_PEAKS (known: {sorted(CHIP_PEAKS)}); add its published "
            "peaks with their source before benchmarking on it") from None


# -------------------------------------------------------------- sentences

# the vocabulary of bench/workload.make_sentences
WORDS = ["tensor", "processing", "unit", "accelerates", "matrix",
         "products", "the", "memory", "bandwidth", "of", "embeddings",
         "semantic", "search", "pipeline", "document", "sentences",
         "vector", "graph", "tokens", "model", "attention", "masked",
         "pooling", "batch"]


# ------------------------------------------------------- operation counts

def bert_fwd_flops(lengths, H: int, I: int, L: int) -> float:
    """Matmul-only encoder forward FLOPs for sequences of the given REAL
    lengths (useful work: padding is not counted). Per token per layer:
    qkv+out projections 8H^2, MLP 4HI; attention (QK^T + AV) 4*S*H."""
    lengths = np.asarray(lengths, np.float64)
    per_tok = L * (8.0 * H * H + 4.0 * H * I)
    return float((lengths * per_tok + L * 4.0 * H * lengths * lengths).sum())


def encoder_param_bytes(H: int, I: int, L: int, bytes_per: float) -> float:
    """Bytes of the layer weights one forward has to read (embedding rows
    actually gathered are negligible beside them)."""
    return L * (4.0 * H * H + 2.0 * H * I) * bytes_per


def gpt_param_count(H: int, I: int, L: int, V: int, P: int) -> float:
    """GPT-2 parameters: tied embedding V*H, positions P*H, L blocks of
    4H^2 + 2HI (+ biases and norms, counted)."""
    per_layer = 4.0 * H * H + 2.0 * H * I + 9.0 * H + I  # biases + 2 LN
    return V * H + P * H + L * per_layer + 2.0 * H


def gpt_token_flops(cache_len, H: int, I: int, L: int, V: int) -> float:
    """Matmul FLOPs to produce ONE token at each given cache length (the
    positions attended over): L*(8H^2 + 4HI) for the blocks, 4*H*S per layer
    for attention, 2HV for the tied head. Used for decode steps (one call
    per token) and, summed over a prompt's positions, for a causal prefill
    that keeps only the last position's logits (the head counted once)."""
    s = np.asarray(cache_len, np.float64)
    return float((L * (8.0 * H * H + 4.0 * H * I) + L * 4.0 * H * s
                  + 2.0 * H * V).sum())


def gpt_prefill_flops(prompt_len, H: int, I: int, L: int, V: int) -> float:
    """A causal prefill of `prompt_len` real tokens: every position pays the
    blocks and attends over the positions before it; one head matmul."""
    n = np.asarray(prompt_len, np.float64)
    blocks = n * L * (8.0 * H * H + 4.0 * H * I)
    attn = L * 4.0 * H * n * (n + 1.0) / 2.0
    return float((blocks + attn + 2.0 * H * V).sum())


def gpt_decode_step_bytes(cache_len, H: int, I: int, L: int, V: int,
                          weight_bytes: float, kv_bytes: float) -> float:
    """Bytes ONE decode step of ONE sequence has to read: every block weight
    and the tied head once, plus the live KV (2*H per position per layer).
    A batched step shares the weight read: the caller divides that part."""
    s = np.asarray(cache_len, np.float64)
    weights = (L * (4.0 * H * H + 2.0 * H * I) + H * V) * weight_bytes
    kv = L * 2.0 * H * s * kv_bytes
    return float((weights + kv).sum())


def topk_scan_bytes(rows: float, dim: int, bytes_per: float) -> float:
    """Bytes an exact top-k scan of `rows` corpus rows has to read."""
    return float(rows) * dim * bytes_per


def topk_scan_flops(rows: float, dim: int) -> float:
    return 2.0 * float(rows) * dim


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take for this work."""
    return max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"])


# ---------------------------------------------------------------- statistics

def percentile(values, q: float) -> float:
    """Linear-interpolated percentile over ALL values given (q in 0..100)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def spread(values) -> float:
    """Interquartile distance as a share of the median, the contract's way
    (`statistics.quantiles(values, n=4)`)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


# --------------------------------------------------- seeded, stratified draws

def stratified_lognormal(n: int, median: float, sigma: float, lo: float,
                         hi: float, rng) -> np.ndarray:
    """`n` integer sizes from a clipped lognormal: always the same multiset
    (the (i+0.5)/n quantiles), in the order `rng` draws."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    sizes = np.clip(np.exp(np.log(median) + sigma * z), lo, hi)
    return rng.permutation(np.rint(sizes).astype(np.int64))


def stratified_poisson_arrivals(n: int, rate_per_s: float, rng) -> np.ndarray:
    """Due times of `n` Poisson arrivals at `rate_per_s`: the exponential
    gaps are the (i+0.5)/n quantiles (always the same multiset, so the same
    load over the same span), in the order `rng` draws."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate_per_s
    gaps = rng.permutation(gaps)
    # the quantile mean is a hair under 1/rate: rescale so n arrivals span
    # exactly n/rate seconds
    gaps *= (n / rate_per_s) / gaps.sum()
    return np.cumsum(gaps) - gaps[0]
