"""Shared bench workload helpers: synthetic corpus, FLOPs model, chip peaks.

Kept device-import-free at module level so `--gate` / `--validate` work
without importing jax.
"""

from __future__ import annotations

import sys

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def make_sentences(n: int, rng) -> list:
    """Synthetic corpus with a realistic sentence-length mix (most sentences
    short, a tail of long ones — what the scraper actually produces)."""
    words = ["tensor", "processing", "unit", "accelerates", "matrix",
             "products", "the", "memory", "bandwidth", "of", "embeddings",
             "semantic", "search", "pipeline", "document", "sentences",
             "vector", "graph", "tokens", "model", "attention", "masked",
             "pooling", "batch"]
    out = []
    for _ in range(n):
        ln = int(np.clip(rng.lognormal(2.6, 0.7), 3, 120))
        out.append(" ".join(rng.choice(words, size=ln)))
    return out


# ------------------------------------------------------------------ MFU math

# Published per-chip peaks, keyed by the EXACT `jax.devices()[0].device_kind`
# string. Source: Google Cloud documentation, "TPU v5e" system architecture
# page (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s). A kind
# lands here only after a run on that chip printed it (PERF.md records the
# run) — a device that is not in the table is an error, never a default.
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def chip_peaks(device_kind: str) -> dict:
    """Peaks for one device kind; raises on a kind the table does not hold
    (a utilisation against a guessed peak is worse than no number)."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"device_kind {device_kind!r} is not in bench/workload.CHIP_PEAKS "
            f"(known: {sorted(CHIP_PEAKS)}); add its published peaks with "
            "their source before benchmarking on it") from None


def bert_fwd_flops(lengths, H: int, I: int, L: int, seq_for_attn=None) -> float:
    """Matmul-only BERT forward FLOPs for a batch of sequences.

    Per token per layer: qkv+out projections 8H², MLP 4HI; attention
    (QKᵀ + AV) 4·S·H where S is the sequence length attended over. With
    seq_for_attn=None S is the sentence's own (real) length — useful-work
    FLOPs; pass the padded bucket length to count what the chip executed."""
    lengths = np.asarray(lengths, np.float64)
    s_attn = lengths if seq_for_attn is None else np.asarray(seq_for_attn,
                                                             np.float64)
    per_tok = L * (8.0 * H * H + 4.0 * H * I)
    return float((lengths * per_tok + L * 4.0 * H * lengths * s_attn).sum())
