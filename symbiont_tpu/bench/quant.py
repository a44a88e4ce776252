"""Quantization tier: the bandwidth win, gated with its quality bars.

ROADMAP item 4 names the attack (int8/fp8 weights + quantized KV decode)
and this tier keeps it honest in BOTH dimensions, archived like every
other metric:

- SPEED primaries: `quant_embed_int8_vs_bf16_x` (mixed-length embed
  throughput, int8 weights vs the f32-at-rest baseline, same engine
  geometry and corpus, median of 3 waves each) and
  `quant_decode_int8kv_vs_bf16_x` (batched greedy decode tok/s, int8 KV
  cache vs the dtype-native cache, same params). Both are SAME-RUN ratios.
- QUALITY primaries: `quant_embed_cos_int8` — min per-row cosine between
  int8 and baseline embeddings on a seeded 256-sentence corpus (the bar is
  ≥ 0.999, the same gate tier-1 enforces on tiny models). f16/fp8 cosines
  and the KV greedy-match fraction archive as secondary fields.
- capacity: `quant_kv_bytes_x` — baseline cache bytes ÷ int8 cache bytes
  at the decode shapes (the dtype-adjusted KV capacity factor the
  lm.kv_cache_bytes gauge reports live).
"""

from __future__ import annotations

import time

import numpy as np

from symbiont_tpu.bench import stats
from symbiont_tpu.bench.tiers import register
from symbiont_tpu.bench.workload import log, make_sentences

N_EMBED = 2048        # throughput corpus (mixed lengths)
N_QUALITY = 256       # parity corpus
EMBED_REPS = 3
DECODE_B, DECODE_NEW = 8, 64
COS_BAR = 0.999


def _row_cos(a: np.ndarray, b: np.ndarray) -> float:
    num = np.sum(a * b, axis=1)
    den = np.maximum(np.linalg.norm(a, axis=1) * np.linalg.norm(b, axis=1),
                     1e-12)
    return float((num / den).min())


@register("quant", primary_metrics=(
        "quant_embed_cos_int8", "quant_embed_int8_vs_bf16_x",
        "quant_decode_int8kv_vs_bf16_x"))
def tier_quant(results: dict, ctx) -> None:
    from symbiont_tpu.config import EngineConfig, LmConfig
    from symbiont_tpu.engine.engine import TpuEngine
    from symbiont_tpu.engine.lm import LmEngine

    rng = np.random.default_rng(23)
    corpus = [s.capitalize() for s in make_sentences(N_EMBED, rng)]
    quality = corpus[:N_QUALITY]

    # ---- embed: bf16-compute engines, f32-at-rest vs quantized-at-rest
    def mk_engine(mode: str) -> TpuEngine:
        return TpuEngine(EngineConfig(embedding_dim=384, quantize=mode))

    base = mk_engine("none")
    base_q = base.embed_texts(quality)

    def waves(eng) -> list:
        eng.embed_texts(corpus[:256])  # warm the executables
        out = []
        for _ in range(EMBED_REPS):
            t0 = time.perf_counter()
            eng.embed_texts(corpus)
            out.append(N_EMBED / (time.perf_counter() - t0))
        return out

    base_rates = waves(base)
    for mode in ("int8", "f16", "fp8"):
        eng = mk_engine(mode)
        cos = _row_cos(base_q, eng.embed_texts(quality))
        results[f"quant_embed_cos_{mode}"] = round(cos, 5)
        if mode == "int8":
            rates = waves(eng)
            ratio = (sorted(rates)[len(rates) // 2]
                     / sorted(base_rates)[len(base_rates) // 2])
            stats.record(results, "quant_embed_int8_emb_per_s", rates,
                         digits=0)
            results["quant_embed_int8_vs_bf16_x"] = round(ratio, 2)
        del eng
    stats.record(results, "quant_embed_bf16_emb_per_s", base_rates, digits=0)
    del base
    if results["quant_embed_cos_int8"] < COS_BAR:
        raise AssertionError(
            f"int8 embed parity broke the ≥{COS_BAR} bar: "
            f"{results['quant_embed_cos_int8']}")
    log(f"quant embed: int8 {results['quant_embed_int8_vs_bf16_x']}× bf16 "
        f"throughput at cos {results['quant_embed_cos_int8']} "
        f"(f16 {results['quant_embed_cos_f16']}, "
        f"fp8 {results['quant_embed_cos_fp8']})")

    # ---- decode: same params, dtype-native KV vs int8 KV
    from symbiont_tpu.models import gpt as gpt_mod

    def mk_lm(kv: str) -> LmEngine:
        return LmEngine(LmConfig(enabled=True, kv_quant=kv, seed=7))

    prompts = [" ".join(make_sentences(1, np.random.default_rng(100 + i)))
               for i in range(DECODE_B)]
    budgets = [DECODE_NEW] * DECODE_B

    def decode_rate(lm) -> tuple:
        lm.generate_batch(prompts, budgets, temperature=0.0)  # warm
        t0 = time.perf_counter()
        out = lm.generate_batch(prompts, budgets, temperature=0.0)
        dt = time.perf_counter() - t0
        toks = sum(len(lm.tokenizer.encode(t, 1 << 30)) for t in out)
        cache = gpt_mod.init_cache(lm.model_cfg, DECODE_B, 64 + DECODE_NEW,
                                   lm.model_cfg.dtype)
        return max(toks, 1) / dt, out, gpt_mod.cache_bytes(cache)

    lm_a = mk_lm("none")
    rate_a, out_a, bytes_a = decode_rate(lm_a)
    del lm_a
    lm_b = mk_lm("int8")
    rate_b, out_b, bytes_b = decode_rate(lm_b)
    del lm_b
    results["quant_decode_int8kv_vs_bf16_x"] = round(rate_b / rate_a, 2)
    results["quant_kv_bytes_x"] = round(bytes_a / bytes_b, 2)
    results["quant_kv_greedy_match_pct"] = round(
        100.0 * sum(a == b for a, b in zip(out_a, out_b)) / len(out_a), 1)
    log(f"quant decode: int8 KV {results['quant_decode_int8kv_vs_bf16_x']}× "
        f"tok/s, {results['quant_kv_bytes_x']}× rows/byte, greedy match "
        f"{results['quant_kv_greedy_match_pct']}% "
        f"(bf16 KV rounds differently — token identity is only guaranteed "
        f"at f32, where tier-1 pins it)")
