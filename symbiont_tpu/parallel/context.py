"""Context parallelism: sequence-sharded decoder training forward.

SURVEY.md §5.7 records that the reference hard-truncates every sequence to one
model's max length (reference: embedding_generator.rs:93-99) and has no
sequence parallelism of any kind. Here long-context LM *training* is
first-class: the batch's sequence dim shards over a mesh axis, every token
mixing op is local except attention, and attention is exact over the full
sequence via the ring schedule (parallel/ring_attention.py — K/V blocks rotate
over ICI with `ppermute` while a streaming softmax accumulates). Activation
memory per device is O(S/n); attention FLOPs stay exact, not windowed.

This is the training-side complement of the KV-cache decode path in
models/gpt.py: same params pytree, same layer math (`_ln`/`_rmsnorm`/`_rope`
are imported, not re-implemented), no cache — causality comes from the ring
step's global-position mask.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from symbiont_tpu.models.gpt import (
    GPTConfig,
    _ln,
    _rmsnorm,
    block_nocache,
    qkv_proj,
)
from symbiont_tpu.parallel.ring_attention import ring_attention
from symbiont_tpu.parallel.ulysses import ulysses_attention

Params = Any


def _block_sp(layer, x, positions, cfg: GPTConfig, axis: str, attn_impl: str):
    """One decoder block with sequence-parallel attention; x: [B, S_loc, H]
    (local shard), positions: [B, S_loc] global token positions. Block
    scaffolding and QKV projection come from models/gpt (block_nocache /
    qkv_proj) — only the attention schedule is local to this module."""
    B, S, H = x.shape
    nh, nkv = cfg.num_heads, cfg.kv_heads

    def attn(h):
        q, k, v = qkv_proj(layer, h, positions, cfg)
        if attn_impl == "ulysses":
            # Ulysses re-shards heads over the axis, so K/V must be at full
            # head count first (the all-to-all splits the head dim)
            if nkv != nh:
                k = jnp.repeat(k, nh // nkv, axis=2)
                v = jnp.repeat(v, nh // nkv, axis=2)
            ctx = ulysses_attention(q, k, v, axis, causal=True).reshape(B, S, H)
        else:
            # GQA: K/V stay at nkv heads — the ring rotates the compact
            # blocks and expands to nh only at the local score computation
            ctx = ring_attention(q, k, v, axis, causal=True).reshape(B, S, H)
        return ctx @ layer["o"]["kernel"] + layer["o"].get("bias", 0)

    return block_nocache(layer, x, cfg, attn)


def gpt_forward_sp(
    params: Params,
    input_ids: jax.Array,  # [B, S] — S divisible by mesh.shape[axis]
    mesh: Mesh,
    cfg: GPTConfig,
    axis: str = "data",
    attn_impl: str = "ring",
) -> jax.Array:
    """Sequence-parallel training forward → logits [B, S, V] (sharded on S).

    Params replicate; activations shard on the sequence dim; the only
    cross-device traffic is the ring's K/V rotation. Equality with the
    KV-cache forward (models/gpt.py) is asserted in tests/test_parallel.py.
    """
    n = mesh.shape[axis]
    B, S = input_ids.shape
    if S % n != 0:
        raise ValueError(f"sequence length {S} not divisible by mesh axis "
                         f"{axis!r} size {n}")
    dtype = jnp.dtype(cfg.dtype)
    params = jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        params)

    def local(params, ids_loc):  # ids_loc: [B, S/n]
        idx = jax.lax.axis_index(axis)
        S_loc = ids_loc.shape[1]
        positions = jnp.broadcast_to(
            idx * S_loc + jnp.arange(S_loc, dtype=jnp.int32), (B, S_loc))
        x = params["wte"][ids_loc]
        if cfg.arch == "gpt2":
            x = x + params["wpe"][positions]
        for layer in params["layers"]:
            x = _block_sp(layer, x, positions, cfg, axis, attn_impl)
        if cfg.arch == "gpt2":
            x = _ln(x, params["ln_f"], cfg.layer_norm_eps)
        else:
            x = _rmsnorm(x, params["ln_f"], cfg.layer_norm_eps)
        head = (params["wte"].T if cfg.tie_word_embeddings
                else params["lm_head"]["kernel"])
        return (x @ head).astype(jnp.float32)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(None, axis)),
        out_specs=P(None, axis, None),
    )
    return fn(params, input_ids)


def lm_loss_sp(params: Params, batch: dict, cfg: GPTConfig, mesh: Mesh,
               axis: str = "data", attn_impl: str = "ring") -> jax.Array:
    """Next-token CE over a sequence-sharded forward. The shifted-target
    gather crosses shard boundaries; XLA inserts the halo exchange."""
    import optax

    ids = batch["ids"]
    mask = batch["mask"].astype(jnp.float32)
    logits = gpt_forward_sp(params, ids, mesh, cfg, axis=axis,
                            attn_impl=attn_impl)
    targets = ids[:, 1:]
    w = mask[:, 1:] * mask[:, :-1]
    ce = optax.softmax_cross_entropy_with_integer_labels(logits[:, :-1], targets)
    return (ce * w).sum() / jnp.maximum(w.sum(), 1.0)


def make_lm_train_step_sp(mesh: Mesh, cfg: GPTConfig, tx, axis: str = "data",
                          attn_impl: str = "ring"):
    """Build a jitted sequence-parallel LM train step bound to (mesh, axis).

    Complements trainer.lm_train_step: same TrainState/metrics contract, but
    activations shard over the sequence so contexts far beyond one device's
    HBM train exactly (ring attention, no approximation).
    """
    from symbiont_tpu.train.trainer import TrainState

    @partial(jax.jit, donate_argnums=(0,))
    def step(state: TrainState, batch: dict):
        loss, grads = jax.value_and_grad(lm_loss_sp)(
            state.params, batch, cfg, mesh, axis, attn_impl)
        import optax

        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return (TrainState(new_params, opt_state, state.step + 1),
                {"loss": loss, "grad_norm": optax.global_norm(grads)})

    return step
