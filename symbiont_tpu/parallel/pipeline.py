"""Pipeline parallelism (GPipe schedule) for decoder-LM training.

SURVEY.md §2's parallelism table scoped PP out for the reference's model
sizes but required the mesh to keep a slot for it ("design mesh axes so PP
can be added"). This module fills that slot with a real implementation, the
TPU-idiomatic way: no scheduler process, no send/recv framework — the whole
schedule is ONE jitted SPMD program. Layers are stacked and sharded over a
`pipe` mesh axis (each device holds a contiguous stage of depth L/P);
microbatch activations flow stage-to-stage with `lax.ppermute` over ICI
inside a `lax.scan` over the GPipe timeline; `jax.grad` differentiates
straight through the collective, so the backward schedule falls out of the
forward's transpose instead of being hand-written.

Semantics are exact: the pipelined loss/step equals the plain
trainer.lm_train_step on the same batch (asserted in tests/test_parallel.py)
— microbatching changes the schedule, not the math, because each microbatch's
loss contributions are accumulated as (ce_sum, weight_sum) and normalized
once at the end.

Deliberate simplicity (documented, not hidden): embeddings and the LM head
replicate on every stage and run every tick with the results masked — at
these vocab/model sizes (SURVEY.md: nothing above TinyLlama-1.1B) the waste
is small and the program stays a single dense scan XLA can pipeline; a
head-sharded schedule is the upgrade path if the model zoo ever outgrows it.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from symbiont_tpu.models.gpt import (
    GPTConfig,
    _ln,
    _rmsnorm,
    block_nocache,
    qkv_proj,
)

Params = Any


def _block_dense(layer, x, positions, cfg: GPTConfig):
    """One decoder block, plain causal attention, no cache — the stage-local
    training forward. Block scaffolding and QKV projection come from
    models/gpt (block_nocache / qkv_proj); only the dense causal attention
    is local to this module."""
    import math

    B, S, H = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim

    def attn(h):
        q, k, v = qkv_proj(layer, h, positions, cfg)
        if nkv != nh:
            k = jnp.repeat(k, nh // nkv, axis=2)
            v = jnp.repeat(v, nh // nkv, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal[None, None], scores.astype(jnp.float32), -1e9)
        probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H)
        return ctx @ layer["o"]["kernel"] + layer["o"].get("bias", 0)

    return block_nocache(layer, x, cfg, attn)


# ------------------------------------------------------------------ params


def stack_layers(params: Params) -> Params:
    """Re-shape the per-layer param list into stacked arrays with a leading
    layer axis — the shape PP shards over `pipe` (and lax.scan consumes).
    The rest of the tree (embeddings, final norm, head) is passed through."""
    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *params["layers"])
    return out


def shard_pp_params(mesh: Mesh, stacked: Params, axis: str = "pipe") -> Params:
    """Place stacked params on the mesh: layer stack split over the pipe
    axis (each device holds its stage's depth), everything else replicated."""
    n = mesh.shape[axis]
    L = jax.tree.leaves(stacked["layers"])[0].shape[0]
    if L % n != 0:
        raise ValueError(f"num_layers {L} not divisible by pipe axis size {n}")
    placed = {
        k: jax.tree.map(lambda a: jax.device_put(
            a, NamedSharding(mesh, P())), v)
        for k, v in stacked.items() if k != "layers"
    }
    placed["layers"] = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P(axis))),
        stacked["layers"])
    return placed


# ----------------------------------------------------------------- forward


def lm_loss_pp(params: Params, batch: dict, cfg: GPTConfig, mesh: Mesh,
               axis: str = "pipe", num_microbatches: int = 4) -> jax.Array:
    """Masked next-token CE through the GPipe schedule. `params` is the
    stacked form (stack_layers); batch["ids"/"mask"]: [B, S] with B
    divisible by num_microbatches."""
    n_stages = mesh.shape[axis]
    ids, mask = batch["ids"], batch["mask"]
    B, S = ids.shape
    M = num_microbatches
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    dtype = jnp.dtype(cfg.dtype)
    params = jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, params)
    mB = B // M

    def local(stage_layers, shared, ids, mask):
        # stage_layers: [L/P, ...] — this device's contiguous depth slice
        p = jax.lax.axis_index(axis)
        ids_m = ids.reshape(M, mB, S)
        mask_m = mask.reshape(M, mB, S)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mB, S))
        head = (shared["wte"].T if cfg.tie_word_embeddings
                else shared["lm_head"]["kernel"])

        def embed(mb_ids):
            x = shared["wte"][mb_ids]
            if cfg.arch == "gpt2":
                x = x + shared["wpe"][positions]
            return x.astype(dtype)

        def run_stage(x):
            def body(x, layer):
                return _block_dense(layer, x, positions, cfg), None
            return jax.lax.scan(body, x, stage_layers)[0]

        def micro_loss(x, mb_mask, mb_ids):
            if cfg.arch == "gpt2":
                x = _ln(x, shared["ln_f"], cfg.layer_norm_eps)
            else:
                x = _rmsnorm(x, shared["ln_f"], cfg.layer_norm_eps)
            logits = (x @ head).astype(jnp.float32)
            import optax

            m = mb_mask.astype(jnp.float32)
            w = m[:, 1:] * m[:, :-1]
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], mb_ids[:, 1:])
            return (ce * w).sum(), w.sum()

        def tick(carry, t):
            x, ce_acc, w_acc = carry
            # GPipe dataflow: stage p at tick t processes microbatch t-p.
            # Stage 0 injects a fresh microbatch; others use the activation
            # received last tick. Out-of-range ticks compute on stale data
            # and are masked out of the loss (their grads are exactly zero).
            feed = embed(ids_m[jnp.clip(t, 0, M - 1)])
            x = jnp.where(p == 0, feed, x)
            x = run_stage(x)
            m_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            ce, w = micro_loss(x, mask_m[m_idx], ids_m[m_idx])
            valid = ((p == n_stages - 1) & (t >= n_stages - 1)
                     ).astype(jnp.float32)
            ce_acc = ce_acc + valid * ce
            w_acc = w_acc + valid * w
            x = jax.lax.ppermute(
                x, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (x, ce_acc, w_acc), None

        x0 = jnp.zeros((mB, S, cfg.hidden_size), dtype)
        zero = jnp.zeros((), jnp.float32)  # strong-typed: scan carry must
        #                                    not drift from weak to strong
        # the carry becomes device-varying after the first tick (axis_index
        # select + ppermute), so the initial value must be marked varying too
        x0, zero_ce, zero_w = pcast((x0, zero, zero), (axis,),
                                            to="varying")
        (x, ce_acc, w_acc), _ = jax.lax.scan(
            tick, (x0, zero_ce, zero_w), jnp.arange(M + n_stages - 1))
        # only the last stage accumulated; psum replicates the totals
        ce_acc = jax.lax.psum(ce_acc, axis)
        w_acc = jax.lax.psum(w_acc, axis)
        return ce_acc / jnp.maximum(w_acc, 1.0)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(), P(), P()),
        out_specs=P(),
    )
    shared = {k: v for k, v in params.items() if k != "layers"}
    return fn(params["layers"], shared, ids, mask)


def make_lm_train_step_pp(mesh: Mesh, cfg: GPTConfig, tx, axis: str = "pipe",
                          num_microbatches: int = 4):
    """Jitted pipeline-parallel LM train step bound to (mesh, axis).

    Same TrainState/metrics contract as trainer.lm_train_step; state params
    must be the stacked+sharded form (stack_layers → shard_pp_params, or
    make_pp_train_state). The backward schedule is jax.grad's transpose of
    the forward scan — reverse ppermutes included."""
    from symbiont_tpu.train.trainer import TrainState

    @partial(jax.jit, donate_argnums=(0,))
    def step(state: TrainState, batch: dict):
        import optax

        loss, grads = jax.value_and_grad(lm_loss_pp)(
            state.params, batch, cfg, mesh, axis, num_microbatches)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return (TrainState(new_params, opt_state, state.step + 1),
                {"loss": loss, "grad_norm": optax.global_norm(grads)})

    return step


def make_pp_train_state(mesh: Mesh, params: Params, learning_rate: float = 3e-4,
                        axis: str = "pipe"):
    """TrainState over stacked+sharded params (optimizer state inherits the
    same shardings via tx.init on the placed arrays)."""
    import optax

    from symbiont_tpu.train.trainer import TrainState

    placed = shard_pp_params(mesh, stack_layers(params), axis=axis)
    from symbiont_tpu.train.trainer import _adamw

    tx = _adamw(learning_rate)  # same optimizer as make_lm_train_state —
    #                             the PP and plain steps must stay in lockstep
    return TrainState(placed, tx.init(placed),
                      jnp.zeros((), jnp.int32)), tx
