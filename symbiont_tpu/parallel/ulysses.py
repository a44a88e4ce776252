"""Ulysses-style sequence parallelism: all-to-all head/sequence re-sharding.

The second of the two standard sequence-parallel schemes (the first, ring
attention, is in ring_attention.py). Where ring attention keeps queries local
and streams K/V blocks around the mesh, Ulysses re-shards with two
all-to-alls: entering attention, each device trades its sequence shard for a
head shard (so it holds the FULL sequence for NH/n heads and runs plain dense
attention — ideal for the MXU, one big matmul, no streaming-softmax carry);
leaving attention, the inverse all-to-all restores sequence sharding. Both
transposes ride ICI as a single collective each.

Trade-offs vs ring (why we ship both):
- Ulysses needs NH divisible by the axis size and moves Q, K, V and the
  output once each (4 all-to-alls of the full activation per attention);
  ring moves only K/V but n-1 times each.
- Ulysses composes head-parallelism-style with any attention kernel (the
  inner attention is just full attention, so the pallas flash kernel drops
  in); ring dictates its own blockwise streaming softmax.

The reference has no sequence parallelism of any kind — it hard-truncates to
one model's max length (reference:
services/preprocessing_service/src/embedding_generator.rs:93-99; SURVEY.md
§5.7). Exactness is tested against full attention on the 8-virtual-device CPU
mesh (tests/test_parallel.py).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P


def _full_attention(q, k, v, causal: bool) -> jax.Array:
    """Plain dense attention, fp32 statistics. [B, S, H, D] layout."""
    B, S, H, D = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,  # [B, S_loc, NH, D] — local sequence shard
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
) -> jax.Array:
    """Exact attention over the full (sharded) sequence; call inside
    shard_map. Requires NH % axis_size == 0."""
    n = axis_size(axis_name)
    NH = q.shape[2]
    if NH % n != 0:
        raise ValueError(f"num_heads {NH} not divisible by axis size {n}")

    # seq-sharded → head-sharded: split heads across the axis, gather the
    # sequence (device order along the axis == global sequence order)
    def to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)  # [B, S, NH/n, D]

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    out = _full_attention(qh, kh, vh, causal)
    # head-sharded → seq-sharded (inverse transpose)
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)  # [B, S_loc, NH, D]


def ulysses_attention_sharded(
    q: jax.Array,  # [B, S, NH, D] — full sequence (host view)
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "data",
    causal: bool = False,
) -> jax.Array:
    """Convenience wrapper: shard the sequence dim over `axis_name` and run
    Ulysses attention; returns the full [B, S, NH, D] result."""
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        partial(ulysses_attention, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
