"""Device-plane parallelism (ICI): mesh, sharding specs, ring attention.

The reference has NO device parallelism of any kind — single candle device,
serial batch-8 loop (reference:
services/preprocessing_service/src/embedding_generator.rs:146-216), and its
only "distributed" layer is NATS pub/sub between single-instance services
(SURVEY.md §2 parallelism inventory). This package is the TPU-native design
that replaces that absence:

mesh       : named device meshes (axes: data, tensor) over real TPU slices or
             the 8-virtual-device CPU backend used in tests
sharding   : NamedSharding rules — DP batch sharding for embedding, TP rules
             for decoder LM params (heads / MLP hidden on 'tensor')
ring_attention : sequence-parallel blockwise attention via shard_map+ppermute
             for long-context (a first-class capability the reference lacks)
context    : sequence-parallel decoder LM *training* — the full train-time
             forward with activations sharded on the sequence dim and exact
             causal attention over the ring (gpt_forward_sp / lm_loss_sp /
             make_lm_train_step_sp)
ulysses    : the all-to-all sequence-parallel scheme — trade sequence shards
             for head shards, run dense attention, trade back (same exactness
             contract as ring; pick per workload)
pipeline   : GPipe pipeline parallelism — layer stages sharded over a 'pipe'
             axis, microbatch activations flowing via ppermute inside one
             jitted scan (lm_loss_pp / make_lm_train_step_pp /
             make_pp_train_state; exact vs the unsharded step)

XLA inserts the collectives (psum/all-gather/ppermute ride ICI); this package
only defines meshes and shardings — no hand-written NCCL analog (SURVEY.md §2
"Distributed communication backend").
"""

from symbiont_tpu.parallel.mesh import (
    build_mesh,
    init_distributed,
    local_device_count,
    mesh_from_config,
    parse_mesh_spec,
)
from symbiont_tpu.parallel.sharding import (
    batch_sharding,
    gpt_param_sharding,
    replicate,
    shard_params,
)
from symbiont_tpu.parallel.context import (
    gpt_forward_sp,
    lm_loss_sp,
    make_lm_train_step_sp,
)
from symbiont_tpu.parallel.ring_attention import (
    ring_attention,
    ring_attention_sharded,
)
from symbiont_tpu.parallel.ulysses import (
    ulysses_attention,
    ulysses_attention_sharded,
)
from symbiont_tpu.parallel.pipeline import (
    lm_loss_pp,
    make_lm_train_step_pp,
    make_pp_train_state,
)

__all__ = [
    "build_mesh",
    "init_distributed",
    "local_device_count",
    "batch_sharding",
    "replicate",
    "gpt_param_sharding",
    "shard_params",
    "gpt_forward_sp",
    "lm_loss_sp",
    "make_lm_train_step_sp",
    "ring_attention",
    "ring_attention_sharded",
    "ulysses_attention",
    "ulysses_attention_sharded",
    "lm_loss_pp",
    "make_lm_train_step_pp",
    "make_pp_train_state",
]
