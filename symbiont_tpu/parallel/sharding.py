"""Sharding specs: how params and batches lay out on the mesh.

Design per the scaling-book recipe: pick a mesh, annotate shardings with
NamedSharding/PartitionSpec, let XLA insert the collectives. Nothing here
issues a collective by hand except ring attention (which needs the explicit
ppermute schedule).
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = Any


def replicate(mesh: Mesh, tree: Params) -> Params:
    """Fully replicate a pytree across the mesh (embedding models: weights are
    small; DP wants replicas)."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Shard dim 0 (batch) over the data axis; everything else replicated."""
    return NamedSharding(mesh, P(axis))


def _gpt_layer_spec(arch: str) -> dict:
    """TP rules for one decoder layer: attention heads and MLP hidden shard on
    'tensor'; output projections shard the contracting dim so XLA reduces the
    partial sums with a psum over 'tensor'."""
    col = P(None, "tensor")  # [in, out] sharded on out
    row = P("tensor", None)  # [in, out] sharded on in  (contraction → psum)
    vec = P("tensor")
    if arch == "gpt2":
        return {
            "ln1": {"scale": P(), "bias": P()},
            "ln2": {"scale": P(), "bias": P()},
            "q": {"kernel": col, "bias": vec},
            "k": {"kernel": col, "bias": vec},
            "v": {"kernel": col, "bias": vec},
            "o": {"kernel": row, "bias": P()},
            "mlp": {
                "in": {"kernel": col, "bias": vec},
                "out": {"kernel": row, "bias": P()},
            },
        }
    return {
        "ln1": {"scale": P()},
        "ln2": {"scale": P()},
        "q": {"kernel": col},
        "k": {"kernel": col},
        "v": {"kernel": col},
        "o": {"kernel": row},
        "mlp": {
            "gate": {"kernel": col},
            "up": {"kernel": col},
            "down": {"kernel": row},
        },
    }


def gpt_param_sharding(mesh: Mesh, params: Params, arch: str = "gpt2") -> Params:
    """PartitionSpec tree for decoder LM params (megatron-style TP).

    The vocab dim shards only when it divides the tensor axis; otherwise the
    embedding/head replicate (correct either way — vocab sharding is a
    memory optimization, and odd vocabs like the 257-entry byte tokenizer
    must still serve)."""
    layer_spec = _gpt_layer_spec(arch)
    tp = mesh.shape.get("tensor", 1)
    vocab_divides = params["wte"].shape[0] % tp == 0
    spec: dict = {
        "wte": P("tensor", None) if vocab_divides else P(),
        "layers": [layer_spec for _ in params["layers"]],
        "ln_f": {k: P() for k in params["ln_f"]},
    }
    if "wpe" in params:
        spec["wpe"] = P()
    if "lm_head" in params:
        spec["lm_head"] = {"kernel": P(None, "tensor") if vocab_divides
                           else P()}
    return spec


def _is_quant(x) -> bool:
    from symbiont_tpu.models.quant import QuantTensor

    return isinstance(x, QuantTensor)


def shard_params(mesh: Mesh, params: Params, spec_tree: Params) -> Params:
    """Place params on the mesh per a PartitionSpec tree.

    QuantTensor leaves (models/quant.py int8/fp8 weights) shard too: the
    codes take the kernel's own spec, and the per-output-channel scale
    vector shards on the kernel's LAST axis entry — a col-sharded kernel
    P(None, 'tensor') keeps its scales co-resident with their channels
    (P('tensor')), a row-sharded kernel P('tensor', None) has unsharded
    output channels so the scales replicate. That co-residency is what lets
    `quantize=int8` compose with TP decode instead of falling back
    unquantized (ROADMAP item 1 / PR 7 gap)."""
    from symbiont_tpu.models.quant import QuantTensor

    def place(arr, spec):
        if isinstance(arr, QuantTensor):
            scale_spec = P(spec[-1]) if len(spec) else P()
            return QuantTensor(
                jax.device_put(arr.q, NamedSharding(mesh, spec)),
                jax.device_put(arr.scale, NamedSharding(mesh, scale_spec)))
        return jax.device_put(arr, NamedSharding(mesh, spec))

    return jax.tree.map(
        place,
        params,
        spec_tree,
        is_leaf=lambda x: isinstance(x, P) or _is_quant(x),
    )
