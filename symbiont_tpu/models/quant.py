"""Weight quantization for the BERT/GPT forwards — narrow HBM reads, fused
dequant.

ROADMAP item 4: both remaining hot paths are bandwidth-bound, not FLOP-bound
(mixed-length embed MFU 25.6%, TinyLlama decode HBM-bound at ~714 GB/s), so
the lever is moving fewer bytes per forward, per "Hardware Acceleration of
Fully Quantized BERT" (arxiv 2103.02800) and "Demystifying BERT" (arxiv
2104.08335). Three storage modes, all selected by a config knob
(`EngineConfig.quantize` / `LmConfig.quantize`) and applied ONCE on host at
load time:

- `f16`  — floating params of rank ≥ 2 stored bfloat16 at rest. The forward
  already computes in bf16, so the entry cast becomes a no-op and every
  weight read out of HBM is half the bytes of the f32-at-rest default.
- `int8` — symmetric per-channel int8 (scale over the LAST axis: the output
  features of an [in, out] kernel, the hidden dim of an embedding table).
  Dequant is algebraically fused into the consumer: `x @ W` becomes
  `(x @ q) * scale` (exact for per-output-channel scales), so XLA reads
  int8 from HBM, upcasts in registers, and never materializes a
  dequantized copy.
- `fp8`  — float8_e4m3fn storage with the same per-channel scale mapping
  each channel's amax to the e4m3 max (448). Same fused-dequant contract;
  coarser mantissa (3 bits) than int8's effective 7, so its parity bar is
  looser (docs/QUANTIZATION.md).

Quantized leaves are `QuantTensor` pytree nodes — (q, scale) ride through
jit / device_put / donation like any other params, and `cast_params` (the
shared entry-cast used by models/bert.py, models/gpt.py and engine/lm.py)
treats them as atomic leaves so the f32 scales are never downcast by the
compute-dtype sweep. An encoder's embedding tables stay out of that sweep:
`take` gathers the rows a call names from the table at rest and casts the
rows, so a float32 table is never rewritten whole inside a forward.

Rank-1 params (biases, norm scales) stay f32: they are a rounding error of
the byte budget and the norms want exact statistics.

The int8 KV-cache variant (quantize-on-append / dequant-on-attend) lives
with its consumer in models/gpt.py; this module only provides the shared
per-channel quantizer it uses.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from symbiont_tpu.config import QUANTIZE_MODES as MODES

Params = Any

_INT8_AMAX = 127.0
_FP8_AMAX = 448.0  # float8_e4m3fn finite max
_NEVER_QUANTIZED = ("scale", "bias")  # leaf names of norm params and biases


@jax.tree_util.register_pytree_node_class
class QuantTensor:
    """A per-channel-quantized weight: `q` (int8 or fp8, [r, c]) and `scale`
    (f32, [c], over the LAST axis); or a stack of such kernels `q` [E, r, c]
    with `scale` [E, c], one scale per stacked kernel and output channel
    (expert kernels: 64 experts must not share one scale; the layers of a
    scanned stack: `lax.scan` slices `q` and `scale` together, and the body
    gets the first form). Dequantized value = q * scale. Registered as a
    pytree node so it flows through jit/device_put; every
    cast-to-compute-dtype sweep must treat it as a leaf (cast_params)."""

    __slots__ = ("q", "scale")

    def __init__(self, q, scale):
        self.q = q
        self.scale = scale

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def nbytes(self):
        return self.q.nbytes + self.scale.nbytes

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    def dequantize(self, dtype=jnp.float32):
        scale = self.scale if self.q.ndim == 2 else self.scale[..., None, :]
        return (self.q.astype(jnp.float32) * scale).astype(dtype)


def is_quantized(x) -> bool:
    return isinstance(x, QuantTensor)


def _leaf(x) -> bool:
    return isinstance(x, QuantTensor)


def channel_quantize(w, amax: float, qdtype) -> QuantTensor:
    """Symmetric per-channel quantization over the last axis; of a stack of
    kernels [..., in, out] only the `in` axis is reduced, so every stacked
    kernel keeps its own scales [..., out]. Host-side, runs once at load."""
    wf = jnp.asarray(w, jnp.float32)
    axes = tuple(range(wf.ndim - 1)) if wf.ndim <= 2 else (wf.ndim - 2,)
    scale = jnp.max(jnp.abs(wf), axis=axes) / amax
    scale = jnp.maximum(scale, 1e-12)
    q = wf / (scale if wf.ndim <= 2 else scale[..., None, :])
    if jnp.issubdtype(jnp.dtype(qdtype), jnp.integer):
        q = jnp.round(q)
    return QuantTensor(q.astype(qdtype), scale.astype(jnp.float32))


def quantize_params(params: Params, mode: str) -> Params:
    """Quantize every floating leaf of rank ≥ 2 (matmul kernels, embedding
    tables) per `mode`; rank-1 leaves (biases, norm params) stay f32, and so
    do norm scales and biases STACKED on a leading layer axis (`scale` /
    `bias` [layers, n] of a stack a scan slices, models/ouro.py): what a
    leaf is for is said by its name, which a stack does not change.
    Idempotent on already-quantized leaves. Runs ONCE on host."""
    if mode not in MODES:
        raise ValueError(f"quantize must be one of {MODES}, got {mode!r}")
    if mode == "none":
        return params

    def one(path, a):
        if isinstance(a, QuantTensor):
            return a
        if not (hasattr(a, "dtype") and hasattr(a, "ndim")
                and jnp.issubdtype(a.dtype, jnp.floating) and a.ndim >= 2):
            return a
        if path and getattr(path[-1], "key", None) in _NEVER_QUANTIZED:
            return a
        if mode == "f16":
            return jnp.asarray(a, jnp.bfloat16)
        if mode == "int8":
            return channel_quantize(a, _INT8_AMAX, jnp.int8)
        return channel_quantize(a, _FP8_AMAX, jnp.float8_e4m3fn)

    return jax.tree_util.tree_map_with_path(one, params, is_leaf=_leaf)


def cast_params(params: Params, dtype) -> Params:
    """The shared entry cast: floating leaves → compute dtype, QuantTensor
    leaves untouched (their f32 scales must survive the sweep — dequant
    precision rides on them)."""
    dtype = jnp.dtype(dtype)

    def cast(a):
        if isinstance(a, QuantTensor):
            return a
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(dtype)
        return a

    return jax.tree.map(cast, params, is_leaf=_leaf)


def param_bytes(params: Params) -> int:
    """At-rest parameter bytes of a (possibly quantized) pytree — the
    dtype-labeled `engine.param_bytes` / `lm.param_bytes` gauges."""
    total = 0
    for leaf in jax.tree.leaves(params, is_leaf=_leaf):
        if isinstance(leaf, QuantTensor):
            total += int(leaf.nbytes)
        elif hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
    return total


# ------------------------------------------------------- fused-dequant ops

def mm(x, w):
    """`x @ w` with dequant fused into the matmul epilogue when `w` is
    quantized: per-output-channel scales commute with the contraction, so
    `(x @ q) * scale` is exactly `x @ (q * scale)` — HBM reads the narrow
    `q`, the scale multiply runs on the [.., out] result in registers."""
    if isinstance(w, QuantTensor):
        return ((x @ w.q.astype(x.dtype)) * w.scale).astype(x.dtype)
    return x @ w


def ragged_mm(x, w, group_sizes, row_group):
    """Grouped matmul over stacked kernels `w` [G, in, out]: rows of `x`
    [m, in] are sorted by group, `group_sizes` [G] says how many rows each
    kernel takes (rows past their sum belong to no group: their output is
    unspecified and the caller discards it), `row_group` [m] names each
    row's group. One grouped matmul (ops/grouped_matmul.py: the Pallas
    kernel on the chip where the shapes tile, `jax.lax.ragged_dot`
    elsewhere): no kernel is applied to a row that is not its own.
    Quantized stacks dequantize in the epilogue with the row's own group's
    scales."""
    # here and not at the top: pallas costs a second and a half to import,
    # and a BERT process never routes an expert (bert.py does the same)
    from symbiont_tpu.ops.grouped_matmul import grouped_matmul

    if isinstance(w, QuantTensor):
        y = grouped_matmul(x, w.q.astype(x.dtype), group_sizes)
        return (y * w.scale[row_group]).astype(x.dtype)
    return grouped_matmul(x, w, group_sizes)


def mm_tied(x, w):
    """`x @ w.T` for a tied embedding head. The scale axis (hidden) is the
    CONTRACTION axis after the transpose, so it is applied to `x` first:
    `(x * scale) @ q.T` == `x @ (q * scale).T` exactly."""
    if isinstance(w, QuantTensor):
        return (x * w.scale).astype(x.dtype) @ w.q.T.astype(x.dtype)
    return x @ w.T


def take(w, ids, dtype=None):
    """Embedding-table gather: the rows `ids` name, and only those, are read
    and converted. A plain table is gathered in its at-rest dtype and the
    gathered rows are cast to `dtype` (the compute dtype; None leaves them at
    rest): `cast(table)[ids]` equals `cast(table[ids])` bit for bit, but a
    table cast first is swept whole on every forward (250,002 x 768 float32
    read, bfloat16 written, for the 8-128 rows a query names), so the gather
    comes first and no caller casts a table before it. A quantized table
    dequantizes per hidden channel, `q[ids] * scale` (exact per element), and
    returns float32 whatever `dtype` says: a caller that sums several tables
    sums them at full precision and casts the sum to its compute dtype."""
    if isinstance(w, QuantTensor):
        return w.q[ids].astype(jnp.float32) * w.scale
    rows = w[ids]
    return rows if dtype is None else rows.astype(dtype)


def kv_channel_quantize(t, eps: float = 1e-8):
    """Quantize-on-append for the int8 KV cache (models/gpt.py): one scale
    per appended (batch, position, kv-head) vector over head_dim, so each
    head's fresh K/V row maps its own amax to ±127. Returns (q int8,
    scale f32 [..., heads])."""
    tf = t.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(tf), axis=-1), eps) / _INT8_AMAX
    q = jnp.round(tf / scale[..., None]).astype(jnp.int8)
    return q, scale


def kv_dequantize(q, scale, dtype):
    """Dequant-on-attend: int8 cache slab * its per-head scales → compute
    dtype. The f32 intermediate never leaves registers; HBM reads int8 +
    the (head_dim× smaller) scale plane."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)
