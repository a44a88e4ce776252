"""The embedder's model family: the one seam between `TpuEngine` and a
forward. A checkpoint names its family (`config.json` `model_type`); each
family gives its config class, its loader (HF names -> params pytree), a
random `init_params` and `embed(params, ids, mask, cfg, pooling, normalize,
segments=None) -> (rows [B, H] float32, aux)` where `aux` is None or what
the family's forward counts on the device (mla_moe: real tokens per expert
layer and expert; sala: keys attended, causal keys and dense-path tokens per
row and sparse layer; ouro: each loop step's exit mass and the token-steps
run, per row; ling: real tokens per expert layer and held expert, routed
choices, passages started and the MLA kernel's block pairs; mimo: the same
expert counts, the keys the window layers attended beside the causal keys
and the full kernel's block steps), and `note_aux(aux)`, which books a fetched `aux` under the
family's own series (None where the forward counts nothing). With
`segments` (models/bert.py `Segments`: the batched
`embed` program's packed rows) a row holds several sentences and the rows
come back [B, S, H]; without, the forward is the unpacked one the fused
query runs. Everything else — tokenizer, bucketing, batcher, the `embed` /
`qsearch` executables and their cache, pooling, the store — is shared.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from symbiont_tpu.models import bert, ling, mimo, mla_moe, ouro, sala
from symbiont_tpu.models.bert import BertConfig
from symbiont_tpu.models.ling import LingConfig
from symbiont_tpu.models.mimo import MimoConfig
from symbiont_tpu.models.mla_moe import MlaMoeConfig
from symbiont_tpu.models.ouro import OuroConfig
from symbiont_tpu.models.sala import SalaConfig
from symbiont_tpu.utils.telemetry import metrics


def _bert_embed(params, ids, mask, cfg, pooling, normalize, segments=None):
    return bert.embed_sentences(params, ids, mask, cfg, pooling=pooling,
                                normalize=normalize, segments=segments), None


def _load_bert(model_dir):
    from symbiont_tpu.models.convert import load_bert_model

    return load_bert_model(model_dir)


def _load_mla_moe(model_dir):
    from symbiont_tpu.models.convert import load_mla_moe_model

    return load_mla_moe_model(model_dir)


def _load_sala(model_dir):
    from symbiont_tpu.models.convert import load_sala_model

    return load_sala_model(model_dir)


def _load_ouro(model_dir):
    from symbiont_tpu.models.convert import load_ouro_model

    return load_ouro_model(model_dir)


def _load_ling(model_dir):
    from symbiont_tpu.models.convert import load_ling_model

    return load_ling_model(model_dir)


def _load_mimo(model_dir):
    from symbiont_tpu.models.convert import load_mimo_model

    return load_mimo_model(model_dir)


_LABELS = {"service": "engine"}


def _note_moe(counts) -> None:
    """Expert-load series of one embed dispatch: `counts` [expert layers,
    E] = real tokens each expert took (docs/OBSERVABILITY.md)."""
    metrics.inc("engine.moe.assignments", int(counts.sum()), labels=_LABELS)
    metrics.inc("engine.moe.experts_idle", int((counts == 0).sum()),
                labels=_LABELS)
    for layer in counts:
        if layer.sum() > 0:
            metrics.observe("engine.moe.expert_load_max_over_mean",
                            float(layer.max() / layer.mean()),
                            labels=_LABELS)


def _note_sparse(counts) -> None:
    """Block-selection series of one embed dispatch: `counts` [rows, sparse
    layers, 2] = keys attended, keys a causal attention reads
    (docs/OBSERVABILITY.md)."""
    per_layer = np.asarray(counts, np.int64).sum(0)
    attended, causal = (int(v) for v in per_layer.sum(0))
    metrics.inc("engine.sparse.keys_attended", attended, labels=_LABELS)
    metrics.inc("engine.sparse.keys_causal", causal, labels=_LABELS)
    for kept, of in per_layer:
        if of > 0:
            metrics.observe("engine.sparse.kept_share", float(kept / of),
                            labels=_LABELS)


def _note_loop(aux) -> None:
    """Loop series of one embed dispatch: `aux` [rows, steps + 1] float32 =
    per row each published step's exit mass (the sum over its real tokens of
    `p_t`: a row's steps add up to its real tokens) and, last, the
    token-steps the loop ran (docs/OBSERVABILITY.md)."""
    aux = np.asarray(aux, np.float64)
    mass, steps = aux[:, :-1].sum(0), aux.shape[1] - 1
    tokens = int(round(mass.sum()))
    metrics.inc("engine.loop.token_steps_run", int(round(aux[:, -1].sum())),
                labels=_LABELS)
    metrics.inc("engine.loop.token_steps_published", tokens * steps,
                labels=_LABELS)
    for t, m in enumerate(mass):
        metrics.inc("engine.loop.exit_mass", float(m),
                    labels={**_LABELS, "step": str(t)})
    if tokens > 0:
        metrics.observe("engine.loop.expected_exit_step",
                        float((mass * np.arange(steps)).sum() / mass.sum()),
                        labels=_LABELS)


def _note_ling(aux) -> None:
    """Series of one embed dispatch of the `ling` family: `aux` [expert
    layers + 2, held] int32 = the held experts' real-token counts by layer
    (the `mla_moe` series over held experts), then [every real token's
    routed choices held or not, passages started x KDA layers, ...], then
    [the MLA kernel's block pairs that computed, the pairs under its
    diagonal, ...] (docs/OBSERVABILITY.md)."""
    aux = np.asarray(aux, np.int64)
    _note_moe(aux[:-2])
    metrics.inc("engine.moe.assignments_routed", int(aux[-2, 0]),
                labels=_LABELS)
    metrics.inc("engine.kda.state_resets", int(aux[-2, 1]), labels=_LABELS)
    metrics.inc("engine.attn.block_steps_run", int(aux[-1, 0]),
                labels=_LABELS)
    metrics.inc("engine.attn.block_steps_causal", int(aux[-1, 1]),
                labels=_LABELS)


def _note_mimo(aux) -> None:
    """Series of one embed dispatch of the `mimo` family: `aux` int32 = a
    row [every real token's routed choices held or not, window layers,
    held, expert layers], the held experts' real-token counts by expert
    layer (the `mla_moe` series over held experts), then a row per batch
    row [keys the window layers attended, causal keys one layer would see,
    the full layers' kernel steps that computed, the steps under their
    diagonal] (docs/OBSERVABILITY.md)."""
    aux = np.asarray(aux, np.int64)
    routed, windows, held, layers = (int(v) for v in aux[0, :4])
    _note_moe(aux[1:1 + layers, :held])
    metrics.inc("engine.moe.assignments_routed", routed, labels=_LABELS)
    attended, causal, steps_run, steps_causal = aux[1 + layers:, :4].sum(0)
    metrics.inc("engine.attn.window_keys", int(attended), labels=_LABELS)
    metrics.inc("engine.attn.keys_causal", int(causal) * windows,
                labels=_LABELS)
    metrics.inc("engine.attn.block_steps_run", int(steps_run),
                labels=_LABELS)
    metrics.inc("engine.attn.block_steps_causal", int(steps_causal),
                labels=_LABELS)


@dataclass(frozen=True)
class Family:
    name: str
    model_types: tuple  # `config.json` `model_type`s that name it
    config_cls: type
    load: Callable  # model_dir -> (params, cfg)
    init_params: Callable  # (key, cfg) -> params
    embed: Callable
    note_aux: Optional[Callable] = None  # fetched aux -> the family's series


BERT = Family("bert", bert.MODEL_TYPES, BertConfig, _load_bert,
              bert.init_params, _bert_embed)
MLA_MOE = Family("mla_moe", mla_moe.MODEL_TYPES, MlaMoeConfig, _load_mla_moe,
                 mla_moe.init_params, mla_moe.embed_sentences, _note_moe)
SALA = Family("sala", sala.MODEL_TYPES, SalaConfig, _load_sala,
              sala.init_params, sala.embed_sentences, _note_sparse)
OURO = Family("ouro", ouro.MODEL_TYPES, OuroConfig, _load_ouro,
              ouro.init_params, ouro.embed_sentences, _note_loop)
LING = Family("ling", ling.MODEL_TYPES, LingConfig, _load_ling,
              ling.init_params, ling.embed_sentences, _note_ling)
MIMO = Family("mimo", mimo.MODEL_TYPES, MimoConfig, _load_mimo,
              mimo.init_params, mimo.embed_sentences, _note_mimo)
FAMILIES = (BERT, MLA_MOE, SALA, OURO, LING, MIMO)
_BY_TYPE = {t: f for f in FAMILIES for t in f.model_types}
_BY_CONFIG = {f.config_cls: f for f in FAMILIES}


def family_of_checkpoint(model_dir) -> Family:
    """By the checkpoint's own `model_type` (a vision-language checkpoint
    nests its text tower's under `text_config`; a config that names none
    reads as `bert`, as `BertConfig.from_hf` reads it). A type no family
    claims is refused here, before a loader meets tensors it cannot name."""
    from symbiont_tpu.models.convert import load_hf_config

    hf = load_hf_config(model_dir)
    types = (hf.get("model_type", "bert"),
             (hf.get("text_config") or {}).get("model_type"))
    for model_type in types:
        if model_type in _BY_TYPE:
            return _BY_TYPE[model_type]
    raise ValueError(
        f"{model_dir}: no embedder family claims model_type {types[0]!r} "
        f"(claimed: {', '.join(sorted(_BY_TYPE))})")


def family_of_config(model_cfg) -> Family:
    return _BY_CONFIG.get(type(model_cfg), BERT)
