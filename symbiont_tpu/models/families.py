"""The embedder's model family: the one seam between `TpuEngine` and a
forward. A checkpoint names its family (`config.json` `model_type`); each
family gives its config class, its loader (HF names -> params pytree), a
random `init_params` and `embed(params, ids, mask, cfg, pooling, normalize,
segments=None) -> (rows [B, H] float32, aux)` where `aux` is None or what
the family's forward counts on the device (mla_moe: real tokens per expert
layer and expert). With `segments` (models/bert.py `Segments`: the batched
`embed` program's packed rows) a row holds several sentences and the rows
come back [B, S, H]; without, the forward is the unpacked one the fused
query runs. Everything else — tokenizer, bucketing, batcher, the `embed` /
`qsearch` executables and their cache, pooling, the store — is shared.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

from symbiont_tpu.models import bert, mla_moe
from symbiont_tpu.models.bert import BertConfig
from symbiont_tpu.models.mla_moe import MlaMoeConfig


def _bert_embed(params, ids, mask, cfg, pooling, normalize, segments=None):
    return bert.embed_sentences(params, ids, mask, cfg, pooling=pooling,
                                normalize=normalize, segments=segments), None


def _load_bert(model_dir):
    from symbiont_tpu.models.convert import load_bert_model

    return load_bert_model(model_dir)


def _load_mla_moe(model_dir):
    from symbiont_tpu.models.convert import load_mla_moe_model

    return load_mla_moe_model(model_dir)


@dataclass(frozen=True)
class Family:
    name: str
    config_cls: type
    load: Callable  # model_dir -> (params, cfg)
    init_params: Callable  # (key, cfg) -> params
    embed: Callable


BERT = Family("bert", BertConfig, _load_bert, bert.init_params, _bert_embed)
MLA_MOE = Family("mla_moe", MlaMoeConfig, _load_mla_moe, mla_moe.init_params,
                 mla_moe.embed_sentences)


def family_of_checkpoint(model_dir) -> Family:
    """By the checkpoint's own `model_type`; anything that is not a known
    other family loads as BERT, as every checkpoint did before the seam."""
    from symbiont_tpu.models.convert import load_hf_config

    hf = load_hf_config(model_dir)
    types = {hf.get("model_type"),
             (hf.get("text_config") or {}).get("model_type")}
    return MLA_MOE if types & set(mla_moe.MODEL_TYPES) else BERT


def family_of_config(model_cfg) -> Family:
    return MLA_MOE if isinstance(model_cfg, MLA_MOE.config_cls) else BERT
