"""Pure-JAX model zoo.

Models are pure functions over parameter pytrees (nested dicts of jax arrays) —
no framework Module state — so they compose directly with jit/shard_map/pjit
and with the training transforms in symbiont_tpu.train.

bert     : encoder family (BERT / XLM-RoBERTa layouts) covering the embedding
           models in BASELINE.md (MiniLM, mpnet-multilingual, bge, e5) and the
           ms-marco cross-encoder
mla_moe  : MLA + routed/shared-expert decoder stacks (DeepSeek-V3 layout:
           Kimi-VL-A3B's language tower) pooled as sentence embedders
families : the embedder's family seam (BERT | mla_moe), chosen by the
           checkpoint's `model_type`; what `TpuEngine` calls
layers   : RMSNorm / RoPE / SwiGLU shared by gpt and mla_moe
convert  : HF torch/safetensors checkpoints → parameter pytrees
gpt      : decoder LMs (GPT-2 layout + Llama/TinyLlama layout) with static-shape
           KV-cache decode
markov   : order-1 word Markov chain (reference parity:
           services/text_generator_service/src/main.rs:13-109)
"""

from symbiont_tpu.models.bert import BertConfig, bert_encode, embed_sentences

__all__ = ["BertConfig", "bert_encode", "embed_sentences"]
