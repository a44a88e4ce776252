"""HF checkpoint → JAX parameter pytrees.

Replaces the reference's weight path — hf-hub download + unsafe mmap VarBuilder
into candle (reference:
services/preprocessing_service/src/embedding_generator.rs:25-58,106-124) — with
an offline converter: local safetensors / torch `.bin` state_dicts are mapped
into the pytree layout of symbiont_tpu.models.bert (and .gpt). No network: the
engine points at a local model dir (config.engine.model_dir). Converted params
can be checkpointed via symbiont_tpu.train.checkpoint so engine restarts skip
reconversion (SURVEY.md §5.4 plan).

Handles the BERT-family layouts named in BASELINE.md: bert.* (MiniLM/bge/e5,
ms-marco cross-encoder), roberta.* (xlm-roberta = mpnet-multilingual), plus
bare (headless) encoder dumps. Torch Linear stores [out, in]; kernels are
transposed to [in, out] on conversion (see bert.py layout note).
"""

from __future__ import annotations

import json
import os
import re
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any, Dict

import numpy as np

from symbiont_tpu.models.bert import BertConfig

Params = Any


def _to_numpy(t) -> np.ndarray:
    if isinstance(t, np.ndarray):
        return t
    # torch tensor (cpu) without importing torch at module load
    return t.detach().cpu().numpy()


def _transposed(mats) -> np.ndarray:
    """n torch kernels [out, in] (numpy) -> ONE C-contiguous array [n, in, out].

    A transposing copy is strided and slow (a 2,048 x 1,408 bfloat16 kernel:
    ~30 ms on one core), and a stack of `.T` views only postpones it to the
    upload, one leaf at a time: an expert model pays it 768 times at every
    boot. So the copies are made here, once, in blocks of columns on a
    thread pool (numpy drops the GIL for a plain-integer copy, which is why
    the bytes are viewed as unsigned ints of the same width)."""
    rows, cols = mats[0].shape
    out = np.empty((len(mats), cols, rows), mats[0].dtype)
    bits = np.dtype(f"u{out.dtype.itemsize}")
    raw, step = out.view(bits), 512
    srcs = [np.ascontiguousarray(w).view(bits) for w in mats]

    def copy(job):
        e, lo = job
        raw[e, lo:lo + step] = srcs[e][:, lo:lo + step].T

    jobs = [(e, lo) for e in range(len(mats)) for lo in range(0, cols, step)]
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(copy, jobs))
    return out


def load_state_dict(model_dir: str | Path) -> Dict[str, np.ndarray]:
    """Load weights from a local model dir: model.safetensors (preferred,
    incl. sharded index — parity with the reference's sharded handling at
    embedding_generator.rs:36-50) or pytorch_model.bin."""
    model_dir = Path(model_dir)
    st = model_dir / "model.safetensors"
    idx = model_dir / "model.safetensors.index.json"
    if st.exists():
        from safetensors.numpy import load_file

        return load_file(str(st))
    if idx.exists():
        from safetensors.numpy import load_file

        weight_map = json.loads(idx.read_text())["weight_map"]
        out: Dict[str, np.ndarray] = {}
        for shard in sorted(set(weight_map.values())):
            out.update(load_file(str(model_dir / shard)))
        return out
    bin_path = model_dir / "pytorch_model.bin"
    if bin_path.exists():
        import torch

        sd = torch.load(str(bin_path), map_location="cpu", weights_only=True)
        return {k: _to_numpy(v) for k, v in sd.items()}
    raise FileNotFoundError(f"no model.safetensors or pytorch_model.bin in {model_dir}")


def load_hf_config(model_dir: str | Path) -> dict:
    return json.loads((Path(model_dir) / "config.json").read_text())


_PREFIXES = ("bert.", "roberta.", "mpnet.", "model.", "electra.")
# a vision-language checkpoint (Kimi-VL) nests its text tower one level down
_LM_PREFIXES = ("language_model.model.", "model.")


def _strip_prefix(name: str) -> str:
    for p in _PREFIXES:
        if name.startswith(p):
            return name[len(p):]
    return name


def convert_bert(
    state_dict: Dict[str, Any], cfg: BertConfig, with_pooler: bool = False
) -> Params:
    """Map an HF BERT/XLM-RoBERTa state_dict to the bert.py pytree."""
    sd = {_strip_prefix(k): _to_numpy(v) for k, v in state_dict.items()}

    def take(name: str) -> np.ndarray:
        if name not in sd:
            raise KeyError(f"checkpoint missing tensor {name!r}; have e.g. "
                           f"{sorted(sd)[:5]}")
        return sd[name].astype(np.float32)

    def linear(prefix: str) -> dict:
        return {"kernel": take(f"{prefix}.weight").T, "bias": take(f"{prefix}.bias")}

    def ln(prefix: str) -> dict:
        return {"scale": take(f"{prefix}.weight"), "bias": take(f"{prefix}.bias")}

    params: Params = {
        "embeddings": {
            "word_embeddings": take("embeddings.word_embeddings.weight"),
            "position_embeddings": take("embeddings.position_embeddings.weight"),
            "token_type_embeddings": (
                take("embeddings.token_type_embeddings.weight")
                if "embeddings.token_type_embeddings.weight" in sd
                else np.zeros((cfg.type_vocab_size, cfg.hidden_size), np.float32)
            ),
            "ln": ln("embeddings.LayerNorm"),
        },
        "layers": [],
    }
    for i in range(cfg.num_layers):
        p = f"encoder.layer.{i}"
        params["layers"].append(
            {
                "attention": {
                    "query": linear(f"{p}.attention.self.query"),
                    "key": linear(f"{p}.attention.self.key"),
                    "value": linear(f"{p}.attention.self.value"),
                    "out": linear(f"{p}.attention.output.dense"),
                    "ln": ln(f"{p}.attention.output.LayerNorm"),
                },
                "mlp": {
                    "in": linear(f"{p}.intermediate.dense"),
                    "out": linear(f"{p}.output.dense"),
                    "ln": ln(f"{p}.output.LayerNorm"),
                },
            }
        )
    if with_pooler:
        params["pooler"] = linear("pooler.dense")
        # cross-encoder classifier head lives outside the encoder prefix
        cls_key = "classifier.weight" if "classifier.weight" in sd else None
        if cls_key:
            params["classifier"] = {"kernel": take("classifier.weight").T,
                                    "bias": take("classifier.bias")}
    return params


def convert_gpt(state_dict: Dict[str, Any], cfg) -> Params:
    """Map an HF GPT-2 or Llama state_dict to the gpt.py pytree.

    GPT-2 uses Conv1D modules whose weights are already [in, out]; the fused
    c_attn [H, 3H] is split into q/k/v. Llama uses Linear ([out, in] →
    transposed) with separate q/k/v/o and SwiGLU gate/up/down.
    """
    import numpy as np

    sd = {_strip_prefix(k.replace("transformer.", "")): _to_numpy(v)
          for k, v in state_dict.items()}

    def take(name):
        if name not in sd:
            raise KeyError(f"checkpoint missing tensor {name!r}")
        return sd[name].astype(np.float32)

    params: Params = {"layers": []}
    if cfg.arch == "gpt2":
        params["wte"] = take("wte.weight")
        params["wpe"] = take("wpe.weight")
        params["ln_f"] = {"scale": take("ln_f.weight"), "bias": take("ln_f.bias")}
        H = cfg.hidden_size
        for i in range(cfg.num_layers):
            p = f"h.{i}"
            qkv_w = take(f"{p}.attn.c_attn.weight")  # [H, 3H] (Conv1D)
            qkv_b = take(f"{p}.attn.c_attn.bias")
            qw, kw, vw = np.split(qkv_w, 3, axis=1)
            qb, kb, vb = np.split(qkv_b, 3)
            params["layers"].append({
                "ln1": {"scale": take(f"{p}.ln_1.weight"), "bias": take(f"{p}.ln_1.bias")},
                "ln2": {"scale": take(f"{p}.ln_2.weight"), "bias": take(f"{p}.ln_2.bias")},
                "q": {"kernel": qw, "bias": qb},
                "k": {"kernel": kw, "bias": kb},
                "v": {"kernel": vw, "bias": vb},
                "o": {"kernel": take(f"{p}.attn.c_proj.weight"),
                      "bias": take(f"{p}.attn.c_proj.bias")},
                "mlp": {
                    "in": {"kernel": take(f"{p}.mlp.c_fc.weight"),
                           "bias": take(f"{p}.mlp.c_fc.bias")},
                    "out": {"kernel": take(f"{p}.mlp.c_proj.weight"),
                            "bias": take(f"{p}.mlp.c_proj.bias")},
                },
            })
    elif cfg.arch == "llama":
        params["wte"] = take("embed_tokens.weight")
        params["ln_f"] = {"scale": take("norm.weight")}
        for i in range(cfg.num_layers):
            p = f"layers.{i}"
            params["layers"].append({
                "ln1": {"scale": take(f"{p}.input_layernorm.weight")},
                "ln2": {"scale": take(f"{p}.post_attention_layernorm.weight")},
                "q": {"kernel": take(f"{p}.self_attn.q_proj.weight").T},
                "k": {"kernel": take(f"{p}.self_attn.k_proj.weight").T},
                "v": {"kernel": take(f"{p}.self_attn.v_proj.weight").T},
                "o": {"kernel": take(f"{p}.self_attn.o_proj.weight").T},
                "mlp": {
                    "gate": {"kernel": take(f"{p}.mlp.gate_proj.weight").T},
                    "up": {"kernel": take(f"{p}.mlp.up_proj.weight").T},
                    "down": {"kernel": take(f"{p}.mlp.down_proj.weight").T},
                },
            })
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"kernel": take("lm_head.weight").T}
    else:
        raise ValueError(f"unsupported arch {cfg.arch!r}")
    return params


def convert_mla_moe(state_dict: Dict[str, Any], cfg) -> Params:
    """Map an HF DeepSeek-V3-layout state_dict (Kimi-VL's language tower) to
    the mla_moe.py pytree. Torch Linear [out, in] -> [in, out]; the experts
    of a layer are stacked [E, in, out] as they are read. Leaf by leaf and
    in the checkpoint's own dtype (bfloat16 for the published weights): no
    float32 copy of a 16 B-parameter checkpoint is ever made on the host;
    rank-1 leaves (norm scales, the router's correction bias) go to float32.
    The output head (`lm_head`) and a vision tower are not read: the encoder
    role pools hidden states of text."""
    sd = {}
    for k, v in state_dict.items():
        for prefix in _LM_PREFIXES:
            if k.startswith(prefix):
                sd[k[len(prefix):]] = v
                break

    def take(name: str) -> np.ndarray:
        if name not in sd:
            raise KeyError(f"checkpoint missing tensor {name!r}; have e.g. "
                           f"{sorted(sd)[:5]}")
        return _to_numpy(sd.pop(name))

    def kernel(name: str) -> dict:
        return {"kernel": _transposed([take(f"{name}.weight")])[0]}

    def ln(name: str) -> dict:
        return {"scale": take(f"{name}.weight").astype(np.float32)}

    def mlp(prefix: str) -> dict:
        return {k: kernel(f"{prefix}.{k}_proj") for k in ("gate", "up", "down")}

    def stacked(prefix: str, proj: str) -> dict:
        return {"kernel": _transposed(
            [take(f"{prefix}.experts.{e}.{proj}_proj.weight")
             for e in range(cfg.n_routed_experts)])}

    params: Params = {"wte": take("embed_tokens.weight"),
                      "ln_f": ln("norm"), "layers": []}
    for i in range(cfg.num_layers):
        p = f"layers.{i}"
        layer = {
            "ln1": ln(f"{p}.input_layernorm"),
            "ln2": ln(f"{p}.post_attention_layernorm"),
            "attn": {"q": kernel(f"{p}.self_attn.q_proj"),
                     "kv_a": kernel(f"{p}.self_attn.kv_a_proj_with_mqa"),
                     "kv_a_ln": ln(f"{p}.self_attn.kv_a_layernorm"),
                     "kv_b": kernel(f"{p}.self_attn.kv_b_proj"),
                     "o": kernel(f"{p}.self_attn.o_proj")}}
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = mlp(f"{p}.mlp")
        else:
            moe = {"router": {
                       **kernel(f"{p}.mlp.gate"),
                       "bias": take(f"{p}.mlp.gate.e_score_correction_bias"
                                    ).astype(np.float32)},
                   "experts": {k: stacked(f"{p}.mlp", k)
                               for k in ("gate", "up", "down")}}
            if cfg.n_shared_experts:
                moe["shared"] = mlp(f"{p}.mlp.shared_experts")
            layer["moe"] = moe
        params["layers"].append(layer)
    return params


def convert_sala(state_dict: Dict[str, Any], cfg) -> Params:
    """Map a `minicpm_sala` state_dict to the sala.py pytree: the HF MiniCPM
    layout, with each mixer's gate and norms as `self_attn.o_gate`,
    `q_norm`, `k_norm` and (linear layers) `o_norm` (assumed names: no
    published checkpoint is in the repository to read them from; the seeded
    one, benchmark/refs/minicpm_sala.py, uses them). Torch Linear [out, in]
    -> [in, out], leaf by leaf in the checkpoint's own dtype as
    `convert_mla_moe` does; rank-1 leaves go to float32. `lm_head` is not
    read: the encoder role pools hidden states."""
    from symbiont_tpu.models.sala import LINEAR

    sd = {k.removeprefix("model."): v for k, v in state_dict.items()}

    def take(name: str) -> np.ndarray:
        if name not in sd:
            raise KeyError(f"checkpoint missing tensor {name!r}; have e.g. "
                           f"{sorted(sd)[:5]}")
        return _to_numpy(sd.pop(name))

    def kernel(name: str) -> dict:
        return {"kernel": _transposed([take(f"{name}.weight")])[0]}

    def ln(name: str) -> dict:
        return {"scale": take(f"{name}.weight").astype(np.float32)}

    params: Params = {"wte": take("embed_tokens.weight"),
                      "ln_f": ln("norm"), "layers": []}
    for i, kind in enumerate(cfg.mixer_types):
        p, a = f"layers.{i}", f"layers.{i}.self_attn"
        mixer = {**{k: kernel(f"{a}.{k}_proj") for k in "qkvo"},
                 "gate": kernel(f"{a}.o_gate"),
                 "q_norm": ln(f"{a}.q_norm"), "k_norm": ln(f"{a}.k_norm")}
        if kind == LINEAR:
            mixer["o_norm"] = ln(f"{a}.o_norm")
        params["layers"].append({
            "ln1": ln(f"{p}.input_layernorm"),
            "ln2": ln(f"{p}.post_attention_layernorm"), "mixer": mixer,
            "mlp": {k: kernel(f"{p}.mlp.{k}_proj")
                    for k in ("gate", "up", "down")}})
    return params


def convert_ouro(state_dict: Dict[str, Any], cfg) -> Params:
    """Map an `ouro` state_dict (HF names: the Llama layout plus each
    block's second norms `input_layernorm_2` / `post_attention_layernorm_2`
    and `model.early_exit_gate`) to the ouro.py pytree, whose layers are
    STACKED leaf by leaf on a leading axis for the scan over them. Torch
    Linear [out, in] -> [layers, in, out], read leaf by leaf in the
    checkpoint's own dtype as `convert_mla_moe` does; norm scales and the
    gate's bias go to float32. `lm_head` is not read: the encoder role pools
    hidden states."""
    sd = {k.removeprefix("model."): v for k, v in state_dict.items()}
    n = cfg.num_layers

    def take(name: str) -> np.ndarray:
        if name not in sd:
            raise KeyError(f"checkpoint missing tensor {name!r}; have e.g. "
                           f"{sorted(sd)[:5]}")
        return _to_numpy(sd.pop(name))

    def stacked(name: str) -> dict:
        return {"kernel": _transposed(
            [take(f"layers.{i}.{name}.weight") for i in range(n)])}

    def ln(name: str) -> dict:
        return {"scale": np.stack(
            [take(f"layers.{i}.{name}.weight").astype(np.float32)
             for i in range(n)])}

    return {
        "wte": take("embed_tokens.weight"),
        "ln_f": {"scale": take("norm.weight").astype(np.float32)},
        "gate": {"kernel": _transposed([take("early_exit_gate.weight")])[0],
                 "bias": take("early_exit_gate.bias").astype(np.float32)},
        "layers": {
            "ln1": ln("input_layernorm"),
            "ln1_post": ln("input_layernorm_2"),
            "ln2": ln("post_attention_layernorm"),
            "ln2_post": ln("post_attention_layernorm_2"),
            "attn": {k: stacked(f"self_attn.{k}_proj") for k in "qkvo"},
            "mlp": {k: stacked(f"mlp.{k}_proj")
                    for k in ("gate", "up", "down")}},
    }


def convert_ling(state_dict: Dict[str, Any], cfg) -> Params:
    """Map a `bailing_hybrid` state_dict to the ling.py pytree. Names (no
    published checkpoint is in the repository to read them from; the seeded
    one, benchmark/refs/ling_flash.py, uses them): `word_embeddings`,
    `norm`; per layer `input_layernorm`, `post_attention_layernorm`;
    `attention.` + KDA's `{q,k,v}_proj`, `{q,k,v}_conv1d` [C, 1, K],
    `f_proj`, `dt_bias`, `A_log`, `b_proj`, `g_proj`, `o_norm`, `o_proj`
    or MLA's `q_proj`, `kv_a_proj_with_mqa`, `kv_a_layernorm`, `kv_b_proj`,
    `q_norm`, `k_norm`, `g_proj`, `o_proj`; `mlp.` + a dense SwiGLU or
    `gate` (+ `expert_bias`), `experts.<e>` for the held e, and
    `shared_experts`. Torch Linear [out, in] -> [in, out], leaf by leaf in
    the checkpoint's own dtype as `convert_mla_moe` does, and each tensor
    leaves `state_dict` as it is read (a 10 GB checkpoint is never held
    twice); rank-1 leaves go to float32. `lm_head` and an MTP layer are not
    read: the encoder role pools hidden states."""
    sd = state_dict
    for k in list(sd):
        for prefix in _LM_PREFIXES:
            if k.startswith(prefix):
                sd[k[len(prefix):]] = sd.pop(k)
                break

    def take(name: str) -> np.ndarray:
        if name not in sd:
            raise KeyError(f"checkpoint missing tensor {name!r}; have e.g. "
                           f"{sorted(sd)[:5]}")
        return _to_numpy(sd.pop(name))

    def kernel(name: str) -> dict:
        return {"kernel": _transposed([take(f"{name}.weight")])[0]}

    def ln(name: str) -> dict:
        return {"scale": take(f"{name}.weight").astype(np.float32)}

    def mlp(prefix: str) -> dict:
        return {k: kernel(f"{prefix}.{k}_proj") for k in ("gate", "up", "down")}

    def stacked(prefix: str, proj: str) -> dict:
        return {"kernel": _transposed(
            [take(f"{prefix}.experts.{e}.{proj}_proj.weight")
             for e in range(cfg.held)])}

    params: Params = {"wte": take("word_embeddings.weight"),
                      "ln_f": ln("norm"), "layers": []}
    for i in range(cfg.num_layers):
        p, a = f"layers.{i}", f"layers.{i}.attention"
        layer = {"ln1": ln(f"{p}.input_layernorm"),
                 "ln2": ln(f"{p}.post_attention_layernorm")}
        if cfg.is_mla(i):
            layer["attn"] = {
                "q": kernel(f"{a}.q_proj"),
                "kv_a": kernel(f"{a}.kv_a_proj_with_mqa"),
                "kv_a_ln": ln(f"{a}.kv_a_layernorm"),
                "kv_b": kernel(f"{a}.kv_b_proj"), "o": kernel(f"{a}.o_proj"),
                "q_norm": ln(f"{a}.q_norm"), "k_norm": ln(f"{a}.k_norm"),
                "gate": kernel(f"{a}.g_proj")}
        else:
            layer["kda"] = {
                **{n: kernel(f"{a}.{n}_proj") for n in "qkv"},
                "conv": {n: np.ascontiguousarray(
                    take(f"{a}.{n}_conv1d.weight")[:, 0, :].T) for n in "qkv"},
                "decay": {**kernel(f"{a}.f_proj"),
                          "bias": take(f"{a}.dt_bias").astype(np.float32),
                          "a_log": take(f"{a}.A_log").astype(np.float32)},
                "beta": kernel(f"{a}.b_proj"), "gate": kernel(f"{a}.g_proj"),
                "o_norm": ln(f"{a}.o_norm"), "o": kernel(f"{a}.o_proj")}
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = mlp(f"{p}.mlp")
        else:
            moe = {"router": {
                       **kernel(f"{p}.mlp.gate"),
                       "bias": take(f"{p}.mlp.gate.expert_bias"
                                    ).astype(np.float32)},
                   "experts": {k: stacked(f"{p}.mlp", k)
                               for k in ("gate", "up", "down")}}
            if cfg.num_shared_experts:
                moe["shared"] = mlp(f"{p}.mlp.shared_experts")
            layer["moe"] = moe
        params["layers"].append(layer)
    return params


def export_hf_bert(params: Params, cfg: BertConfig, out_dir: str | Path,
                   tokenizer_file: str | Path | None = None) -> Path:
    """Inverse of convert_bert: write a hub-format model dir
    (config.json + model.safetensors, torch tensor-name layout) from a bert.py
    pytree — so checkpoints trained IN this framework are loadable by both the
    engine's standard model_dir path and by `transformers` itself. Kernels go
    back to torch Linear's [out, in]; tensor names match what BertModel's own
    save_pretrained produces (no "bert." prefix — convert_bert strips either
    form)."""
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    sd: Dict[str, np.ndarray] = {}

    def put_linear(prefix: str, p: dict) -> None:
        sd[f"{prefix}.weight"] = np.ascontiguousarray(
            np.asarray(p["kernel"], np.float32).T)
        sd[f"{prefix}.bias"] = np.asarray(p["bias"], np.float32)

    def put_ln(prefix: str, p: dict) -> None:
        sd[f"{prefix}.weight"] = np.asarray(p["scale"], np.float32)
        sd[f"{prefix}.bias"] = np.asarray(p["bias"], np.float32)

    emb = params["embeddings"]
    sd["embeddings.word_embeddings.weight"] = np.asarray(
        emb["word_embeddings"], np.float32)
    sd["embeddings.position_embeddings.weight"] = np.asarray(
        emb["position_embeddings"], np.float32)
    sd["embeddings.token_type_embeddings.weight"] = np.asarray(
        emb["token_type_embeddings"], np.float32)
    put_ln("embeddings.LayerNorm", emb["ln"])
    for i, layer in enumerate(params["layers"]):
        p = f"encoder.layer.{i}"
        put_linear(f"{p}.attention.self.query", layer["attention"]["query"])
        put_linear(f"{p}.attention.self.key", layer["attention"]["key"])
        put_linear(f"{p}.attention.self.value", layer["attention"]["value"])
        put_linear(f"{p}.attention.output.dense", layer["attention"]["out"])
        put_ln(f"{p}.attention.output.LayerNorm", layer["attention"]["ln"])
        put_linear(f"{p}.intermediate.dense", layer["mlp"]["in"])
        put_linear(f"{p}.output.dense", layer["mlp"]["out"])
        put_ln(f"{p}.output.LayerNorm", layer["mlp"]["ln"])
    if "pooler" in params:
        put_linear("pooler.dense", params["pooler"])
    if "classifier" in params:
        put_linear("classifier", params["classifier"])

    from safetensors.numpy import save_file

    # metadata format=pt: transformers refuses safetensors without it
    save_file(sd, str(out_dir / "model.safetensors"), metadata={"format": "pt"})
    # model_type must invert BertConfig.from_hf exactly: an XLM-RoBERTa-family
    # pytree (position_offset = pad_token_id + 1, e.g. the default
    # mpnet-multilingual model) written back as model_type='bert'/pad=0 would
    # reload with offset-0 position ids — silently wrong embeddings both here
    # and in transformers. from_hf derives offset from pad_token_id, so
    # pad_token_id = position_offset - 1 round-trips it.
    if cfg.position_offset:
        model_type, architectures = "xlm-roberta", ["XLMRobertaModel"]
        pad_token_id = cfg.position_offset - 1
    else:
        model_type, architectures = "bert", ["BertModel"]
        pad_token_id = 0
    hf_cfg = {
        "model_type": model_type,
        "architectures": architectures,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "intermediate_size": cfg.intermediate_size,
        "max_position_embeddings": cfg.max_position_embeddings,
        "type_vocab_size": cfg.type_vocab_size,
        "layer_norm_eps": cfg.layer_norm_eps,
        "hidden_act": cfg.hidden_act,
        "pad_token_id": pad_token_id,
    }
    (out_dir / "config.json").write_text(json.dumps(hf_cfg, indent=2))
    if tokenizer_file is not None:
        import shutil

        shutil.copyfile(tokenizer_file, out_dir / "tokenizer.json")
    return out_dir


def load_gpt_model(model_dir: str | Path):
    """One-call load: (params, GPTConfig) from a local HF model dir."""
    from symbiont_tpu.models.gpt import GPTConfig

    hf_cfg = load_hf_config(model_dir)
    cfg = GPTConfig.from_hf(hf_cfg)
    params = convert_gpt(load_state_dict(model_dir), cfg)
    return params, cfg


def load_mla_moe_model(model_dir: str | Path):
    """One-call load: (params, MlaMoeConfig) from a local HF model dir."""
    from symbiont_tpu.models.mla_moe import MlaMoeConfig

    cfg = MlaMoeConfig.from_hf(load_hf_config(model_dir))
    return convert_mla_moe(load_state_dict(model_dir), cfg), cfg


def load_sala_model(model_dir: str | Path):
    """One-call load: (params, SalaConfig) from a local HF model dir."""
    from symbiont_tpu.models.sala import SalaConfig

    cfg = SalaConfig.from_hf(load_hf_config(model_dir))
    return convert_sala(load_state_dict(model_dir), cfg), cfg


def load_ouro_model(model_dir: str | Path):
    """One-call load: (params, OuroConfig) from a local HF model dir."""
    from symbiont_tpu.models.ouro import OuroConfig

    cfg = OuroConfig.from_hf(load_hf_config(model_dir))
    return convert_ouro(load_state_dict(model_dir), cfg), cfg


def convert_mimo(state_dict: Dict[str, Any], cfg) -> Params:
    """Map a `mimo_v2_flash` state_dict to the mimo.py pytree. Names (no
    published checkpoint is in the repository to read them from; the seeded
    one, benchmark/refs/mimo_v2_flash.py, uses them): `embed_tokens`,
    `norm`; per layer `input_layernorm`, `post_attention_layernorm`;
    `self_attn.` + `{q,k,v,o}_proj` and, in a layer with a sink,
    `attention_sink_bias` [heads]; `mlp.` + a dense SwiGLU or `gate` (+
    `e_score_correction_bias`) and `experts.<e>` for the held e. Torch
    Linear [out, in] -> [in, out], leaf by leaf in the checkpoint's own
    dtype, each tensor leaving `state_dict` as it is read; W_q's and W_k's
    columns go to the lanes the attention kernel reads (`mimo.lane_of`,
    zeros between), W_v's columns and W_o's rows to whole 128-lane value
    heads; rank-1 leaves go to float32. `lm_head` and the MTP layers are
    not read: the encoder role pools hidden states."""
    from symbiont_tpu.models.mimo import lane_of, to_lanes

    sd = state_dict
    for k in list(sd):
        for prefix in _LM_PREFIXES:
            if k.startswith(prefix):
                sd[k[len(prefix):]] = sd.pop(k)
                break

    def take(name: str) -> np.ndarray:
        if name not in sd:
            raise KeyError(f"checkpoint missing tensor {name!r}; have e.g. "
                           f"{sorted(sd)[:5]}")
        return _to_numpy(sd.pop(name))

    def kernel(name: str) -> dict:
        return {"kernel": _transposed([take(f"{name}.weight")])[0]}

    def ln(name: str) -> dict:
        return {"scale": take(f"{name}.weight").astype(np.float32)}

    def mlp(prefix: str) -> dict:
        return {k: kernel(f"{prefix}.{k}_proj") for k in ("gate", "up", "down")}

    def stacked(prefix: str, proj: str) -> dict:
        return {"kernel": _transposed(
            [take(f"{prefix}.experts.{e}.{proj}_proj.weight")
             for e in range(cfg.held)])}

    nh, D, Dv = cfg.num_heads, cfg.head_dim, cfg.v_head_dim
    where, v_where = lane_of(D, cfg.rotary_dim), np.arange(Dv)

    def heads(name: str, n: int, width: int, lanes: int, at) -> dict:
        return {"kernel": to_lanes(kernel(name)["kernel"], n, width, lanes,
                                   at)}

    params: Params = {"wte": take("embed_tokens.weight"), "ln_f": ln("norm"),
                      "layers": []}
    for i in range(cfg.num_layers):
        p, a = f"layers.{i}", f"layers.{i}.self_attn"
        nkv = cfg.kv_heads(i)
        o = kernel(f"{a}.o_proj")["kernel"]  # [heads * Dv, H]
        attn = {"q": heads(f"{a}.q_proj", nh, D, cfg.lanes, where),
                "k": heads(f"{a}.k_proj", nkv, D, cfg.lanes, where),
                "v": heads(f"{a}.v_proj", nkv, Dv, cfg.v_lanes, v_where),
                "o": {"kernel": np.ascontiguousarray(to_lanes(
                    np.ascontiguousarray(o.T), nh, Dv, cfg.v_lanes,
                    v_where).T)}}
        if cfg.sink(i):
            attn["sink"] = take(f"{a}.attention_sink_bias").astype(np.float32)
        layer = {"ln1": ln(f"{p}.input_layernorm"),
                 "ln2": ln(f"{p}.post_attention_layernorm"), "attn": attn}
        if cfg.is_moe(i):
            layer["moe"] = {
                "router": {**kernel(f"{p}.mlp.gate"),
                           "bias": take(f"{p}.mlp.gate.e_score_correction_bias"
                                        ).astype(np.float32)},
                "experts": {k: stacked(f"{p}.mlp", k)
                            for k in ("gate", "up", "down")}}
        else:
            layer["mlp"] = mlp(f"{p}.mlp")
        params["layers"].append(layer)
    return params


def load_mimo_model(model_dir: str | Path):
    """One-call load: (params, MimoConfig) from a local HF model dir."""
    from symbiont_tpu.models.mimo import MimoConfig

    cfg = MimoConfig.from_hf(load_hf_config(model_dir))
    return convert_mimo(load_state_dict(model_dir), cfg), cfg


def load_ling_model(model_dir: str | Path):
    """One-call load: (params, LingConfig) from a local HF model dir."""
    from symbiont_tpu.models.ling import LingConfig

    cfg = LingConfig.from_hf(load_hf_config(model_dir))
    return convert_ling(load_state_dict(model_dir), cfg), cfg


def load_bert_model(model_dir: str | Path, with_pooler: bool = False):
    """One-call load: (params, BertConfig) from a local HF model dir."""
    hf_cfg = load_hf_config(model_dir)
    cfg = BertConfig.from_hf(hf_cfg)
    params = convert_bert(load_state_dict(model_dir), cfg, with_pooler=with_pooler)
    return params, cfg


def main(argv=None) -> None:
    """CLI: convert a local HF checkpoint and cache the JAX pytree.

        python -m symbiont_tpu.models.convert <hf_model_dir> [--out DIR]
               [--kind auto|bert|gpt] [--pooler]

    With --out, the converted params land in a mmap-friendly checkpoint dir
    (symbiont_tpu.train.checkpoint format) so engine restarts skip
    reconversion (SURVEY.md §5.4 plan — the reference re-downloads and
    re-converts on every boot, embedding_generator.rs:25-58). Without --out,
    it's a dry run that validates the layout and prints the geometry."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m symbiont_tpu.models.convert", description=main.__doc__)
    ap.add_argument("model_dir", help="local HF model dir (safetensors/.bin + config.json)")
    ap.add_argument("--out", help="checkpoint dir to write converted params to")
    ap.add_argument("--kind", choices=["auto", "bert", "gpt"], default="auto")
    ap.add_argument("--pooler", action="store_true",
                    help="include pooler+classifier head (cross-encoders)")
    args = ap.parse_args(argv)

    hf_cfg = load_hf_config(args.model_dir)
    kind = args.kind
    if kind == "auto":
        kind = "gpt" if hf_cfg.get("model_type") in ("gpt2", "llama", "mistral") else "bert"
    if kind == "gpt":
        params, cfg = load_gpt_model(args.model_dir)
    else:
        params, cfg = load_bert_model(args.model_dir, with_pooler=args.pooler)
    import jax

    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"{kind}: {type(cfg).__name__} hidden={cfg.hidden_size} "
          f"layers={cfg.num_layers} heads={cfg.num_heads} — "
          f"{n_params / 1e6:.1f}M params converted OK")
    if args.out:
        import dataclasses

        from symbiont_tpu.train.checkpoint import save_params

        save_params(args.out, params,
                    meta={"kind": kind, "config": dataclasses.asdict(cfg),
                          "source": str(args.model_dir)})
        print(f"saved checkpoint to {args.out}")


if __name__ == "__main__":
    main()
