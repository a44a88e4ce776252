"""Plain float32 reference for MiMo-V2-Flash's language tower
(`mimo_v2_flash`: sliding-window GQA with a learned sink beside full GQA,
routed experts) run as a passage encoder.

Follows the published configuration (`XiaomiMiMo/MiMo-V2-Flash`
`config.json`); the configuration's `assumed` lists each law read where it
is silent:

    block:  h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h));  final RMSNorm
            (RMSNorm eps layernorm_epsilon)
    Attn, layer i (hybrid_layer_pattern[i]: 0 full, 1 window):
        q = W_q x: heads x head_dim;  k = W_k x: KV x head_dim;
        v = W_v x: KV x v_head_dim (KV = num_key_value_heads in full layers,
        swa_num_key_value_heads in window layers); no biases
        RoPE on the first int(partial_rotary_factor x head_dim) dims of each
        q and k head, dim j with dim j + rot/2, theta^(-2j/rot), theta =
        rope_theta (full) or swa_rope_theta (window)
        s_ij = q_i.k_j / sqrt(head_dim), query head h reading KV head
        h // (heads / KV); keys j <= i of the passage (full) or
        i - sliding_window < j <= i (window)
        window: p_ij = e^s_ij / (e^sink_h + sum_j e^s_ij); full: softmax
        o_i = W_o (attention_value_scale * sum_j p_ij v_j)
    FFN: SwiGLU_dense where moe_layer_freq[i] is 0, else
        s = sigmoid(W_r x);  the best k experts by s + bias;
        weights s / sum(chosen s) * (routed_scaling_factor or 1)
        y = sum over the chosen experts HELD here of w_e SwiGLU_e(x)

Straightforward `jax.numpy`, float32 under matmul precision "highest":
attention over explicit masks, a block of queries at a time (a window
layer's block against the keys from its first query's window start to its
last query; a full layer's against every key of the passage), the sink a
column of the softmax that is dropped after it; each held expert over the
tokens that chose it (gathered, up to 16 experts batched, summed back by a
scatter); no kernel, no lane layout, no packing: ONE passage a call,
computed in `padded(n)` rows (its tail, which causal attention keeps from
every real token, chooses no expert and is not pooled). The
forward walks the stack layer by layer over all the passages checked, with
ONE layer's float32 weights drawn from the seed and on the device at a time.
A passage's row, once computed, is kept under the benchmark's cache by this
file's text, the model, the weights' seed and the passage's ids, and read
back by a later run that checks it again.

Departures, each noted: the encoder head (the model publishes none: final
norm, mean over the passage's tokens); the output head and the MTP layers
are not instantiated; the experts another chip would hold give nothing (the
configuration's deployment: 16 chips share a layer); the hash tokenizer
(refs/xlmr.py re-implements it; imported from there); weights drawn from
`weights_seed` where the model block has one.

Imports nothing of the program.
"""

from __future__ import annotations

import hashlib
import json
import shutil
import struct
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from refs import common
from refs.xlmr import token_count, tokenize  # noqa: F401  (the hash tokenizer)

ARCH = "mimo_v2_flash"
GAP = 1e-3  # a k-th / (k+1)-th router score gap under this is "near"
QUERIES = {True: 512, False: 128}  # queries of a window / full layer at a time
EXPERT_GROUP = 16  # held experts gathered and computed together, at most
GROUP_ROWS = 65536  # gathered rows of a group, at most
STEP = 8192  # a passage is padded at its end to a multiple of this
KEPT = Path(__file__).resolve().parents[1] / ".cache" / "refs" / ARCH
# seeded laws (the configuration's `assumed.weights`): kind -> (mean, std)
LAWS = {"w": (0.0, 0.02), "b": (0.0, 0.02), "ln_scale": (1.0, 0.1),
        "sink": (3.0, 0.5)}


def is_window(m: dict, i: int) -> bool:
    return bool(m["hybrid_layer_pattern"][i])


def is_moe(m: dict, i: int) -> bool:
    return bool(m["moe_layer_freq"][i])


def held(m: dict) -> int:
    return m.get("experts_held") or m["n_routed_experts"]


def kv_heads(m: dict, i: int) -> int:
    return (m["swa_num_key_value_heads"] if is_window(m, i)
            else m["num_key_value_heads"])


def has_sink(m: dict, i: int) -> bool:
    return bool(m["add_swa_attention_sink_bias"] if is_window(m, i)
                else m["add_full_attention_sink_bias"])


def _mlp_specs(prefix: str, H: int, width: int) -> list:
    # torch Linear layout: [out, in]
    return [(f"{prefix}.gate_proj.weight", (width, H), "w"),
            (f"{prefix}.up_proj.weight", (width, H), "w"),
            (f"{prefix}.down_proj.weight", (H, width), "w")]


def layer_specs(m: dict, i: int) -> list:
    """Tensor names (assumed: no checkpoint is in the repository to read
    them from; models/convert.py `convert_mimo` reads these)."""
    H, nh, D, Dv = (m["hidden_size"], m["num_attention_heads"],
                    m["head_dim"], m["v_head_dim"])
    nkv = kv_heads(m, i)
    p, a = f"model.layers.{i}", f"model.layers.{i}.self_attn"
    specs = [(f"{p}.input_layernorm.weight", (H,), "ln_scale"),
             (f"{p}.post_attention_layernorm.weight", (H,), "ln_scale"),
             (f"{a}.q_proj.weight", (nh * D, H), "w"),
             (f"{a}.k_proj.weight", (nkv * D, H), "w"),
             (f"{a}.v_proj.weight", (nkv * Dv, H), "w"),
             (f"{a}.o_proj.weight", (H, nh * Dv), "w")]
    if has_sink(m, i):
        specs.append((f"{a}.attention_sink_bias", (nh,), "sink"))
    if not is_moe(m, i):
        return specs + _mlp_specs(f"{p}.mlp", H, m["intermediate_size"])
    E, I = m["n_routed_experts"], m["moe_intermediate_size"]
    specs += [(f"{p}.mlp.gate.weight", (E, H), "w"),
              (f"{p}.mlp.gate.e_score_correction_bias", (E,), "b")]
    for e in range(held(m)):
        specs += _mlp_specs(f"{p}.mlp.experts.{e}", H, I)
    return specs


def tensor_specs(m: dict) -> list:
    specs = [("model.embed_tokens.weight",
              (m["vocab_size"], m["hidden_size"]), "w"),
             ("model.norm.weight", (m["hidden_size"],), "ln_scale")]
    for i in range(m["num_hidden_layers"]):
        specs += layer_specs(m, i)
    return specs


def weights_seed(model: dict, seed: int) -> int:
    return int(model.get("weights_seed", seed))


def draw(specs: list, seed: int, names=None) -> dict:
    """The seeded tensors of `specs` whose name is in `names` (all where
    None): {name: bfloat16 array}. A tensor's values depend on the seed and
    its place in `specs` alone (a generator per tensor, and per block of 4 M
    values inside a large one, spawned off one seed), so a layer drawn alone
    is the layer the checkpoint holds."""
    children = np.random.SeedSequence(int(seed)).spawn(len(specs))
    out, jobs = {}, []
    for (name, shape, kind), ss in zip(specs, children):
        if names is not None and name not in names:
            continue
        out[name] = np.empty(shape, common.BF16)
        rows = shape[0]
        per = max(1, common.BLOCK_ELEMENTS // max(1, int(np.prod(shape[1:]))))
        blocks = [(a, min(rows, a + per)) for a in range(0, rows, per)]
        for (a, b), child in zip(blocks, ss.spawn(len(blocks))):
            jobs.append((name, kind, a, b, child))

    def one(job):
        name, kind, a, b, child = job
        mean, std = LAWS[kind]
        x = np.random.default_rng(child).standard_normal(
            out[name][a:b].shape, dtype=np.float32)
        x *= np.float32(std)
        x += np.float32(mean)
        out[name][a:b] = x

    with ThreadPoolExecutor(max_workers=common.WORKERS) as pool:
        list(pool.map(one, jobs))
    return out


def _write_safetensors(specs: list, seed: int, m: dict, path: Path) -> None:
    """The whole checkpoint, bfloat16, one layer drawn and written at a time
    (7.8 GB never sits in memory twice)."""
    header, offset = {}, 0
    for name, shape, _ in specs:
        size = 2 * int(np.prod(shape))
        header[name] = {"dtype": "BF16", "shape": list(shape),
                        "data_offsets": [offset, offset + size]}
        offset += size
    header["__metadata__"] = {"format": "pt"}
    raw = json.dumps(header, separators=(",", ":")).encode()
    raw += b" " * (-len(raw) % 8)
    groups = [[n for n, _, _ in specs[:2]]] + [
        [n for n, _, _ in layer_specs(m, i)]
        for i in range(m["num_hidden_layers"])]
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(raw)))
        f.write(raw)
        for names in groups:
            tensors = draw(specs, seed, set(names))
            for name in names:
                f.write(np.ascontiguousarray(tensors.pop(name)).tobytes())


def write_checkpoint(model: dict, seed: int, out_dir: Path) -> None:
    """`config.json` + `model.safetensors` (bfloat16) in the hub layout the
    program's `model_dir` loader reads. With a `weights_seed` the 7.8 GB
    are written once per checkout under the benchmark's cache and
    hard-linked into `out_dir` (refs/kimi_mla_moe.py says why). No
    `tokenizer.json`: the program falls back to its hash tokenizer."""
    out_dir = Path(out_dir)
    program = Path(__file__).resolve().parents[2] / "symbiont_tpu" / "models"
    if not (program / "mimo.py").is_file():
        # a checkout from before the family cannot load this checkpoint:
        # say so now, not after 7.8 GB of weights are drawn and written
        raise SystemExit(f"{ARCH}: this checkout's program has no "
                         "models/mimo.py; the configuration cannot run")
    shape = {k: v for k, v in model.items() if k != "weights_seed"}
    common.write_hf_config(shape, out_dir)
    wseed = weights_seed(model, seed)
    specs = tensor_specs(model)
    if "weights_seed" not in model:
        _write_safetensors(specs, wseed, model, out_dir / "model.safetensors")
        return
    store = out_dir.parent / f"weights-{wseed}"
    marker = store / "benchmark_weights.json"
    if not (marker.is_file() and json.loads(marker.read_text()) == shape):
        shutil.rmtree(store, ignore_errors=True)
        _write_safetensors(specs, wseed, model, store / "model.safetensors")
        marker.write_text(json.dumps(shape))
    link = out_dir / "model.safetensors"
    link.unlink(missing_ok=True)
    link.hardlink_to(store / "model.safetensors")


# ------------------------------------------------------------- the maths

def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def partial_rope(x, rot: int, theta: float):
    """x [n, heads, d], position = row: the first `rot` dims turn, dim j
    with dim j + rot/2 (rotate_half within them) by position *
    theta^(-2j/rot); the rest pass unturned."""
    import jax.numpy as jnp

    n = x.shape[0]
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang),
                            x[..., rot:]], -1)


def attention(w: dict, x, m: dict, window: bool):
    """x [n, H] normed, one passage -> [n, H]: softmax attention over
    explicit masks, `QUERIES[window]` queries at a time."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    nh, D, Dv = m["num_attention_heads"], m["head_dim"], m["v_head_dim"]
    nkv = m["swa_num_key_value_heads" if window else "num_key_value_heads"]
    rot = int(m["partial_rotary_factor"] * D)
    theta = m["swa_rope_theta" if window else "rope_theta"]
    W = m["sliding_window"]
    q = partial_rope((x @ w["q_proj"].T).reshape(n, nh, D), rot, theta)
    k = partial_rope((x @ w["k_proj"].T).reshape(n, nkv, D), rot, theta)
    v = (x @ w["v_proj"].T).reshape(n, nkv, Dv)
    group = nh // nkv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    Q = min(QUERIES[window], n)
    nq = -(-n // Q)
    qp = jnp.pad(q, ((0, nq * Q - n), (0, 0), (0, 0))).reshape(nq, Q, nh, D)
    sink = w.get("attention_sink_bias")
    if window:  # the keys a block's window reaches: W - 1 before it, then it
        span = Q + W - 1
        kp = jnp.pad(k, ((W - 1, nq * Q - n), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((W - 1, nq * Q - n), (0, 0), (0, 0)))

    def some(xs):
        qb, b = xs  # [Q, nh, D], the block's index
        t = b * Q + jnp.arange(Q)  # the queries' positions
        if window:
            kb = jax.lax.dynamic_slice_in_dim(kp, b * Q, span)
            vb = jax.lax.dynamic_slice_in_dim(vp, b * Q, span)
            j = b * Q - (W - 1) + jnp.arange(span)  # the keys' positions
            keep = (j[None, :] >= 0) & (j[None, :] <= t[:, None]) & (
                j[None, :] > t[:, None] - W)
        else:
            kb, vb, j = k, v, jnp.arange(n)
            keep = j[None, :] <= t[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, kb) / np.sqrt(D)
        s = jnp.where(keep[None], s, -jnp.inf)
        if sink is not None:  # a column that attends to nothing
            s = jnp.concatenate([s, jnp.broadcast_to(
                sink[:, None, None], (nh, Q, 1))], -1)
        p = jax.nn.softmax(s, axis=-1)[..., :kb.shape[0]]
        return jnp.einsum("hqk,khd->qhd", p, vb)

    ctx = jax.lax.map(some, (qp, jnp.arange(nq)))
    ctx = ctx.reshape(nq * Q, nh * Dv)[:n] * m["attention_value_scale"]
    return ctx @ w["o_proj"].T


def router(w: dict, x, m: dict):
    """x [T, H] -> (idx [T, k], weights [T, k], gap [T] = k-th less the
    (k+1)-th of the scores chosen among)."""
    import jax
    import jax.numpy as jnp

    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ w["gate"].T)
    top, idx = jax.lax.top_k(s + w["gate.e_score_correction_bias"], k + 1)
    chosen = jnp.take_along_axis(s, idx[:, :k], axis=-1)
    if m.get("norm_topk_prob", True):
        chosen = chosen / chosen.sum(-1, keepdims=True)
    return (idx[:, :k], chosen * (m.get("routed_scaling_factor") or 1.0),
            top[:, k - 1] - top[:, k])


def experts(w: dict, x, idx, weights, m: dict, cap: int):
    """The held experts' part: each held expert over the (at most `cap`)
    tokens that chose it, times each token's weight for it, summed back
    (`EXPERT_GROUP` experts at a time: gathered, batched, one scatter)."""
    import jax
    import jax.numpy as jnp

    E = held(m)
    # experts a group, fewer where many tokens chose one: a group's gathered
    # rows stay under GROUP_ROWS ([rows, 4096] float32 is 1 GB at 65,536)
    per = np.gcd(E, max(1, min(EXPERT_GROUP, GROUP_ROWS // cap)))

    def group(y, xs):
        gate, up, down, ids = xs  # [G, I, H], [G, I, H], [G, H, I], [G]
        chose = idx[:, :, None] == ids  # [T, k, G]
        hit = chose.any(1)
        col = jnp.where(chose, weights[:, :, None], 0.0).sum(1)  # [T, G]
        rows = jnp.argsort(~hit, axis=0, stable=True)[:cap]  # its tokens first
        scale = jnp.take_along_axis(col, rows, axis=0)  # 0 past them
        xe = x[rows]  # [cap, G, H]
        hidden = (jax.nn.silu(jnp.einsum("ceh,eih->cei", xe, gate))
                  * jnp.einsum("ceh,eih->cei", xe, up))
        ye = jnp.einsum("cei,ehi->ceh", hidden, down) * scale[..., None]
        return y.at[rows.reshape(-1)].add(ye.reshape(-1, x.shape[1])), None

    def grouped(a):
        return a.reshape(E // per, per, *a.shape[1:])

    y, _ = jax.lax.scan(group, jnp.zeros_like(x),
                        (grouped(w["experts.gate_proj"]),
                         grouped(w["experts.up_proj"]),
                         grouped(w["experts.down_proj"]),
                         grouped(jnp.arange(E))))
    return y


def padded(n: int) -> int:
    """The rows a passage of n tokens is computed in: n rounded up to a
    power of two up to STEP, to a multiple of STEP past it (the mix's six
    lengths fall in three shapes, so the float32 programs compile three
    times, not six). Attention is causal, so no real token sees the tail."""
    return 1 << (n - 1).bit_length() if n <= STEP else -(-n // STEP) * STEP


def layer_weights(m: dict, i: int, seed: int) -> dict:
    """Layer i's float32 weights under short names, its held experts
    stacked, drawn from the seed alone."""
    p = f"model.layers.{i}."
    specs = tensor_specs(m)
    names = {n for n, _, _ in layer_specs(m, i)}
    drawn = draw(specs, seed, names)
    out = {}
    for name, _, _ in layer_specs(m, i):
        short = name[len(p):].removesuffix(".weight")
        short = short.removeprefix("self_attn.").removeprefix("mlp.")
        if not short.startswith("experts."):
            out[short] = np.asarray(drawn.pop(name), np.float32)
    if is_moe(m, i):
        for proj in ("gate_proj", "up_proj", "down_proj"):
            out[f"experts.{proj}"] = np.stack([
                np.asarray(drawn.pop(f"{p}mlp.experts.{e}.{proj}.weight"),
                           np.float32) for e in range(held(m))])
    return out


class Reference:
    """`embed(texts)` -> [n, H] float32 mean-pooled passage vectors."""

    def __init__(self, model: dict, seed: int, max_len: int):
        self.m = model
        self.max_len = max_len
        self.seed = weights_seed(model, seed)
        self.gap_share = None  # (token, expert layer) with a gap under GAP

    def forward(self, passages: list) -> list:
        """`passages` = [ids] -> one pooled row each; layer by layer over
        all of them, one layer's weights on the device at a time, the hidden
        states waiting on the host between layers."""
        import jax
        import jax.numpy as jnp

        m = self.m
        eps = m["layernorm_epsilon"]
        specs = tensor_specs(m)
        top = draw(specs, self.seed, {n for n, _, _ in specs[:2]})
        mixers = {win: jax.jit(lambda w, x, win=win: x + attention(
                      w, rms_norm(x, w["input_layernorm"], eps), m, win))
                  for win in (True, False)}
        dense = jax.jit(lambda w, h: h + swiglu(
            rms_norm(h, w["post_attention_layernorm"], eps), w["gate_proj"],
            w["up_proj"], w["down_proj"]))
        route = jax.jit(lambda w, h: router(
            w, rms_norm(h, w["post_attention_layernorm"], eps), m))
        ffn = jax.jit(lambda w, h, idx, weights, cap: h + experts(
            w, rms_norm(h, w["post_attention_layernorm"], eps), idx, weights,
            m, cap), static_argnums=4)
        self.gaps = [(0, 0)] * len(passages)
        t0 = time.monotonic()
        with jax.default_matmul_precision("highest"):
            wte = np.asarray(top["model.embed_tokens.weight"], np.float32)
            lens = [len(ids) for ids in passages]
            xs = [wte[np.pad(np.asarray(ids), (0, padded(n) - n))]
                  for ids, n in zip(passages, lens)]
            del wte
            for i in range(m["num_hidden_layers"]):
                w = jax.device_put(layer_weights(m, i, self.seed))
                for b, x in enumerate(xs):
                    h = mixers[is_window(m, i)](w, x)
                    if not is_moe(m, i):
                        xs[b] = np.asarray(dense(w, h))
                        continue
                    idx, weights, gap = route(w, h)
                    n = lens[b]  # the tail's tokens choose no expert
                    idx = np.where(np.arange(len(x))[:, None] < n,
                                   np.asarray(idx), -1)
                    gap = np.asarray(gap)[:n]
                    near, total = self.gaps[b]
                    self.gaps[b] = (near + int((gap < GAP).sum()),
                                    total + gap.size)
                    taken = np.bincount(idx[:n].ravel(),
                                        minlength=m["n_routed_experts"])
                    # four times an expert's mean share of the tokens, more
                    # where one took more, rounded up to a power of two: a
                    # few shapes a passage length, each compiled once
                    need = max(int(taken[:held(m)].max()), 4 * n
                               * m["num_experts_per_tok"]
                               // m["n_routed_experts"], 1)
                    cap = min(len(x), 1 << (need - 1).bit_length())
                    xs[b] = np.asarray(ffn(w, h, idx, weights, cap))
                del w
                print(f"reference {ARCH}: layer {i} done at "
                      f"{time.monotonic() - t0:.1f} s", file=sys.stderr,
                      flush=True)
            scale = jnp.asarray(np.asarray(top["model.norm.weight"],
                                           np.float32))
            out = [np.asarray(rms_norm(jnp.asarray(x[:n]), scale, eps)
                              .mean(0)) for x, n in zip(xs, lens)]
        return out

    def _kept(self, ids) -> Path:
        """Where passage `ids`'s row is kept: named by this file's text, the
        model, the weights' seed and the ids."""
        h = hashlib.sha256(Path(__file__).read_bytes())
        h.update(json.dumps(self.m, sort_keys=True).encode())
        h.update(str(self.seed).encode())
        h.update(np.asarray(ids, np.int64).tobytes())
        return KEPT / f"{h.hexdigest()[:32]}.npz"

    def embed(self, texts: list, rows_per_call: int = 1) -> np.ndarray:
        """Pooled rows of `texts`. A passage's row and its router-gap counts
        are kept under the benchmark's cache once computed, and read back
        for the same passage under the same weights (a control or a planted
        fault run on the seed of a sound run checks the same passages)."""
        del rows_per_call  # one passage a call: nothing is batched here
        enc = [tokenize(t, self.m["vocab_size"], self.max_len) for t in texts]
        paths = [self._kept(ids) for ids in enc]
        todo = [b for b, path in enumerate(paths) if not path.is_file()]
        rows = self.forward([enc[b] for b in todo]) if todo else []
        for j, (b, row) in enumerate(zip(todo, rows)):
            paths[b].parent.mkdir(parents=True, exist_ok=True)
            near, total = self.gaps[j]
            part = paths[b].with_suffix(".part")
            with open(part, "wb") as f:
                np.savez(f, row=row, near=near, total=total)
            part.replace(paths[b])
        kept = [np.load(path) for path in paths]
        near = sum(int(k["near"]) for k in kept)
        total = sum(int(k["total"]) for k in kept)
        self.gap_share = near / total if total else 0.0
        out = np.stack([k["row"] for k in kept]) if kept else np.zeros(
            (0, self.m["hidden_size"]), np.float32)
        print(f"reference {ARCH}: {len(enc) - len(todo)} of {len(enc)} "
              "passages' rows read back", file=sys.stderr, flush=True)
        print(f"reference {ARCH}: _router_gap_under_{GAP:g}_share = "
              f"{self.gap_share:.6g} (tokens x expert layers whose k-th and "
              "(k+1)-th choice scores lie that close)", file=sys.stderr,
              flush=True)
        return out.astype(np.float32)
