"""The `dots3_note` family's plain half: its leaves, how each is drawn, and
the float32 forward in three pieces (embed, one layer, head). Text path only:
no vision tower, no audio encoder, no multi-token-prediction module.

Straightforward jax.numpy, float32, no kernel, no cache, no batching tricks.
Written from the configuration's keys and the description of the family
(latent attention of two kinds, a learned top-k indexer on the full layers,
a window on the others, a head-wise output gate, sigmoid-routed experts with
a shared one); what the configuration names without spelling its form is
listed under `assumed` in the configuration's file, with the same words.

A layer is one of (`layer_kinds`): `dense_full`, `moe_full`, `dense_window`,
`moe_window`: the feed-forward of the first `first_k_dense_replace` layers is
dense, of the others an expert layer; `layer_types` says which attention.
Pre-norm residual block, RMSNorm. With u = N1(x):

  latent attention (both kinds, each with its own ranks and head sizes)
    c_q = r_q * RMSNorm(u W_qa);  [q_nope_i | q_rope_i] = c_q W_qb (head i)
    [c_kv | k_r] = u W_kva;  c_kv = r_kv * RMSNorm(c_kv);  k_rope = rope(k_r)
    [k_nope_i | v_i] = c_kv W_kvb;   r_q = sqrt(h / q_rank), r_kv likewise
    a = softmax over the allowed keys of (q_nope.k_nope + q_rope.k_rope)
        / sqrt(nope + rope);  o_i = sigmoid(u W_g)_i * sum_s a v
  full layers allow the `index_topk` keys s <= t of largest
    I(t, s) = sum_j w_tj relu(qI_tj . kI_s) / sqrt(index_head_dim * n_j),
    qI = c_q W_Iq, kI = LayerNorm(u W_Ik), w = u W_Iw (rope on the first
    `qk_rope_head_dim` dims of qI and kI); all of them while t < index_topk
  window layers allow t - window < s <= t

  experts: z = sigmoid(y W_r); the `num_experts_per_tok` largest of z + b are
  chosen, weighed by z / sum of the chosen z, times `routed_scaling_factor`;
  plus one shared expert on every token. A chip's SHARE holds experts
  [ep_rank * n, (ep_rank + 1) * n) of the published count, n =
  `n_routed_experts`: the router keeps its published width, and what the
  absent experts would add is left out. Each expert runs on its own tokens
  only: the assignments are sorted by expert and walked in tiles.

It imports nothing of the program and nothing of the harness. Every linear
product goes through the `mm(x, w)` it is handed; attention's and the
indexer's own products are at `highest`. Leaves are named a layer each."""
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 64        # queries a block: 128 heads x 64 x 16,384 scores are 0.5 GB
EXPERT_TILE = 256       # sorted assignments a tile of the expert walk


# ------------------------------------------------------------------ shapes
def attention_dims(cfg, full):
    """The sizes of one kind of attention: the full layers read the plain
    keys, the window layers the `swa_` ones."""
    pre = "" if full else "swa_"
    return {"heads": cfg[pre + "num_attention_heads"],
            "q_rank": cfg[pre + "q_lora_rank"],
            "kv_rank": cfg[pre + "kv_lora_rank"],
            "nope": cfg[pre + "qk_nope_head_dim"],
            "rope": cfg[pre + "qk_rope_head_dim"],
            "v": cfg[pre + "v_head_dim"],
            "theta": cfg[pre + "rope_theta"]}


def published_experts(cfg):
    return cfg.get("published_n_routed_experts",
                   cfg["n_routed_experts"] * cfg.get("ep_size", 1))


def layer_kinds(cfg):
    """One entry a layer; layers of one kind share a compiled program."""
    out = []
    for i in range(cfg["num_hidden_layers"]):
        ffn = "dense" if i < cfg["first_k_dense_replace"] else "moe"
        full = cfg["layer_types"][i] == "full_attention"
        out.append(f"{ffn}_{'full' if full else 'window'}")
    return out


def layer_shapes(cfg, kind):
    h = cfg["hidden_size"]
    ffn, attn = kind.split("_")
    d = attention_dims(cfg, attn == "full")
    heads = d["heads"]
    out = {"norm1": (h,), "q_a": (h, d["q_rank"]), "q_a_norm": (d["q_rank"],),
           "q_b": (d["q_rank"], heads * (d["nope"] + d["rope"])),
           "kv_a": (h, d["kv_rank"] + d["rope"]),
           "kv_a_norm": (d["kv_rank"],),
           "kv_b": (d["kv_rank"], heads * (d["nope"] + d["v"])),
           "o": (heads * d["v"], h), "gate": (h, heads), "norm2": (h,)}
    if attn == "full":
        ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
        out.update({"idx_q": (d["q_rank"], ih * idim), "idx_k": (h, idim),
                    "idx_k_norm_w": (idim,), "idx_k_norm_b": (idim,),
                    "idx_w": (h, ih)})
    if ffn == "dense":
        inner = cfg["intermediate_size"]
        out.update({"mlp_gate_up": (h, 2 * inner), "mlp_down": (inner, h)})
    else:
        inner, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        shared = inner * cfg["n_shared_experts"]
        out.update({"router": (h, published_experts(cfg)),
                    "router_bias": (published_experts(cfg),),
                    "experts_gate_up": (held, h, 2 * inner),
                    "experts_down": (held, inner, h),
                    "shared_gate_up": (h, 2 * shared),
                    "shared_down": (shared, h)})
    return out


GAINS = ("norm1", "norm2", "q_a_norm", "kv_a_norm", "idx_k_norm_w", "norm_f")


def leaves(cfg):
    """{leaf: (shape, mean, spread)}: matrices and embeddings normal(0,
    `initializer_range`), gains 1 + that, the LayerNorm's bias around 0, and
    the router's correction bias normal(0, `router_bias_range`) so that
    choosing by z + b and weighing by z are told apart."""
    h, std = cfg["hidden_size"], cfg["initializer_range"]
    shapes = {"embed": (cfg["vocab_size"], h), "norm_f": (h,),
              "lm_head": (h, cfg["vocab_size"])}
    for i, kind in enumerate(layer_kinds(cfg)):
        shapes.update({f"L{i}.{k}": s
                       for k, s in layer_shapes(cfg, kind).items()})

    def spread(name):
        return (cfg["router_bias_range"] if name.endswith(".router_bias")
                else std)
    return {name: (shape, 1.0 if name.split(".")[-1] in GAINS else 0.0,
                   spread(name)) for name, shape in shapes.items()}


def embed_leaves(cfg):
    return {"embed": "embed"}


def layer_leaves(cfg, index):
    return {k: f"L{index}.{k}"
            for k in layer_shapes(cfg, layer_kinds(cfg)[index])}


def head_leaves(cfg):
    return {"norm_f": "norm_f", "lm_head": "lm_head"}


def parts(name, value):
    """No leaf is split in a comparison: serving compares logits."""
    return [value]


def leaf_names(cfg):
    return [(name, 0, None) for name in sorted(leaves(cfg))]


def leaf_norms(cfg, tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        tree[name].astype(jnp.float32)))) for name, _, _ in leaf_names(cfg)])


# ----------------------------------------------------------------- pieces
def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _rope(t, theta):
    """t: [S, ..., D], position s on the first axis: adjacent dims (2i,
    2i + 1) are one pair, turned by s * theta ** (-2i / D)."""
    seq, dim = t.shape[0], t.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv     # [S, D/2]
    angle = angle.reshape((seq,) + (1,) * (t.ndim - 2) + (dim // 2,))
    even, odd = t[..., 0::2], t[..., 1::2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(t.shape)


def _swiglu(x, gate_up, down, mm):
    gate, up = jnp.split(mm(x, gate_up), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, down)


def index_scores(cfg, p, u, c_q, mm):
    """(qI [S, n_j, D], kI [S, D], w [S, n_j]) of one row, roped."""
    seq = u.shape[0]
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    rope, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    q = mm(c_q, p["idx_q"]).reshape(seq, heads, dim)
    k = _layer_norm(mm(u, p["idx_k"]), p["idx_k_norm_w"], p["idx_k_norm_b"],
                    cfg["index_norm_eps"])
    q = jnp.concatenate([_rope(q[..., :rope], theta), q[..., rope:]], -1)
    k = jnp.concatenate([_rope(k[..., :rope], theta), k[..., rope:]], -1)
    return q, k, mm(u, p["idx_w"])


def allowed_keys(cfg, full, index, first, block, seq):
    """[block, seq] bool: the keys queries first .. first + block - 1 may
    attend. `index` is (qI, kI, w) of the whole row on a full layer."""
    t = first + jnp.arange(block)[:, None]
    s = jnp.arange(seq)[None, :]
    causal = s <= t
    if not full:
        return causal & (s > t - cfg["sliding_window_size"])
    q, k, w = index
    q = jax.lax.dynamic_slice_in_dim(q, first, block)
    w = jax.lax.dynamic_slice_in_dim(w, first, block)
    dots = jnp.einsum("tjd,sd->jts", q, k, precision=HIGHEST)
    score = jnp.einsum("jts,tj->ts", jax.nn.relu(dots), w, precision=HIGHEST)
    score = score / math.sqrt(cfg["index_head_dim"] * cfg["index_n_heads"])
    score = jnp.where(causal, score, -jnp.inf)
    topk = cfg["index_topk"]
    if seq <= topk:
        return causal
    kth = jnp.sort(score, axis=-1)[:, seq - topk][:, None]  # exact, by sort
    return causal & (score >= kth)


def attention(cfg, full, p, u, mm):
    """u: [S, h], one row, normed -> the attention's output [S, h]."""
    seq, h = u.shape
    d = attention_dims(cfg, full)
    heads, nope, rope, vd = d["heads"], d["nope"], d["rope"], d["v"]
    eps = cfg["rms_norm_eps"]
    rescale = cfg["apply_mla_qkv_lora_rescale"]
    c_q = _rms_norm(mm(u, p["q_a"]), p["q_a_norm"], eps)
    kv = mm(u, p["kv_a"])
    c_kv = _rms_norm(kv[:, :d["kv_rank"]], p["kv_a_norm"], eps)
    if rescale:
        c_q = c_q * math.sqrt(h / d["q_rank"])
        c_kv = c_kv * math.sqrt(h / d["kv_rank"])
    q = mm(c_q, p["q_b"]).reshape(seq, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], d["theta"])], -1)
    k_rope = _rope(kv[:, d["kv_rank"]:], d["theta"])            # [S, rope]
    kvb = mm(c_kv, p["kv_b"]).reshape(seq, heads, nope + vd)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_rope[:, None, :], (seq, heads, rope))], -1)
    v = kvb[..., nope:]
    index = index_scores(cfg, p, u, c_q, mm) if full else None
    block = min(QUERY_BLOCK, seq)
    padded = -(-seq // block) * block
    q = jnp.pad(q, [(0, padded - seq), (0, 0), (0, 0)])
    if full:
        index = (jnp.pad(index[0], [(0, padded - seq), (0, 0), (0, 0)]),
                 index[1], jnp.pad(index[2], [(0, padded - seq), (0, 0)]))

    def one_block(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, block)
        scores = jnp.einsum("thd,shd->hts", qb, k, precision=HIGHEST)
        scores = scores / math.sqrt(nope + rope)
        allowed = allowed_keys(cfg, full, index, first, block, seq)
        probs = jax.nn.softmax(jnp.where(allowed[None], scores, -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", probs, v, precision=HIGHEST)
    out = jax.lax.map(one_block, jnp.arange(0, padded, block))
    out = out.reshape(padded, heads, vd)[:seq]
    gate = jax.nn.sigmoid(mm(u, p["gate"]))                      # [S, heads]
    return mm((out * gate[..., None]).reshape(seq, heads * vd), p["o"])


def route(cfg, p, y, mm):
    """(experts chosen [N, k] by their published number, weights [N, k])."""
    z = jax.nn.sigmoid(mm(y, p["router"]))
    _, chosen = jax.lax.top_k(z + p["router_bias"],
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(z, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return chosen, w * cfg["routed_scaling_factor"]


def routed_experts(cfg, p, y, mm):
    """What the experts held here add for y: [N, h]: each on the tokens
    routed to it, the assignments sorted by expert and walked in tiles."""
    n, h = y.shape
    held = cfg["n_routed_experts"]
    chosen, w = route(cfg, p, y, mm)
    local = chosen - cfg.get("ep_rank", 0) * held
    here = (local >= 0) & (local < held)
    expert = jnp.where(here, local, held).reshape(-1)           # held: absent
    order = jnp.argsort(expert, stable=True)
    token = (jnp.arange(n)[:, None] + jnp.zeros_like(chosen)).reshape(-1)
    token, weight = token[order], w.reshape(-1)[order]
    counts = jnp.bincount(expert, length=held + 1)[:held]
    starts = jnp.cumsum(counts) - counts
    tile = min(EXPERT_TILE, expert.shape[0])
    tiles = -(-counts // tile)
    tile_ends = jnp.cumsum(tiles)

    def one_tile(i, acc):
        e = jnp.searchsorted(tile_ends, i, side="right")
        row = starts[e] + (i - (tile_ends[e] - tiles[e])) * tile \
            + jnp.arange(tile)
        real = row < starts[e] + counts[e]
        row = jnp.minimum(row, expert.shape[0] - 1)
        out = _swiglu(y[token[row]], p["experts_gate_up"][e],
                      p["experts_down"][e], mm)
        return acc.at[token[row]].add(
            out * jnp.where(real, weight[row], 0.0)[:, None])
    return jax.lax.fori_loop(0, tile_ends[-1], one_tile,
                             jnp.zeros((n, h), jnp.float32))


def feed_forward(cfg, kind, p, y, mm):
    """y: [N, h] normed -> (routed part, part every chip computes alike)."""
    if kind.startswith("dense"):
        return jnp.zeros_like(y), _swiglu(y, p["mlp_gate_up"], p["mlp_down"],
                                          mm)
    return (routed_experts(cfg, p, y, mm),
            _swiglu(y, p["shared_gate_up"], p["shared_down"], mm))


def embed(cfg, p, ids):
    """ids: [B, S] int -> [B, S, h] float32."""
    return p["embed"][ids]


def layer(cfg, kind, p, x, mm):
    """x: [B, S, h] float32; p: one layer's leaves; rows one at a time."""
    full = kind.endswith("full")
    eps = cfg["rms_norm_eps"]

    def row(x):
        x = x + attention(cfg, full, p, _rms_norm(x, p["norm1"], eps), mm)
        routed, alike = feed_forward(cfg, kind, p,
                                     _rms_norm(x, p["norm2"], eps), mm)
        return x + routed + alike
    return jnp.stack([row(x[b]) for b in range(x.shape[0])])


def stack(cfg, tree, x, mm, remat=False):
    """Every layer in turn over a whole tree."""
    def body(kind, p, x):
        return layer(cfg, kind, p, x, mm)
    if remat:
        body = jax.checkpoint(body, static_argnums=(0,))
    for index, kind in enumerate(layer_kinds(cfg)):
        x = body(kind, {k: tree[name] for k, name in
                        layer_leaves(cfg, index).items()}, x)
    return x


def head(cfg, p, x, mm):
    """x: [..., h] -> logits [..., vocab] float32, over the slice held."""
    return mm(_rms_norm(x, p["norm_f"], cfg["rms_norm_eps"]), p["lm_head"])


def forward_bytes(cfg, seq):
    """Float32 bytes alive at once in one row's forward of `seq` positions,
    roughly: keys and values of all heads, one block of scores and of the
    indexer's products, the expert walk's accumulator, the residual stream."""
    d = attention_dims(cfg, True)
    block = min(QUERY_BLOCK, seq)
    return 4 * seq * (d["heads"] * (2 * d["nope"] + d["rope"] + d["v"])
                      + 2 * block * (d["heads"] + cfg["index_n_heads"])
                      + 8 * cfg["hidden_size"])
