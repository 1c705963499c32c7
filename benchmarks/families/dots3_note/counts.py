"""Operations and bytes the `dots3_note` family's algorithms need, from the
configuration and the shapes alone: MODEL operations of the share this chip
holds, no padding, no recompute, whatever form the program computes them in.

A token's forward through one layer: the projections of its latent attention
(each token's keys and values up-projected once), the attention products over
the keys it attends (min(context, index_topk) on a full layer, min(context,
window) on a window layer), on a full layer the indexer's projections and
its scores over ALL earlier keys, the head-wise gate, and the feed-forward:
dense, or the router over the published experts, the expected share of its
`num_experts_per_tok` routed experts that is held here
(n_routed_experts / published of them) and the shared expert. The head
counts where a token is sampled. This PR brings no kernel of its own: both
forms of the attention and the expert walk are XLA's, so there is no
kernel cost function here."""


def _dims(cfg, full):
    pre = "" if full else "swa_"
    return {k: cfg[pre + name] for k, name in (
        ("heads", "num_attention_heads"), ("q_rank", "q_lora_rank"),
        ("kv_rank", "kv_lora_rank"), ("nope", "qk_nope_head_dim"),
        ("rope", "qk_rope_head_dim"), ("v", "v_head_dim"))}


def _published_experts(cfg):
    return cfg.get("published_n_routed_experts",
                   cfg["n_routed_experts"] * cfg.get("ep_size", 1))


def _layers(cfg):
    """(full attention?, dense feed-forward?) a layer."""
    return [(cfg["layer_types"][i] == "full_attention",
             i < cfg["first_k_dense_replace"])
            for i in range(cfg["num_hidden_layers"])]


def projection_flops_per_token(cfg, full):
    """The attention's linear products for one token, the gate and (full
    layers) the indexer's three projections among them."""
    h, d = cfg["hidden_size"], _dims(cfg, full)
    heads = d["heads"]
    params = (h * d["q_rank"] + d["q_rank"] * heads * (d["nope"] + d["rope"])
              + h * (d["kv_rank"] + d["rope"])
              + d["kv_rank"] * heads * (d["nope"] + d["v"])
              + heads * d["v"] * h + h * heads)
    if full:
        params += (d["q_rank"] * cfg["index_n_heads"] * cfg["index_head_dim"]
                   + h * cfg["index_head_dim"] + h * cfg["index_n_heads"])
    return 2 * params


def attended_keys(cfg, full, context):
    return min(context, cfg["index_topk"] if full
               else cfg["sliding_window_size"])


def attention_flops_per_token(cfg, full, context):
    """QK^T and PV over the keys one query attends, and on a full layer the
    indexer's scores (products and their weighted sum) over all `context`."""
    d = _dims(cfg, full)
    flops = 2 * d["heads"] * (d["nope"] + d["rope"] + d["v"]) * attended_keys(
        cfg, full, context)
    if full:
        flops += 2 * cfg["index_n_heads"] * (cfg["index_head_dim"] + 1) \
            * context
    return flops


def feed_forward_flops_per_token(cfg, dense):
    h = cfg["hidden_size"]
    if dense:
        return 2 * 3 * h * cfg["intermediate_size"]
    published = _published_experts(cfg)
    expert = 2 * 3 * h * cfg["moe_intermediate_size"]
    held_share = cfg["n_routed_experts"] / published
    return (2 * h * published
            + expert * (cfg["num_experts_per_tok"] * held_share
                        + cfg["n_shared_experts"]))


def lm_head_flops_per_token(cfg):
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def _token(cfg, context, sampled):
    """(all operations, those of them that are attention's) of one token."""
    total = attention = 0
    for full, dense in _layers(cfg):
        a = attention_flops_per_token(cfg, full, context)
        attention += a
        total += (a + projection_flops_per_token(cfg, full)
                  + feed_forward_flops_per_token(cfg, dense))
    return total + (lm_head_flops_per_token(cfg) if sampled else 0), attention


def _sum_min(n, cap):
    """sum over c = 1..n of min(c, cap)."""
    m = min(n, cap)
    return m * (m + 1) // 2 + (n - m) * cap


def prompt_work(cfg, plen):
    """What serving a prompt of `plen` tokens takes: all operations of its
    forward (one sample at its end), those of them that are attention's,
    and the rows of the full layers' pool it must read (each at least
    once)."""
    total = attention = 0
    for full, dense in _layers(cfg):
        d = _dims(cfg, full)
        cap = cfg["index_topk"] if full else cfg["sliding_window_size"]
        a = 2 * d["heads"] * (d["nope"] + d["rope"] + d["v"]) * _sum_min(
            plen, cap)
        if full:
            a += 2 * cfg["index_n_heads"] * (cfg["index_head_dim"] + 1) \
                * (plen * (plen + 1) // 2)
        attention += a
        total += a + plen * (projection_flops_per_token(cfg, full)
                             + feed_forward_flops_per_token(cfg, dense))
    return {"model_flops": total + lm_head_flops_per_token(cfg),
            "attention_flops": attention, "kv_rows": plen}


def token_work(cfg, context):
    """The same for one output token that attends from `context` keys."""
    total, attention = _token(cfg, context, sampled=True)
    return {"model_flops": total, "attention_flops": attention,
            "kv_rows": attended_keys(cfg, True, context)}


def kv_bytes_per_row(cfg, dtype_bytes=2):
    """What one token leaves over all layers: on a full layer the latent
    row and the indexer's key, on a window layer the latent row."""
    numbers = 0
    for full, _ in _layers(cfg):
        d = _dims(cfg, full)
        numbers += d["kv_rank"] + d["rope"] + (cfg["index_head_dim"]
                                               if full else 0)
    return numbers * dtype_bytes


def train_flops_per_token(cfg, seq):
    """Forward plus backward (2x the forward) of one token of a packed
    causal sequence of `seq` tokens, at the mean context. No cell trains
    this configuration; the family contract asks for the count."""
    total, _ = _token(cfg, (seq + 1) // 2, sampled=True)
    return 3 * total
