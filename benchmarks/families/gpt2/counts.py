"""Operations and bytes the `gpt2` family's algorithms need, from the
configuration and the shapes alone. No recompute is counted, and no padding:
these are the numerators of `mfu.*` and of the kernels' rooflines.

A configuration is the dict of its file (`n_embd`, `n_layer`, `n_head`,
`vocab_size`, `n_inner`). Every family gives `prompt_work`, `token_work`,
`kv_bytes_per_row` and `train_flops_per_token`; the rest is this family's
own, and `flash_train_cost` is the cost of a kernel only its cells run."""


def _dims(cfg):
    h = cfg["n_embd"]
    return h, cfg["n_layer"], cfg["n_inner"] or 4 * h, cfg["vocab_size"]


def block_matmul_flops_per_token(cfg):
    """Forward matmuls of one block for one token: qkv, out, fc1, down."""
    h, _, inner, _ = _dims(cfg)
    return 2 * (h * 3 * h + h * h + 2 * h * inner)


def attention_flops_per_token(cfg, context):
    """Forward QK^T and PV of one layer for one query token that attends to
    `context` keys (itself included)."""
    return 4 * cfg["n_embd"] * context


def lm_head_flops_per_token(cfg):
    h, _, _, vocab = _dims(cfg)
    return 2 * h * vocab


def train_flops_per_token(cfg, seq):
    """Forward plus backward (2x the forward) of one token of a packed causal
    sequence of `seq` tokens: a token at position p attends to p + 1 keys,
    (seq + 1) / 2 on average."""
    _, layers, _, _ = _dims(cfg)
    mean_context = (seq + 1) / 2
    forward = (layers * (block_matmul_flops_per_token(cfg)
                         + attention_flops_per_token(cfg, mean_context))
               + lm_head_flops_per_token(cfg))
    return 3 * forward


def flash_train_cost(cfg, batch, seq):
    """(flops, bytes) of causal flash attention for ONE layer of one step:
    forward (QK^T, PV) and backward (QK^T again, dP, dV, dK, dQ: five
    products, the count of the algorithm, whatever the kernels' split
    recomputes), each product 2*S*S*D a head and half of it masked; forward
    reads Q, K, V and writes O, backward reads Q, K, V, O, dO and writes dQ,
    dK, dV, all bf16."""
    heads, dim = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    product = 2 * seq * seq * dim / 2        # causal: the lower triangle
    flops = batch * heads * 7 * product
    tensor_bytes = batch * heads * seq * dim * 2
    return flops, 12 * tensor_bytes


def kv_bytes_per_row(cfg, dtype_bytes=2):
    """K and V of one token over all layers."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * dtype_bytes


def serve_token_flops(cfg, context, sampled):
    """One forward pass of one token at `context` keys; the lm head only
    where a token is sampled from it."""
    _, layers, _, _ = _dims(cfg)
    flops = layers * (block_matmul_flops_per_token(cfg)
                      + attention_flops_per_token(cfg, context))
    return flops + (lm_head_flops_per_token(cfg) if sampled else 0)


def prompt_flops(cfg, plen):
    """Forward of a whole prompt, one sample at its end."""
    _, layers, _, _ = _dims(cfg)
    contexts = plen * (plen + 1) // 2          # 1 + 2 + ... + plen
    return (layers * (plen * block_matmul_flops_per_token(cfg)
                      + attention_flops_per_token(cfg, contexts))
            + lm_head_flops_per_token(cfg))


def prompt_work(cfg, plen):
    """What serving a prompt of `plen` tokens takes: all operations of its
    forward, those of them that are attention's, and the K,V rows the
    attention must read (each row at least once)."""
    return {"model_flops": prompt_flops(cfg, plen),
            "attention_flops": cfg["n_layer"] * attention_flops_per_token(
                cfg, plen * (plen + 1) // 2),
            "kv_rows": plen}


def token_work(cfg, context):
    """The same for one output token that attends to `context` keys."""
    return {"model_flops": serve_token_flops(cfg, context, sampled=True),
            "attention_flops": cfg["n_layer"] * attention_flops_per_token(
                cfg, context),
            "kv_rows": context}
