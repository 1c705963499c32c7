"""Operations and bytes of the fixture family, from the configuration and
the shapes alone: what every family gives."""


def _token_flops(cfg, context, sampled=True):
    h, inner = cfg["hidden_size"], cfg["intermediate_size"]
    block = 2 * (3 * h * h + h * h + 3 * h * inner)
    head = 2 * h * cfg["vocab_size"] if sampled else 0
    return cfg["num_hidden_layers"] * (block + 4 * h * context) + head


def token_work(cfg, context):
    return {"model_flops": _token_flops(cfg, context),
            "attention_flops": cfg["num_hidden_layers"] * 4
            * cfg["hidden_size"] * context,
            "kv_rows": context}


def prompt_work(cfg, plen):
    rows = [token_work(cfg, p) for p in range(1, plen + 1)]
    return {"model_flops": sum(_token_flops(cfg, p, sampled=(p == plen))
                               for p in range(1, plen + 1)),
            "attention_flops": sum(r["attention_flops"] for r in rows),
            "kv_rows": plen}


def kv_bytes_per_row(cfg, dtype_bytes=2):
    return 2 * cfg["num_hidden_layers"] * cfg["hidden_size"] * dtype_bytes


def train_flops_per_token(cfg, seq):
    return 3 * _token_flops(cfg, (seq + 1) / 2)
