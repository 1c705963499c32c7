"""Share of the chip's bf16 peak that the train steps of the traced window
used, by MODEL operations: forward and backward products, the head, causal
attention, as the configuration's family counts them; nothing recomputed is
counted."""


def read(view):
    steps = view.records.get("traced_steps")
    if not steps:
        return None
    tokens = steps * view.records["batch"] * view.records["seq"]
    done = tokens * view.family.counts.train_flops_per_token(
        view.cfg, view.records["seq"])
    return 100.0 * done / (view.window_s * view.chips
                           * view.peaks["bf16_flops"])
