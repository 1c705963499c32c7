"""Share of the chip's bf16 peak that the step programs used for USEFUL
tokens in the traced window: prompt and output tokens of the benchmark's own
request records, counted by the configuration's family
(`serve_driver.traced_work`), no padding and no masked slot."""


def read(view):
    work = view.records.get("model_flops")
    if not work:
        return None
    return 100.0 * work / (view.window_s * view.chips
                           * view.peaks["bf16_flops"])
