"""XLA compiled-program introspection, normalized.

What XLA already knows about a compiled training step is the cheapest
telemetry there is — it costs nothing at step time because it was computed at
compile time. This module is the one place that normalizes the two relevant
surfaces across jax versions and backends:

* ``cost_analysis`` / ``cost_flops`` — the compiled program's own FLOP count
  (jax returns a dict on some versions, a 1-list of dicts on others; some
  backends return nothing).  This is the number the live ``StepMonitor``
  MFU and the serving ledger's FLOPs probe read.
* ``memory_stats`` — ``compiled.memory_analysis()`` (XLA's
  ``CompiledMemoryStats``) flattened to plain ints: argument / output / temp /
  generated-code / alias bytes plus a derived ``peak_bytes`` watermark
  (arguments + outputs + temps + generated code − aliased), the HBM number a
  creeping-toward-OOM alert wants.  Backends with no CompiledMemoryStats fall
  back to the static estimator (analysis/hbm.py), tagged ``estimated=True``.
* ``device_peak_flops`` — per-chip dense bf16 peak (public TPU specs), the
  denominator of MFU.  ``None`` on CPU so MFU degrades to "absent", never to
  a made-up number; an accelerator whose ``device_kind`` is not in the table
  RAISES — a chip that reports ``mfu: null`` and "ok" hides the device.

The compiled-program readers are defensive: an introspection surface a
backend does not implement yields ``{}`` / ``0.0``, never an exception —
telemetry must not be able to take down the training loop it watches.
"""
from __future__ import annotations

__all__ = ["cost_analysis", "cost_flops", "memory_stats",
           "device_peak_flops", "PEAK_BF16_FLOPS",
           "device_ici_bandwidth", "ICI_BANDWIDTH_BYTES"]

# Per-chip peak bf16 TFLOP/s (dense), from public TPU specs. The single
# source of truth for the program's own gauges (the benchmark keeps its
# table, benchmarks/harness/peaks.py: ROADMAP Queue C).
PEAK_BF16_FLOPS = {
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def _per_chip(table, device, what):
    if device.platform == "cpu":
        return None
    kind = device.device_kind
    for name, value in table.items():
        if kind.startswith(name):
            return value
    raise ValueError(
        f"no {what} for device_kind {kind!r} (platform {device.platform!r}):"
        " add the chip to the table in observability/xla.py with its source")


def device_peak_flops(device) -> float | None:
    """Dense bf16 peak FLOP/s of `device`; None on CPU (MFU is reported only
    when the denominator is real). An accelerator missing from the table is
    a ValueError, not a silent None."""
    return _per_chip(PEAK_BF16_FLOPS, device, "bf16 peak FLOP/s")


# Per-chip aggregate ICI bandwidth in BYTES/s (public TPU specs: v3
# 6x112 Gbps/link ≈ 656 Gbps, v4 2400 Gbps, v5e 1600 Gbps, v5p 4800 Gbps,
# v6e/Trillium 3584 Gbps — bits on the spec sheet, bytes here). The
# bandwidth sibling of PEAK_BF16_FLOPS: the comms lint's comms-over-budget
# rule (analysis/comms.py) divides per-tick wire bytes by this.
ICI_BANDWIDTH_BYTES = {
    "TPU v3": 656e9 / 8,
    "TPU v4": 2400e9 / 8,
    "TPU v5 lite": 1600e9 / 8,
    "TPU v5e": 1600e9 / 8,
    "TPU v5p": 4800e9 / 8,
    "TPU v5": 4800e9 / 8,
    "TPU v6 lite": 3584e9 / 8,
    "TPU v6e": 3584e9 / 8,
}


def device_ici_bandwidth(device) -> float | None:
    """Per-chip ICI bandwidth of `device` in bytes/s; None on CPU, ValueError
    for an accelerator missing from the table — same contract as
    device_peak_flops."""
    return _per_chip(ICI_BANDWIDTH_BYTES, device, "ICI bandwidth")


def cost_analysis(compiled) -> dict:
    """`compiled.cost_analysis()` as a plain dict ({} when unavailable)."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost) if cost else {}


def cost_flops(compiled) -> float:
    """FLOPs of one execution of `compiled` per its own cost analysis
    (0.0 when the backend does not report them)."""
    try:
        return float(cost_analysis(compiled).get("flops", 0.0))
    except Exception:
        return 0.0


_MEM_FIELDS = {
    "argument": "argument_size_in_bytes",
    "output": "output_size_in_bytes",
    "temp": "temp_size_in_bytes",
    "generated_code": "generated_code_size_in_bytes",
    "alias": "alias_size_in_bytes",
}


def memory_stats(compiled, jaxpr=None) -> dict:
    """`compiled.memory_analysis()` flattened to ints.

    Keys: ``argument_bytes``, ``output_bytes``, ``temp_bytes``,
    ``generated_code_bytes``, ``alias_bytes`` and the derived watermark
    ``peak_bytes`` = argument + output + temp + generated_code − alias
    (aliased donated buffers are counted once).

    Backends with no ``CompiledMemoryStats`` fall back to the static
    estimator (analysis/hbm.py) instead of returning ``{}``: the full
    liveness walk when the caller passes the program's ``jaxpr``, else a
    degraded tier from the executable's aval/donation metadata alone.
    Fallback dicts carry ``estimated=True`` so dashboards can tell a real
    watermark from a model of one — either way,
    ``paddle_train_hbm_bytes{kind}`` stops reading zero on stats-less
    hosts. ``{}`` only when no surface yields anything."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is None:
        return _estimated_memory_stats(compiled, jaxpr)
    out = {}
    for key, attr in _MEM_FIELDS.items():
        try:
            out[f"{key}_bytes"] = int(getattr(ma, attr))
        except Exception:
            out[f"{key}_bytes"] = 0
    out["peak_bytes"] = max(0, out["argument_bytes"] + out["output_bytes"]
                            + out["temp_bytes"] + out["generated_code_bytes"]
                            - out["alias_bytes"])
    return out


def _estimated_memory_stats(compiled, jaxpr) -> dict:
    """The ``estimated=True`` degraded path, in its own frame so the lazy
    analysis import cannot shadow a real-stats failure (telemetry must not
    take down the loop it watches)."""
    try:
        from ..analysis.hbm import estimate_memory_stats

        return estimate_memory_stats(jaxpr, compiled=compiled)
    except Exception:
        return {}
