"""MoE package. Reference: python/paddle/incubate/distributed/models/moe/."""
from .gate import BaseGate, GShardGate, NaiveGate, SwitchGate  # noqa: F401
from .held_experts import HeldExperts  # noqa: F401
from .moe_layer import MoELayer  # noqa: F401

__all__ = ["MoELayer", "HeldExperts", "BaseGate", "NaiveGate", "GShardGate",
           "SwitchGate"]
