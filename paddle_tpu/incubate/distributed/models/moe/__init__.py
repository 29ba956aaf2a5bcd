"""MoE (reference: python/paddle/incubate/distributed/models/moe/)."""
from .moe_layer import MoELayer, ExpertLayer  # noqa: F401
from .dropless import DroplessMoELayer  # noqa: F401
from .gate import BaseGate, NaiveGate, GShardGate, SwitchGate  # noqa: F401
from .grad_clip import ClipGradForMOEByGlobalNorm  # noqa: F401
from .utils import global_scatter, global_gather  # noqa: F401
