"""DuetServe's primary contribution: the attention-aware roofline predictor,
the SM/chip partition optimizer (Algorithm 1), the adaptive multiplexing
controller, and the interruption-free look-ahead decode engine."""
from repro.core.roofline import (H100_LIKE, TPU_V5E, HardwareSpec, OpCost,
                                 RequestLoad, RooflineModel, hardware_spec)
from repro.core.partition import (PartitionConfig, ScheduleDecision, decide,
                                  optimize_partition)
from repro.core.multiplexer import AdaptiveMultiplexer, MultiplexerStats
from repro.core.lookahead import lookahead_decode, make_lookahead_fn
from repro.core.device import DeviceContext

__all__ = [
    "HardwareSpec", "OpCost", "RequestLoad", "RooflineModel", "TPU_V5E",
    "hardware_spec",
    "H100_LIKE", "PartitionConfig", "ScheduleDecision", "decide",
    "optimize_partition", "AdaptiveMultiplexer", "MultiplexerStats",
    "lookahead_decode", "make_lookahead_fn", "DeviceContext",
]
