"""repro.models — unified multi-family model zoo (see DESIGN.md §4)."""

from .config import SHAPES, ModelConfig, ShapeConfig
from .model import (
    Caches,
    decode_step,
    decode_step_ws,
    init_caches,
    loss_fn,
    prefill,
    shard_caches,
    ws_decode_supported,
)
from .sharding import param_shardings, shard, use_mesh
from .transformer import init_params

__all__ = [
    "Caches",
    "ModelConfig",
    "SHAPES",
    "ShapeConfig",
    "decode_step",
    "decode_step_ws",
    "init_caches",
    "init_params",
    "loss_fn",
    "param_shardings",
    "prefill",
    "shard",
    "shard_caches",
    "use_mesh",
    "ws_decode_supported",
]
