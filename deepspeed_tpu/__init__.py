"""deepspeed_tpu — a TPU-native training & inference framework with the
capability surface of DeepSpeed (reference: xylian-site/DeepSpeed v0.17.6),
re-designed for JAX/XLA/Pallas and SPMD device meshes.

Public API parity (reference: deepspeed/__init__.py):
- `initialize()`        (:69)   -> TrainEngine with train_batch / fwd / bwd / step
- `init_inference()`    (:291)  -> InferenceEngine (tensor-parallel serving)
- `comm` as `dist`              -> deepspeed.comm analog over XLA collectives
- `DeepSpeedTPUConfig`          -> JSON config, DeepSpeed-compatible keys
"""
from __future__ import annotations

import time as _time
_IMPORT_T0 = _time.perf_counter_ns()    # the `host.import` span begins here

import argparse

__version__ = "0.1.0"

from .config.config import (DeepSpeedTPUConfig, ConfigError, ServingConfig,
                            FleetConfig, SupervisorConfig, AutoscaleConfig,
                            SpeculativeConfig, DisaggConfig)
from .parallel.mesh import MeshTopology, make_mesh
from .runtime.engine import TrainEngine, TrainState, initialize
from . import comm
from . import ops
from . import models
from .runtime import zero
from .runtime.zero import OnDevice  # reference: deepspeed.OnDevice
# BERT-era fused-layer API shim (reference: deepspeed/__init__.py:39)
from .ops.transformer import DeepSpeedTransformerConfig, DeepSpeedTransformerLayer
from .runtime.pipeline.module import PipelineModule, LayerSpec
from .runtime import activation_checkpointing as checkpointing
from . import moe

dist = comm  # reference idiom: `import deepspeed.comm as dist`

# what importing the package cost (jax included, where this is what first
# imports it), on the host-clock log of utils/spans.py
from .utils.spans import span
with span("host.import") as _imported:
    _imported.begun(_IMPORT_T0)
del span


def init_inference(*args, **kwargs):
    from .inference.engine import init_inference as _init
    return _init(*args, **kwargs)


def tp_model_init(*args, **kwargs):
    """AutoTP for training (reference: deepspeed/__init__.py:369)."""
    from .runtime.tensor_parallel import tp_model_init as _tp
    return _tp(*args, **kwargs)


def add_config_arguments(parser):
    """Attach the standard CLI flags to an argparse parser (reference:
    deepspeed/__init__.py:268 `add_config_arguments` — the `--deepspeed
    --deepspeed_config ds.json` glue user scripts rely on)."""
    group = parser.add_argument_group("DeepSpeed-TPU",
                                      "DeepSpeed-TPU configuration")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="enable the deepspeed_tpu engine")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="path to the JSON config file")
    # deprecated aliases fold into the new dests (reference :275-285 keeps
    # both; scripts read args.deepspeed/deepspeed_config)
    group.add_argument("--deepscale", dest="deepspeed", action="store_true",
                       help=argparse.SUPPRESS)
    group.add_argument("--deepscale_config", dest="deepspeed_config",
                       type=str, help=argparse.SUPPRESS)
    return parser
