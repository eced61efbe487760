"""deepspeed_tpu — TPU-native training framework with DeepSpeed capabilities.

API facade mirroring /root/reference/deepspeed/__init__.py: the product is
`initialize()` (returns an engine wrapping the user model) plus a launcher,
re-designed for JAX/XLA: parallelism is a `jax.sharding.Mesh`, ZeRO stages
are sharding specs, kernels are Pallas/XLA.
"""

from .version import __version__, git_hash  # noqa: F401
from . import comm  # noqa: F401
from . import module_inject  # noqa: F401
from . import ops  # noqa: F401
from .comm import init_distributed  # noqa: F401
from .runtime.activation_checkpointing import checkpointing  # noqa: F401
from .runtime import zero  # noqa: F401
# top-level names a reference user reaches for (reference __init__.py:7-23)
from .runtime.engine import DeepSpeedEngine  # noqa: F401
from .runtime.pipe.engine import PipelineEngine  # noqa: F401
from .runtime.pipe.module import (PipelineModule, LayerSpec,  # noqa: F401
                                  TiedLayerSpec)
from . import pipe  # noqa: F401  (the deepspeed.pipe parity package —
#                    NOT runtime.pipe, which would shadow it)
from .runtime.lr_schedules import add_tuning_arguments  # noqa: F401
from .runtime.config import DeepSpeedConfig, DeepSpeedConfigError  # noqa: F401
from .runtime.constants import (ADAM_OPTIMIZER,  # noqa: F401
                                LAMB_OPTIMIZER)
from .ops.transformer import (DeepSpeedTransformerLayer,  # noqa: F401
                              DeepSpeedTransformerConfig)
from .utils.logging import log_dist  # noqa: F401

version = __version__
__git_hash__ = git_hash
__git_branch__ = "main"


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None):
    """Initialize the training engine (reference: deepspeed/__init__.py:52-145).

    Returns a tuple of ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    """
    from .runtime.engine import DeepSpeedEngine
    from .runtime.pipe.module import PipelineModule

    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)

    if isinstance(model, PipelineModule):
        from .runtime.pipe.engine import PipelineEngine

        engine = PipelineEngine(args=args,
                                model=model,
                                optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler,
                                mpu=model.mpu() if mpu is None else mpu,
                                dist_init_required=dist_init_required,
                                collate_fn=collate_fn,
                                config_params=config)
    else:
        engine = DeepSpeedEngine(args=args,
                                 model=model,
                                 optimizer=optimizer,
                                 model_parameters=model_parameters,
                                 training_data=training_data,
                                 lr_scheduler=lr_scheduler,
                                 mpu=mpu,
                                 dist_init_required=dist_init_required,
                                 collate_fn=collate_fn,
                                 config_params=config)

    return (engine, engine.optimizer, engine.training_dataloader,
            engine.lr_scheduler)


def add_config_arguments(parser):
    """Add --deepspeed / --deepspeed_config args (reference __init__.py:148-212)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag, parity only)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the deepspeed json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    group.add_argument("--deepscale_config", default=None, type=str,
                       help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse

    return argparse.SUPPRESS
