"""Utilities: flat-parameter handling, config, logging, metrics, checkpoint."""

from mpit_tpu.utils.params import (  # noqa: F401
    FlatParamSpec,
    flatten_params,
    unflatten_params,
    tree_zeros_like,
)
from mpit_tpu.utils.checkpoint import (  # noqa: F401
    save_checkpoint,
    restore_checkpoint,
    latest_checkpoint,
    list_checkpoints,
)
from mpit_tpu.utils.config import TrainConfig, PRESETS  # noqa: F401
from mpit_tpu.utils.metrics import MetricsLogger  # noqa: F401
from mpit_tpu.utils.profiling import (  # noqa: F401
    force_completion,
    span,
    trace,
)
