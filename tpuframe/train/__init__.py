"""Training engine: jitted steps, TrainState, high-level Trainer, algorithms.

TPU-native re-expression of the reference's L4 layer (SURVEY.md §1): the
Composer Trainer shape, the DDP epoch loop, Accelerate's low-level step feel,
and Ray Train's structured results, all on one donated jitted XLA step.
"""

from tpuframe.train.algorithms import (
    Algorithm,
    ChannelsLast,
    CutMix,
    LabelSmoothing,
    MixUp,
    apply_algorithms,
    resolve_algorithms,
)
from tpuframe.train.callbacks import Callback, EarlyStopping, ProgressLogger
from tpuframe.train.duration import Duration
from tpuframe.train.schedules import (
    cosine_annealing,
    step_decay,
    warmup_cosine,
    warmup_decay_lr,
    warmup_lr,
)
from tpuframe.train.optim import optimizer_from_config
from tpuframe.train.schedules import from_config as schedule_from_config
from tpuframe.train.ema import EmaState, ema_params, with_ema
from tpuframe.train.state import TrainState, create_train_state, param_count
from tpuframe.train.step import (
    cross_entropy,
    make_eval_step,
    make_grad_accum_step,
    make_predict_fn,
    make_train_step,
    merge_metrics,
    model_objective,
    ModelObjective,
    summarize_metrics,
)
from tpuframe.train.trainer import FitResult, Trainer

__all__ = [
    "Algorithm",
    "ChannelsLast",
    "EmaState",
    "ema_params",
    "with_ema",
    "CutMix",
    "LabelSmoothing",
    "MixUp",
    "apply_algorithms",
    "resolve_algorithms",
    "Callback",
    "EarlyStopping",
    "ProgressLogger",
    "Duration",
    "warmup_lr",
    "warmup_decay_lr",
    "warmup_cosine",
    "cosine_annealing",
    "step_decay",
    "schedule_from_config",
    "optimizer_from_config",
    "TrainState",
    "create_train_state",
    "param_count",
    "cross_entropy",
    "model_objective",
    "ModelObjective",
    "make_eval_step",
    "make_grad_accum_step",
    "make_predict_fn",
    "make_train_step",
    "merge_metrics",
    "summarize_metrics",
    "FitResult",
    "Trainer",
]
