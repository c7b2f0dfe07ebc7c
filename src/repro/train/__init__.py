"""Training driver."""

from repro.train.trainer import TrainConfig, Trainer, make_train_step  # noqa: F401
