"""Distribution: sharding rules, explicit collectives, gradient compression."""

from repro.distributed.sharding import (  # noqa: F401
    TRAIN_RULES,
    Rules,
    activation_rules,
    constrain,
    physical_spec,
)
