"""Architecture configs: one module per assigned architecture + registry."""

from repro.configs.base import (  # noqa: F401
    ARCH_IDS,
    ArchConfig,
    EncoderConfig,
    MLAConfig,
    MoEConfig,
    SSMConfig,
    VisionStubConfig,
    XLSTMConfig,
    get_config,
    get_smoke,
    list_archs,
)
