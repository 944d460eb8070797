from repro.configs.base import (  # noqa: F401
    DataConfig,
    FrontendConfig,
    LTFBConfig,
    MambaConfig,
    MeshConfig,
    ModelConfig,
    MoEConfig,
    OptimizerConfig,
    RunConfig,
    XLSTMConfig,
    replace,
)
