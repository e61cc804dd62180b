from .config import (DSAConfig, HybridConfig, MLAConfig, MoEConfig,
                     ModelConfig)
from .transformer import ModelApi, get_api, lm_loss_from_hidden

__all__ = ["DSAConfig", "HybridConfig", "MLAConfig", "MoEConfig", "ModelConfig",
           "ModelApi", "get_api", "lm_loss_from_hidden"]
