from repro.models.model import build_model, federated_lm_loss, Model

__all__ = ["build_model", "federated_lm_loss", "Model"]
