from .policy_trainer import (PolicyTrainer, PolicyTrainerConfig,
                             SimulatedFailure, TransitionDataset,
                             train_policy_state)

__all__ = ["SimulatedFailure", "PolicyTrainer", "PolicyTrainerConfig",
           "TransitionDataset", "train_policy_state"]
