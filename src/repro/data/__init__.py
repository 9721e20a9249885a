from .pipeline import DataConfig, SyntheticLM, make_fcn_batch, make_train_batch

__all__ = ["DataConfig", "SyntheticLM", "make_train_batch", "make_fcn_batch"]
