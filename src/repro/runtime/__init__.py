from .serve_loop import ServeConfig, Server, make_decode_fn, make_prefill_fn
from .tracing import op_scopes
from .train_loop import (SimulatedFailure, Trainer, TrainerConfig,
                         make_train_step, opt_spec_tree, shard_batch)

__all__ = ["ServeConfig", "Server", "SimulatedFailure", "Trainer",
           "TrainerConfig", "make_decode_fn", "make_prefill_fn",
           "make_train_step", "op_scopes", "opt_spec_tree", "shard_batch"]
