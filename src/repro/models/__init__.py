from repro.models.model import (
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_fn,
    param_count,
    prefill,
    shared_sites,
)

__all__ = ["decode_step", "forward", "init_cache", "init_params",
           "loss_fn", "param_count", "prefill", "shared_sites"]
